"""Open-loop serving: requests are due at seeded times at a fixed rate and
are sent whether or not earlier ones have finished; each is timed from when
it was DUE.

Set-up admits the ramp — one request of every distinct (prompt, output) pair
of the window's multiset, `ramp_per_step` per engine step, so every shape is
compiled and the engine is loaded as in steady state when the window opens;
ramp requests still running then are background load and are not counted.
The ramp is paced in steps, not seconds, so a compiling run reaches the same
state as a warm one.  After the window closes nothing new is sent, and the
requests due inside it are followed to their end, at most `drain_cap_s`; one
the engine refused or that has not finished by then counts in `failed` and
misses every percentile.
"""

from __future__ import annotations

import time

from perfbench import stats, traffic
from perfbench.drivers._serve import Serving, report_requests


def run(ctx) -> dict:
    tr = ctx.cell["traffic"]
    sv = Serving(ctx)
    ramp, window = traffic.open_loop(tr, ctx.seconds, ctx.seed)
    t = time.perf_counter()
    todo = list(ramp)
    while todo or sv.engine.pending_requests():
        for r in todo[:tr["ramp_per_step"]]:
            sv.send(r)
        del todo[:tr["ramp_per_step"]]
        sv.step()
    ctx.say(f"ramp: {len(ramp)} requests (every distinct shape) admitted in "
            f"{time.perf_counter() - t:.1f} s; {len(sv.in_flight)} still "
            "running as the window opens")

    t_open = sv.open_window()
    nxt = 0
    while True:
        now = time.perf_counter() - t_open
        if now >= ctx.seconds:
            break
        if ctx.tracer.due(now):
            ctx.tracer.start()
        while nxt < len(window) and window[nxt].due <= now:
            sv.send(window[nxt])
            nxt += 1
            now = time.perf_counter() - t_open
        if sv.engine.has_work():
            sv.step()
        else:
            until = window[nxt].due if nxt < len(window) else ctx.seconds
            with ctx.rec.span("traffic.wait"):
                time.sleep(max(0.0, min(until, ctx.seconds)
                               - (time.perf_counter() - t_open)))
    t_close = time.perf_counter()
    ctx.tracer.stop()
    counters = sv.counters()
    facts = sv.facts(t_open + ctx.tracer.start_at, t_close)
    waiting = sum(r.t_first is None for r in window[:nxt])
    backlog = len(sv.engine.pending_requests())
    for r in window[nxt:]:          # due in the window's last instants
        sv.send(r)
    t_cap = t_close + tr["drain_cap_s"]
    while (any(r.t_done is None and not r.refused for r in window)
           and time.perf_counter() < t_cap and sv.engine.has_work()):
        sv.step()
    ctx.say(f"followed the window's requests for "
            f"{time.perf_counter() - t_close:.1f} s after it closed")

    failed = sum(r.t_done is None for r in window)
    ttfts = [v for v in (stats.ttft_ms(r, t_open) for r in window)
             if v is not None]
    done_in = [r for r in sv.requests.values()
               if r.t_done is not None and t_open <= r.t_done <= t_close]
    tpots = [v for v in (stats.tpot_ms(r, not_before=t_open)
                         for r in done_in) if v is not None]
    report_requests(ctx, sv, window, t_open, t_close, failed)
    ctx.say(f"samples: ttft over {len(window)} requests due in the window "
            f"({stats.samples_beyond(len(window), 90)} beyond the 90th "
            f"percentile), median {stats.median(ttfts):.2f} ms; tpot over "
            f"{len(tpots)} requests finished in the window "
            f"({stats.samples_beyond(len(tpots), 90)} beyond), median "
            f"{stats.median(tpots):.3f} ms")
    ctx.say(f"offered {len(window) / ctx.seconds:.3f} requests/s; at the "
            f"window's close {waiting} of the requests sent had no first "
            f"token yet, engine queue {backlog}, {len(window) - nxt} due but "
            "not yet sent")
    checks = sv.check_against_reference(
        [r for r in window if r.t_done is not None])
    checks["every request due in the window finished"] = failed == 0
    return {
        "checks": checks,
        "attempted": len(window), "failed": failed,
        "window": (t_open, t_close),
        "end_to_end": {
            "ttft_p90_ms": stats.percentile(
                ttfts, 90, missed=len(window) - len(ttfts)),
            "tpot_p90_ms": stats.percentile(tpots, 90)},
        "counters": counters,
        "facts": facts,
    }
