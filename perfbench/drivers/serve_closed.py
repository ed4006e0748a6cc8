"""Closed-loop serving: `clients` callers, one per engine slot, each sending
its next request the moment its last one finished, so every slot stays full.

Set-up admits the first wave (whose lengths cover every shape the loop can
send, so nothing compiles later) and takes `warm_steps` steps; then the
window opens.  The window holds a FIXED AMOUNT OF WORK: `steps_per_second` x
`--seconds` loop iterations (refill every free slot, then one engine step),
a number from the cell's file sized so that the window lasts about
`--seconds` on the tree that defined the cell.  Lengths come in a fixed
order, so the same requests are admitted and finish in every run, and only
the time they take differs.  `serve_tok_s` is every token those iterations
emitted over the time they took; `tpot_p90_ms` is over the requests that
finished in them.

Why work and not the clock: an admission takes 2 s and emits one token, a
step 0.9 s and emits 256, so tokens against time is a staircase.  A window
cut at a fixed instant read 113.756 tokens/s exactly whenever the cut fell
into an admission, and 2.5 times the timing's change whenever it fell into
a step; the driver's two sets of the same code stood 2.5% apart with next to
no spread inside either (PR 23, refused).  Over fixed work the rate moves by
exactly as much as the time does.
"""

from __future__ import annotations

import time

from perfbench import stats, traffic
from perfbench.drivers._serve import Serving, report_requests


def run(ctx) -> dict:
    tr = ctx.cell["traffic"]
    sv = Serving(ctx)
    loop = traffic.ClosedLoop(tr)
    block = sv.p["block_size"]
    shape = lambda pr, out: (pr, -(-(pr + out) // block))  # noqa: E731
    first = loop.first_wave()
    missing = ({shape(*s) for s in loop.shapes()}
               - {shape(r.prompt_len, r.max_new) for r in first})
    if missing:
        raise ValueError(f"the first wave leaves shapes cold: {sorted(missing)}")
    t = time.perf_counter()
    for r in first:
        sv.send(r)
    ctx.say(f"first wave of {len(first)} admitted in "
            f"{time.perf_counter() - t:.1f} s")
    t = time.perf_counter()
    for _ in range(tr["warm_steps"]):
        sv.step()
    ctx.say(f"{tr['warm_steps']} warm steps in {time.perf_counter() - t:.1f} s")

    n_steps = max(1, round(ctx.seconds * tr["steps_per_second"]))
    trace_from = max(0, n_steps - max(1, round(
        ctx.cell.get("trace_seconds", 3.0) * tr["steps_per_second"])))
    t_open = sv.open_window()
    t_trace = t_open
    counted = []
    for i in range(n_steps):
        if ctx.tracer.enabled and i == trace_from:
            t_trace = time.perf_counter()
            ctx.tracer.start()
        while len(sv.in_flight) < loop.clients:
            r = loop.next_request()
            counted.append(r)
            sv.send(r)
        sv.step()
    t_close = time.perf_counter()       # step() returns with its tokens read
    ctx.tracer.stop()

    tokens = sum(n for s, n in sv.emissions if s >= t_open)
    done = [r for r in sv.requests.values()
            if r.t_done is not None and t_open <= r.t_done <= t_close]
    tpots = [v for v in (stats.tpot_ms(r, not_before=t_open) for r in done)
             if v is not None]
    failed = sum(r.refused for r in counted)
    report_requests(ctx, sv, counted, t_open, t_close, failed)
    ctx.say(f"samples: {n_steps} iterations, {tokens} tokens; tpot over {len(tpots)} requests "
            f"finished in the window ({stats.samples_beyond(len(tpots), 90)}"
            f" beyond the 90th percentile), median "
            f"{stats.median(tpots):.3f} ms")
    checks = sv.check_against_reference(done)
    checks["every request sent in the window was accepted"] = failed == 0
    return {
        "checks": checks,
        "attempted": len(counted), "failed": failed,
        "window": (t_open, t_close),
        "end_to_end": {"serve_tok_s": tokens / (t_close - t_open),
                       "tpot_p90_ms": stats.percentile(tpots, 90)},
        "counters": sv.counters(),
        "facts": sv.facts(t_trace, t_close),
    }
