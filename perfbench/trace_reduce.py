"""From a profiler trace to busy/idle, per-name sums and gaps by host span.

Two halves.  `read_xplane` turns the `.xplane.pb` the JAX profiler writes into
plain intervals with nothing but `jax.profiler.ProfileData`.  Everything after
that is pure arithmetic on `(start, end)` pairs in seconds, tested on synthetic
events (tests/perfbench/test_perfbench_trace.py) and on a trace recorded on the
chip (perfbench/fixtures/).

What the trace of a v5e looks like (looked at by hand in PR 23, see PERF.md):
one plane per chip named `/device:TPU:<n>` whose line `XLA Ops` holds one event
per executed HLO operation; the host plane `/host:CPU` holds one line per
thread, and a `jax.profiler.TraceAnnotation` is an event on its thread's line
under the name it was given.  Device and host events share one clock.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


# ------------------------------------------------------ interval arithmetic

def union(intervals):
    """Merge overlapping `(start, end)` pairs; returns them sorted."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in intervals)


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def intersect(a, b):
    """Intersection of two MERGED interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy, lo, hi):
    """The idle intervals of a MERGED busy list inside the window."""
    out, at = [], lo
    for s, e in clip(busy, lo, hi):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def idle_share(busy, lo, hi):
    """1 - busy/window, in [0, 1]."""
    if hi <= lo:
        raise ValueError("empty window")
    return 1.0 - total(clip(union(busy), lo, hi)) / (hi - lo)


def sums_by_name(events, lo, hi):
    """Seconds per event name inside the window, longest first.
    `events` are `(name, start, end)`."""
    acc = {}
    for name, s, e in events:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            acc[name] = acc.get(name, 0.0) + d
    return sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))


def self_events(events):
    """Events of ONE timeline with nested children cut out of their parents:
    an operation that encloses others (a `while` around its body) keeps only
    the time in which none of them ran, so per-name sums add up to the busy
    time instead of counting it once per level.  Returns `(name, seconds)`
    pairs, one per event."""
    out, stack = [], []   # stack of [name, end, self seconds]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _end, own = stack.pop()
            out.append((name, own))

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][1]) - s
        stack.append([name, e, e - s])
    close(float("inf"))
    return out


def self_sums_by_name(events, lo, hi):
    """Self seconds per name over the events that START inside the window."""
    acc = {}
    for name, own in self_events([ev for ev in events if lo <= ev[1] < hi]):
        acc[name] = acc.get(name, 0.0) + own
    return sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))


def span_at(spans, t, none="none"):
    """Name of the innermost host span open at time t (the one that started
    last).  `spans` are `(name, start, end)`."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else none


def gaps_by_span(busy, spans, lo, hi):
    """Idle seconds per host span: every idle gap of the device goes to the
    span open on the host at the gap's middle.  Longest first."""
    acc = {}
    for s, e in gaps(union(busy), lo, hi):
        name = span_at(spans, (s + e) / 2)
        acc[name] = acc.get(name, 0.0) + (e - s)
    return sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))


def busy_inside(busy, spans, names, lo, hi):
    """Device-busy seconds that fall inside host spans of the given names."""
    inside = union((s, e) for name, s, e in spans if name in names)
    return total(intersect(clip(union(busy), lo, hi), inside))


# ------------------------------------------------------------- the reader

class Trace:
    """A trace reduced to intervals in seconds on the trace's own clock.

    device_ops: {chip index: [(name, start, end), ...]} from each device
    plane's op line; host_events: [(name, start, end)] from every thread of
    the host plane; seen: [(plane, line, events)] for the printed summary."""

    def __init__(self, device_ops, host_events, seen):
        self.device_ops = device_ops
        self.host_events = host_events
        self.seen = seen

    def spans(self, names):
        return [ev for ev in self.host_events if ev[0] in names]

    def busy(self, chip):
        return union((s, e) for _n, s, e in self.device_ops[chip])

    def window(self, span_names=None):
        """The traced window: from the first to the last event of the named
        host spans (the benchmark's own), else of the device ops."""
        evs = self.spans(span_names) if span_names else []
        if not evs:
            evs = [ev for ops in self.device_ops.values() for ev in ops]
        if not evs:
            raise ValueError("the trace holds neither spans nor device ops")
        return min(s for _n, s, _e in evs), max(e for _n, _s, e in evs)


_LAYOUT = re.compile(r"\{[^}]*\}")
_INSTRUCTION = re.compile(r"^(\(.*?\)|\S+) ([\w\-]+)\(")


def short_name(name, width=96):
    """The trace prints a device operation as its whole HLO instruction
    (`%fusion.225 = (bf16[4096,32768]{...}, ...) fusion(...), kind=kOutput`).
    Keep what the same operation of another layer shares: the instruction's
    base name without its number, its kind, and its result shape without
    layouts — so that per-name sums group the layers of a model."""
    if " = " not in name:
        return name[:width]
    lhs, rhs = name.split(" = ", 1)
    base = re.sub(r"[.\d]+$", "", lhs.lstrip("%"))
    kind = rhs.split("kind=", 1)[1].split(",", 1)[0] if "kind=" in rhs else ""
    m = _INSTRUCTION.match(_LAYOUT.sub("", rhs))
    result, op = (m.group(1), m.group(2)) if m else ("", "")
    head = " ".join(x for x in (base, op if op != base else "", kind) if x)
    return f"{head} -> {result}"[:width].rstrip()


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def read_xplane(path, keep_host=None):
    """Read one `.xplane.pb`.  `keep_host` (a set of names) limits the host
    events kept; None keeps all."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops, host_events, seen = {}, [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            n = 0
            if m and line.name == OP_LINE:
                ops = device_ops.setdefault(int(m.group(1)), [])
                for ev in line.events:
                    s = ev.start_ns * 1e-9
                    ops.append((short_name(ev.name), s,
                                s + ev.duration_ns * 1e-9))
                    n += 1
            elif plane.name == HOST_PLANE:
                for ev in line.events:
                    n += 1
                    if keep_host is None or ev.name in keep_host:
                        s = ev.start_ns * 1e-9
                        host_events.append(
                            (ev.name, s, s + ev.duration_ns * 1e-9))
            else:
                n = sum(1 for _ in line.events)
            seen.append((plane.name, line.name, n))
    return Trace(device_ops, host_events, seen)


def reduce_trace(trace, span_names, top=10):
    """What a traced run reports: busy and window seconds averaged over the
    chips used, the top device operations, the idle gaps by host span."""
    if not trace.device_ops:
        return None
    lo, hi = trace.window(span_names)
    spans = trace.spans(span_names)
    chips = sorted(trace.device_ops)
    busy_s = sum(total(clip(trace.busy(c), lo, hi)) for c in chips) / len(chips)
    acc = {}
    for c in chips:   # self time, so that a loop does not hide its body
        for name, sec in self_sums_by_name(trace.device_ops[c], lo, hi):
            acc[name] = acc.get(name, 0.0) + sec
    ops = sorted(acc.items(), key=lambda kv: (-kv[1], kv[0]))
    gap = {}
    for c in chips:
        for name, sec in gaps_by_span(trace.busy(c), spans, lo, hi):
            gap[name] = gap.get(name, 0.0) + sec / len(chips)
    return {
        "busy_s": busy_s,
        "window_s": hi - lo,
        "device_ops": [[n, s / len(chips)] for n, s in ops[:top]],
        "idle_gaps": [[n, s] for n, s in
                      sorted(gap.items(), key=lambda kv: -kv[1])[:top]],
    }
