"""The per-layer metrics that read what the PROGRAM records about itself:
its spans (`paddle_tpu.profiler.SPAN_NAMES`), its admission counters
(`serving.decode_stats`), its compile seconds and its kernels' names.

    python3 -m perfbench.program_metrics --workload <cell> --seed <n> --seconds <s> --trace 1

runs the cell exactly as `perfbench.run` does and prints the same line with
these metrics beside the accepted ones.  It is a command of its own because
PR 25 (`tracing`) could add files to the benchmark but edit none: a cell's
`per_layer` list lives in its cell file, and the readers in `run.read_metric`.
`program_metrics.json` holds, per cell, the names a `benchmark` issue appends
to that list (and to `BENCHMARK.json`); the two reductions below then move to
`trace_reduce.py` and `roofline.py`, their branches and the wider `keep_host`
into `run.py`, and this file goes (PERF.md section 7).

On a program that records none of this (the parent of PR 25) every reader
here finds nothing, returns None, and the metric is left out of the line.
"""

from __future__ import annotations

import re
import sys

from perfbench import roofline, run, trace_reduce
from perfbench.spans import SPAN_NAMES

_accepted_run_cell = run.run_cell


# ------------------------------------------- for trace_reduce.py, one day

def idle_inside(busy, spans, names, lo, hi):
    """Device-idle seconds of the window that fall inside host spans of the
    given names (the complement of `trace_reduce.busy_inside`)."""
    inside = trace_reduce.union((s, e) for name, s, e in spans if name in names)
    idle = trace_reduce.gaps(trace_reduce.union(busy), lo, hi)
    return trace_reduce.total(trace_reduce.intersect(idle, inside))


# ---------------------------------------------- for roofline.py, one day

def train_attention_flops_per_token(cfg, seq: int) -> float:
    """The attention term of `roofline.train_flops_per_token`: QK^T and PV
    over the (seq+1)/2 keys a token meets on average, 2 FLOPs a
    multiply-add, backward twice the forward."""
    attn_width = cfg["num_attention_heads"] * roofline.head_dim(cfg)
    return cfg["num_hidden_layers"] * 3 * 2 * 2 * attn_width * (seq + 1) / 2


def train_attention_min_s(cfg, facts, device_kind) -> float:
    """Least seconds the attention of one train step could take: its
    required FLOPs at peak (FLOP-bound: at 4096 tokens the kernels' bytes
    are a hundredth of that time at 819 GB/s)."""
    tokens = facts["batch"] * facts["seq"]
    return (tokens * train_attention_flops_per_token(cfg, facts["seq"])
            / roofline.peaks(device_kind)["flops_bf16"])


FUNCTIONS = {"train_attention_min_s": train_attention_min_s}


# ------------------------------------------- for run.read_metric, one day

def read_metric(spec, out, rec, trace, facts, device_kind):
    """`run.read_metric` with three additions: a counter expression whose
    counter is missing or whose denominator is zero reads None instead of
    raising; the trace reduction `idle_in_span`; a roofline `over` the
    device operations whose name matches `op_match`."""
    r = spec["reader"]
    if r["source"] == "counter":
        try:
            return run.read_metric(spec, out, rec, trace, facts, device_kind)
        except (KeyError, ZeroDivisionError):
            return None
    new = (r["source"] == "trace" and r["reduce"] == "idle_in_span"
           or r["source"] == "roofline" and "op_match" in r["over"])
    if not new:
        return run.read_metric(spec, out, rec, trace, facts, device_kind)
    if trace is None or not trace.device_ops:
        return None
    lo, hi = trace.window(SPAN_NAMES)
    chips = sorted(trace.device_ops)
    if r["source"] == "trace":
        spans = trace.spans({r["span"]})
        if not spans:
            return None
        idle = sum(idle_inside(trace.busy(c), spans, {r["span"]}, lo, hi)
                   for c in chips) / len(chips)
        return 100.0 * idle / (hi - lo)
    over = r["over"]
    units = len(trace.spans({over["per_span"]}))
    pat = re.compile(over["op_match"])
    spent = sum(s for c in chips
                for n, s in trace_reduce.sums_by_name(trace.device_ops[c], lo, hi)
                if pat.search(n)) / len(chips)
    if not units or not spent or device_kind is None:
        return None
    least = FUNCTIONS[r["fn"]](out["config"], facts, device_kind)
    return 100.0 * least / (spent / units)


def wanted_spans(specs):
    """Every host span a metric file names, for `read_xplane(keep_host=...)`."""
    names = set()
    for spec in specs.values():
        r = spec["reader"]
        names.update(v for v in (r.get("span"), r.get("over", {}).get("per_span"),
                                 r.get("over", {}).get("busy_in_span")) if v)
    return names


def say_window_counters(counters):
    """The window's compile seconds and its admissions on earlier lines,
    in a traced run and a plain one alike (match phase and whole attempt
    included; neither is a metric)."""
    c = counters.get("compile_stats", {})
    if "compile_seconds" in c:
        run.say(f"window, programs first used inside it: {c.get('compiles')}, "
                f"traced in {c.get('trace_seconds', 0.0):.3f} s, compiled or "
                f"read from the persistent cache in {c['compile_seconds']:.3f} s")
    from paddle_tpu.profiler.statistics import decode_line

    # the operator's own line (docs/DECODE.md "Reading an admission"), over
    # the window's deltas; the parent's decode_line has no such line
    for text in decode_line(counters.get("decode_stats", {})).splitlines():
        if text.startswith("Admission split"):
            run.say("window, a" + text[1:])


# ------------------------------------------------------------ one cell, once

def run_cell(root, workload, seed, seconds, trace, trace_dir=None):
    """`run.run_cell`, its traced line extended by the metrics that
    `<root>/program_metrics.json` names for the cell."""
    extra = run._load(root, "", "program_metrics").get(workload, [])
    specs = {m: run._load(root, "layer_metrics", m) for m in extra}
    accepted_finish = run.finish

    def finish(ctx, out, devices):
        line = accepted_finish(ctx, out, devices)
        say_window_counters(out.get("counters", {}))
        if not ctx.trace:
            return line
        tr = None
        if ctx.tracer.stopped:   # read again: the accepted read keeps its own spans only
            tr = trace_reduce.read_xplane(
                trace_reduce.find_xplane(ctx.tracer.out_dir),
                keep_host=set(SPAN_NAMES) | wanted_spans(specs))
        kind = ctx.device.device_kind if ctx.peaks else None
        for name, spec in specs.items():
            v = read_metric(spec, out, ctx.rec, tr, out.get("facts", {}), kind)
            if v is not None:
                line["metrics"][name] = {"value": v, "unit": spec["unit"]}
        return line

    run.finish = finish
    try:
        return _accepted_run_cell(root, workload, seed, seconds, trace,
                                  trace_dir)
    finally:
        run.finish = accepted_finish


def main(argv=None) -> int:
    """`run.main` (same arguments, same refusals), with this `run_cell`."""
    run.run_cell = run_cell
    try:
        return run.main(argv)
    finally:
        run.run_cell = _accepted_run_cell


if __name__ == "__main__":
    sys.exit(main())
