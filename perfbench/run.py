"""Run one cell once and print its result line.

    python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` and, in a traced run, `breakdown`.  With
`--trace 0` the metrics are the cell's end-to-end metrics, taken with no
profiler and no annotation; with `--trace 1` they are its per-layer metrics,
and a few seconds at the end of the window are traced by `jax.profiler`.

The command refuses to measure unless jax's first device is a TPU and the
chips the cell asks for are visible: there is no CPU route through `main`.
The tests call `run_cell` with tiny files instead.
"""

from __future__ import annotations

import time

_T_PROCESS = time.perf_counter()   # as early as this module can know

import argparse  # noqa: E402
import ast  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import operator  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

from perfbench import roofline, stats, trace_reduce  # noqa: E402
from perfbench.spans import SPAN_NAMES, Recorder  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))


def say(msg: str):
    print(f"[perfbench] {msg}", flush=True)


# ------------------------------------------------------------- the files

def _load(root, kind, name):
    path = os.path.join(root, kind, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> dict:
    """A cell, its configuration and its per-layer metrics, all found by
    name under `root` (workloads/, configs/, layer_metrics/)."""
    cell = _load(root, "workloads", name)
    cell["name"] = name
    cell["config_file"] = _load(root, "configs", cell["config"])
    cell["metric_files"] = {m: _load(root, "layer_metrics", m)
                            for m in cell["per_layer"]}
    return cell


# -------------------------------------------------------- the device trace

class DeviceTracer:
    """Traces the last `seconds` of the window with `jax.profiler`."""

    def __init__(self, enabled, out_dir, start_at):
        self.enabled, self.out_dir, self.start_at = enabled, out_dir, start_at
        self.started = self.stopped = False
        self.start_cost = self.stop_cost = 0.0

    def due(self, t_rel):
        return self.enabled and not self.started and t_rel >= self.start_at

    def start(self):
        import jax

        shutil.rmtree(self.out_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # no event per Python call
        opts.host_tracer_level = 1     # TraceAnnotations, no runtime detail
        opts.enable_hlo_proto = False  # the programs' protos are megabytes
        t = time.perf_counter()
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        self.start_cost = time.perf_counter() - t
        self.started = True

    def stop(self):
        if self.started and not self.stopped:
            import jax

            t = time.perf_counter()
            jax.profiler.stop_trace()
            self.stop_cost = time.perf_counter() - t
            self.stopped = True


# ------------------------------------------------------------- the context

@dataclass
class Context:
    cell: dict
    config: dict
    family: object
    seed: int
    seconds: float
    trace: bool
    rec: Recorder
    tracer: DeviceTracer
    device: object
    peaks: dict | None
    setup_s: float | None = None
    say: object = staticmethod(say)

    def reference(self):
        return importlib.import_module(self.family.REFERENCE)

    def open_window(self) -> float:
        """Set-up is over: everything before this instant is `setup_s`."""
        now = time.perf_counter()
        self.setup_s = now - _T_PROCESS
        return now


# ------------------------------------------------------ per-layer readers

_OPS = {ast.Add: operator.add, ast.Sub: operator.sub, ast.Mult: operator.mul,
        ast.Div: operator.truediv, ast.USub: operator.neg}


def evaluate(expr: str, names: dict) -> float:
    """Arithmetic over named numbers: + - * / and parentheses, nothing else."""
    def ev(node):
        if isinstance(node, ast.Expression):
            return ev(node.body)
        if isinstance(node, ast.Constant) and isinstance(node.value, (int, float)):
            return node.value
        if isinstance(node, ast.Name):
            return names[node.id]
        if isinstance(node, ast.BinOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](ev(node.left), ev(node.right))
        if isinstance(node, ast.UnaryOp) and type(node.op) in _OPS:
            return _OPS[type(node.op)](ev(node.operand))
        raise ValueError(f"not arithmetic: {ast.dump(node)}")
    return float(ev(ast.parse(expr, mode="eval")))


def _span_stat(durations, stat):
    if stat == "median_ms":
        return stats.median(durations) * 1e3
    if stat == "p90_ms":
        return stats.percentile(durations, 90) * 1e3
    raise ValueError(f"unknown span stat {stat!r}")


def read_metric(spec: dict, out: dict, rec: Recorder, trace, facts: dict,
                device_kind: str | None):
    """One per-layer metric from what the run left behind; None when there
    is nothing to read (the metric is then left out of the line)."""
    r = spec["reader"]
    src = r["source"]
    if src == "span":
        lo, hi = out["window"]
        d = [e - s for s, e in rec.within(r["span"], lo, hi)]
        return _span_stat(d, r["stat"]) if d else None
    if src == "counter":
        names = dict(out.get("counters", {}).get(r["fn"]) or {})
        if not names:
            return None
        names.update({k: v for k, v in facts.items()
                      if isinstance(v, (int, float))})
        return evaluate(r["expr"], names)
    if trace is None or not trace.device_ops:
        return None
    lo, hi = trace.window(SPAN_NAMES)
    spans = trace.spans(SPAN_NAMES)
    chips = sorted(trace.device_ops)

    def mean_over_chips(fn):
        return sum(fn(trace.busy(c)) for c in chips) / len(chips)

    def busy(within):
        if within is None:
            return mean_over_chips(
                lambda b: trace_reduce.total(trace_reduce.clip(b, lo, hi)))
        return mean_over_chips(
            lambda b: trace_reduce.busy_inside(b, spans, {within}, lo, hi))

    if src == "trace":
        if r["reduce"] == "idle_share":
            return 100.0 * mean_over_chips(
                lambda b: trace_reduce.idle_share(b, lo, hi))
        if r["reduce"] == "busy_in_span":
            whole = busy(None)
            return 100.0 * busy(r["span"]) / whole if whole else None
        if r["reduce"] == "op_time":
            pat = re.compile(r["match"])
            return 1e3 * sum(
                s for n, s in trace_reduce.sums_by_name(
                    (ev for c in chips for ev in trace.device_ops[c]), lo, hi)
                if pat.search(n)) / len(chips)
        raise ValueError(f"unknown trace reduction {r['reduce']!r}")
    if src == "roofline":
        # least time per unit of work over device-busy time per unit
        over = r["over"]
        units = sum(1 for n, _s, _e in spans if n == over["per_span"])
        units *= facts.get(over["times"], 1) if "times" in over else 1
        spent = busy(over.get("busy_in_span"))
        if not units or not spent or device_kind is None:
            return None
        least = roofline.FUNCTIONS[r["fn"]](out["config"], facts, device_kind)
        return 100.0 * least / (spent / units)
    raise ValueError(f"unknown metric source {src!r}")


# ------------------------------------------------------------ one cell, once

def device_record(devices, n_chips, reduced):
    peak = 0
    for d in devices[:n_chips]:
        peak = max(peak, (d.memory_stats() or {}).get("peak_bytes_in_use", 0))
    rec = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices), "memory_peak_bytes": peak}
    if reduced:
        rec["busy_s"], rec["window_s"] = reduced["busy_s"], reduced["window_s"]
    return rec


def run_cell(root: str, workload: str, seed: int, seconds: float, trace: bool,
             trace_dir: str | None = None) -> dict:
    """Run the cell named `workload` found under `root`; returns the result
    object (the caller prints it).  No platform check here: `main` makes it."""
    import jax

    from paddle_tpu._core import compile_cache

    cell = load_cell(root, workload)
    config = cell["config_file"]
    devices = jax.devices()
    dev = devices[0]
    cache_dir = compile_cache.enable()   # the program's one rule; no directory named here
    say(f"cell {workload}: config {cell['config']}, driver {cell['driver']}, "
        f"seed {seed}, {seconds:g} s, trace {int(trace)}; {dev.device_kind} "
        f"({dev.platform}) x {len(devices)}; compile cache {cache_dir}")
    peaks = roofline.PEAKS.get(dev.device_kind)
    trace_dir = trace_dir or os.path.join(os.getcwd(), "chiprun_out",
                                          "traces", workload)
    t_len = float(cell.get("trace_seconds", 3.0))
    ctx = Context(
        cell=cell, config=config,
        family=importlib.import_module(f"perfbench.families.{config['family']}"),
        seed=seed, seconds=seconds, trace=trace, rec=Recorder(annotate=trace),
        tracer=DeviceTracer(trace, trace_dir, max(0.0, seconds - t_len)),
        device=dev, peaks=peaks)
    driver = importlib.import_module(f"perfbench.drivers.{cell['driver']}")
    out = driver.run(ctx)
    out["config"] = config
    return finish(ctx, out, devices)


def finish(ctx: Context, out: dict, devices) -> dict:
    cell = ctx.cell
    for what, ok in out["checks"].items():
        say(("ok: " if ok else "FAILED: ") + what)
    stats_mem = ctx.device.memory_stats() or {}
    if "peak_bytes_in_use" in stats_mem and stats_mem.get("bytes_limit"):
        say(f"peak device memory {stats_mem['peak_bytes_in_use'] / 2**30:.2f} "
            f"GiB of {stats_mem['bytes_limit'] / 2**30:.2f} GiB "
            f"({100 * stats_mem['peak_bytes_in_use'] / stats_mem['bytes_limit']:.1f}%)")
    cs = out.get("counters", {}).get("compile_stats", {})
    say(f"programs first used inside the window (compiled or read from the "
        f"persistent cache): {cs.get('compiles')}"
        + (f"; persistent cache {cs['persistent_cache_hits']} hits, "
           f"{cs['persistent_cache_misses']} misses (a miss compiled)"
           if "persistent_cache_misses" in cs else ""))
    e2e = dict(out["end_to_end"])
    e2e["setup_s"] = ctx.setup_s
    units = cell["units"]
    reduced = None
    if not ctx.trace:
        metrics = {k: {"value": e2e[k], "unit": units[k]}
                   for k in cell["end_to_end"]}
    else:
        trace = None
        if ctx.tracer.stopped:
            trace = trace_reduce.read_xplane(
                trace_reduce.find_xplane(ctx.tracer.out_dir),
                keep_host=set(SPAN_NAMES))
            say("trace: " + "; ".join(f"{p}|{l}|{n}" for p, l, n in trace.seen
                                      if n)[:1500])
            reduced = trace_reduce.reduce_trace(trace, SPAN_NAMES)
            say(f"tracing cost the host {ctx.tracer.start_cost:.2f} s to start "
                f"(inside the window) and {ctx.tracer.stop_cost:.2f} s to stop "
                "(after it)")
        say("end-to-end readings of this TRACED run (not reported as metrics): "
            + ", ".join(f"{k} {v:.4f}" for k, v in e2e.items()))
        metrics = {}
        kind = ctx.device.device_kind if ctx.peaks else None
        for name, spec in cell["metric_files"].items():
            v = read_metric(spec, out, ctx.rec, trace, out.get("facts", {}), kind)
            if v is not None:
                metrics[name] = {"value": v, "unit": spec["unit"]}
    bad = [k for k, m in metrics.items() if not math.isfinite(m["value"])]
    if bad:
        raise RuntimeError(f"{bad} undefined: too many requests failed")
    line = {
        "correct": all(out["checks"].values()),
        "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics,
        "device": device_record(devices, cell["chips"], reduced),
    }
    if reduced:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(HERE, args.workload)   # a wrong name fails before jax
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"perfbench: jax's first device is {devices[0]}, not a TPU; "
              "this command has no CPU route", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"perfbench: cell {args.workload} needs {cell['chips']} chips, "
              f"jax sees {len(devices)}", file=sys.stderr)
        return 2
    roofline.peaks(devices[0].device_kind)   # an unknown chip is an error
    line = run_cell(HERE, args.workload, args.seed, args.seconds,
                    bool(args.trace))
    if args.trace and "busy_s" not in line["device"]:
        print("perfbench: the traced run found no device operations",
              file=sys.stderr)
        return 3
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
