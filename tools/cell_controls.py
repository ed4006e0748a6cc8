"""Controls that must FAIL, read through the harness's own comparison.

Runs a cell of the `window_moe` or the `cca_moe` family once through
`perfbench.run.run_cell` (drivers `serve_closed_swa_moe`, `serve_closed_cca_
moe`), then calls that driver's OWN comparisons — the rows of logits from the
engine as the window left it, and the routing limit — once more against
references that compute something else on purpose (the controls of
`perfbench/reference_window_moe.py` / `reference_cca_moe.py`), on the same
engine and with the cell's own limits.  A comparison that reads `correct True`
for a control cannot tell that mechanism or precision and is too loose for it:

    chiprun --timeout 1700 -- python tools/cell_controls.py \\
        --workload serve-laguna-decode-ctx8k --seed 2147487001
    chiprun --timeout 1700 -- python tools/cell_controls.py \\
        --workload serve-zaya-decode-ctx8k --seed 2147487001

PERF.md section 6 (PR 31, PR 34) has the readings; the cell files quote them.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CONTROLS = (("none (the reference as it is)", {}),
            ("window ignored", {"ignore_window": True}),
            ("gate left out", {"no_gate": True}),
            ("softmax in bfloat16", {"softmax_dtype": "bfloat16"}),
            ("router in bfloat16", {"router_dtype": "bfloat16"}),
            ("everything in bfloat16", {"dtype": "bfloat16"}))
# what must read NOT correct of them (the softmax alone no end-to-end row can
# tell: chip_smoke.py's window_softmax_probe holds it)
MUST_FAIL = ("window ignored", "gate left out", "router in bfloat16",
             "everything in bfloat16")
# a cell's driver -> (its controls, those that must read NOT correct)
CCA_CONTROLS = (("none (the reference as it is)", {}),
                ("depthwise conv left out", {"no_conv0": True}),
                ("value shift left out", {"no_shift": True}),
                ("depth carry left out", {"no_carry": True}),
                ("skip computed as zero", {"skip_zero": True}),
                ("router in bfloat16", {"router_dtype": "bfloat16"}),
                ("everything in bfloat16", {"dtype": "bfloat16"}))
BY_DRIVER = {
    "serve_closed_swa_moe": (CONTROLS, MUST_FAIL),
    "serve_closed_cca_moe": (CCA_CONTROLS,
                             tuple(name for name, _c in CCA_CONTROLS[1:])),
}


class _Controlled:
    """A family whose reference is told to compute something else."""

    def __init__(self, family, control):
        self._family, self._control = family, control

    def __getattr__(self, name):
        return getattr(self._family, name)

    def reference_sizes(self, cfg):
        return {**self._family.reference_sizes(cfg), **self._control}


def run(root, workload, seed, seconds, rows=1, say=print) -> dict:
    """{control: (correct, {check: verdict})}, after one run of the cell."""
    import importlib

    from perfbench import run as harness
    from perfbench import traffic

    name = harness.load_cell(root, workload)["driver"]
    controls, _must_fail = BY_DRIVER[name]
    driver = importlib.import_module(f"perfbench.drivers.{name}")
    kept = {}
    compare = driver.logit_rows

    def keeping(ctx, sv):
        kept.update(ctx=ctx, sv=sv)
        return compare(ctx, sv)

    driver.logit_rows = keeping
    try:
        line = harness.run_cell(root, workload, seed, seconds, False)
    finally:
        driver.logit_rows = compare
    say(json.dumps(line))
    ctx, sv = kept["ctx"], kept["sv"]
    fam, ck = ctx.family, dict(ctx.cell["check"])
    ids = traffic.prompt_tokens(ctx.seed, 0, ck["routing_prompt"],
                                ctx.config["vocab_size"])
    ctx.cell["check"] = {**ck, "logit_rows": rows}
    out = {}
    for name, control in controls:
        t0 = time.perf_counter()
        ctx.family = _Controlled(fam, control)
        checks = dict(compare(ctx, sv))
        share, pairs = fam.routing_agreement(
            fam.model, fam.reference_weights(fam.model),
            ctx.family.reference_sizes(ctx.config), ids, ctx.reference())
        checks[f"routing {share:.4f} of {pairs} (at least "
               f"{ck['routing_agreement']})"] = share >= ck["routing_agreement"]
        out[name] = (all(checks.values()), checks)
        say(f"[control] {name}: correct {out[name][0]} "
            f"({time.perf_counter() - t0:.1f} s)")
        for what, ok in checks.items():
            say(f"[control]    {'ok' if ok else 'FAILED'}: {what}")
    return out


def main(argv=None) -> int:
    from perfbench import run as harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=harness.HERE)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--rows", type=int, default=1,
                    help="rows of logits compared per control")
    a = ap.parse_args(argv)
    out = run(a.root, a.workload, a.seed, a.seconds, a.rows)
    # the reference as it is must pass; the mechanisms left out must not
    controls, must_fail = BY_DRIVER[harness.load_cell(a.root, a.workload)["driver"]]
    return 0 if out[controls[0][0]][0] and not any(
        out[name][0] for name in must_fail) else 1


if __name__ == "__main__":
    sys.exit(main())
