"""`serve_closed` for a configuration with routed experts: the same loop,
window and comparison, and beside them

- among its facts, what the expert layer's roofline needs from the program's
  own counters (`serving.decode_stats()`, counted on the device over the
  window's decode steps): held experts that received at least one token, and
  assignments to held experts, each a mean per expert-layer step;
- among its checks, one that a comparison of tokens cannot make: the
  program's router, handed the reference's own router inputs for one prompt
  (`check.routing_prompt` tokens), must choose the reference's experts for
  at least `check.routing_agreement` of the (token, expert layer) pairs
  (`families/mla_moe.routing_agreement`).  A token of the stream has the
  reference's top logit whether the router and the softmax ran in float32 or
  in bfloat16 (PERF.md section 6, PR 27); the experts chosen do not."""

from __future__ import annotations

from perfbench import roofline_mla_moe, traffic
from perfbench.drivers import serve_closed


class _Remembering:
    """The family, keeping hold of the model it builds: `serve_closed` keeps
    its engine and model to itself."""

    def __init__(self, family):
        self._family, self.model = family, None

    def __getattr__(self, name):
        return getattr(self._family, name)

    def build(self, *args, **kw):
        self.model = self._family.build(*args, **kw)
        return self.model


def run(ctx) -> dict:
    ctx.family = fam = _Remembering(ctx.family)
    out = serve_closed.run(ctx)
    d = out["counters"]["decode_stats"]
    steps = d.get("moe_layer_steps", 0)
    if not steps:
        raise RuntimeError("no expert-layer step was counted in the window: "
                           "this driver is for configurations with experts")
    held = ctx.config["n_routed_experts"]
    out["facts"].update(
        held_experts=held,
        moe_touched_per_layer_step=d["moe_experts_touched"] / steps,
        moe_held_per_layer_step=d["moe_held_assignments"] / steps)
    ctx.say(f"expert load over the window's {steps} expert-layer steps: "
            f"{d['moe_held_assignments']} of {d['moe_assignments']} assignments "
            f"held here ({d['moe_held_assignments'] / max(1, d['moe_assignments']):.4f};"
            f" 1/{roofline_mla_moe.routed_experts(ctx.config) // held}"
            f" is even), {d['moe_experts_touched'] / steps:.2f} of {held} held "
            f"experts touched a step, busiest expert "
            f"{d['moe_peak_expert_assignments'] / steps:.2f} tokens a step; "
            f"prefill: {d.get('moe_prefill_held_assignments', 0)} of "
            f"{d.get('moe_prefill_assignments', 0)} held")
    ck = ctx.cell["check"]
    ids = traffic.prompt_tokens(ctx.seed, 0, ck["routing_prompt"],
                                ctx.config["vocab_size"])
    share, pairs = fam.routing_agreement(
        fam.model, fam.reference_weights(fam.model),
        fam.reference_sizes(ctx.config), ids, ctx.reference())
    out["checks"][
        f"the program's router, on the reference's own router inputs, chooses "
        f"the reference's experts for {share:.4f} of {pairs} (token, expert "
        f"layer) pairs (at least {ck['routing_agreement']})"
    ] = share >= ck["routing_agreement"]
    return out
