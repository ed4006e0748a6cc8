"""`serve_closed` for a configuration with attention in a convolved latent, an
MLP router carried across depth and top-1 experts with a skip choice
(`families/cca_moe.py`): the same loop, window and comparison, and beside them

- among its facts, what the family's rooflines need: from the program's own
  counters (`serving.decode_stats()`, counted on the device over the window's
  decode steps) the held experts that received a token and the assignments to
  held experts, each a mean per layer step; from the loop itself the prompt
  lengths of the admissions inside the traced part of the window (the k-th
  `add_request` span is the k-th request of the cell's fixed order), and the
  share of prefill assignments that chose a held expert (the rest chose
  skip); under the names the accepted metric files read: `held_experts`,
  `decode_chunk`, `rows`;
- among its checks, the three that `serve_closed_swa_moe` makes, made by ITS
  functions on this family's reference.  NEAR TIES APART: the router is
  top-1, and a token whose OWN first and second selection scores (p + beta;
  the accepted functions' messages say "router logits") lie within
  `check.tie_tau` of each other in some layer may be handed another expert's
  output, or skip, by the rounding of the program's bfloat16 hidden state:
  another result, not a less precise one.  The reference says which tokens
  those are, by its own margin (`logits_and_near_ties`), and such a token is
  held to `check.tie_margin_sigma` (a row of logits to `check.tie_logit_
  sigma`).  ROWS OF LOGITS from the timed engine when the window has closed:
  every slot's state a slot is then many steps old and has served several
  requests.  ROUTING: the program's router (`models.cca_moe.route_mlp`),
  handed the reference's own router inputs (m and the carried r) for one
  prompt, must make the reference's choice for at least
  `check.routing_agreement` of the (token, layer) pairs."""

from __future__ import annotations

from perfbench import traffic
from perfbench.drivers import serve_closed
from perfbench.drivers.serve_closed_moe import _Remembering
from perfbench.drivers.serve_closed_swa_moe import (_Kept, _Noting,
                                                    _TieNoting, logit_rows,
                                                    ties_apart)


def run(ctx) -> dict:
    ctx.family = fam = _Remembering(ctx.family)
    ctx.tracer = tracer = _Noting(ctx.tracer)
    ck = ctx.cell["check"]
    noting = _TieNoting(ctx.reference(), ck["tie_tau"])
    ctx.reference = lambda: noting
    serving, serve_closed.Serving = serve_closed.Serving, _Kept
    try:
        out = serve_closed.run(ctx)
    finally:
        serve_closed.Serving = serving
        del ctx.reference
    sv, _Kept.last = _Kept.last, None
    out["checks"] = ties_apart(ck, out["checks"], noting.calls, sv.requests)
    out["checks"].update(logit_rows(ctx, sv))
    d = out["counters"]["decode_stats"]
    steps = d.get("moe_layer_steps", 0)
    if not steps:
        raise RuntimeError("no expert-layer step was counted in the window: "
                           "this driver is for configurations with experts")
    _first, held = fam.held_experts(ctx.config)
    # the k-th add_request span is the k-th request of the fixed order
    loop = traffic.ClosedLoop(ctx.cell["traffic"])
    spans = ctx.rec.spans.get("add_request", [])
    order = [r.prompt_len for r in loop.first_wave()]
    order += [loop.next_request().prompt_len for _ in spans[len(order):]]
    lo = tracer.started_at if tracer.started_at is not None else out["window"][0]
    admitted = [n for (s, e), n in zip(spans, order)
                if lo <= s and e <= out["window"][1]]
    out["facts"].update(
        held_experts=held,
        moe_touched_per_layer_step=d["moe_experts_touched"] / steps,
        moe_held_per_layer_step=d["moe_held_assignments"] / steps,
        admitted_prompt_lens=admitted,
        moe_prefill_held_share=(d["moe_prefill_held_assignments"]
                                / max(1, d["moe_prefill_assignments"])))
    ctx.say(f"expert load over the window's {steps} layer steps: "
            f"{d['moe_held_assignments']} of {d['moe_assignments']} rows chose "
            f"an expert held here, {d['moe_skipped']} chose skip; "
            f"{d['moe_experts_touched'] / steps:.2f} of {held} held experts "
            f"touched a step, busiest expert "
            f"{d['moe_peak_expert_assignments'] / steps:.2f} rows a step; "
            f"prefill: {d['moe_prefill_held_assignments']} of "
            f"{d['moe_prefill_assignments']} held, "
            f"{d['moe_prefill_skipped']} skipped; state a slot "
            f"{sv._decode_stats()['slot_state_bytes']} B; "
            f"{len(admitted)} admissions in the traced part, prompts "
            f"{sorted(set(admitted))}")
    ids = traffic.prompt_tokens(ctx.seed, 0, ck["routing_prompt"],
                                ctx.config["vocab_size"])
    share, pairs = fam.routing_agreement(
        fam.model, fam.reference_weights(fam.model),
        fam.reference_sizes(ctx.config), ids, ctx.reference())
    out["checks"][
        f"the program's router, on the reference's own router inputs, makes "
        f"the reference's choice for {share:.4f} of {pairs} (token, layer) "
        f"pairs (at least {ck['routing_agreement']})"
    ] = share >= ck["routing_agreement"]
    return out
