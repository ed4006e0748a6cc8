"""`serve_closed` for a configuration of latent attention with a learned sparse
indexer on its full layers, a latent window on the others and routed experts
(`families/dsa_moe.py`): the same loop, window and comparison, and beside them

- among its facts, what the family's rooflines need: from the program's own
  counters (`serving.decode_stats()`, counted on the device over the window's
  decode steps) the held experts that received a token and the assignments to
  held experts, each a mean per expert-layer step, and per token step the
  live positions of the full layers (the rows' lengths), the positions the
  full layers' attention read (the selection, counted from it) and the live
  window positions (min(len, W) summed over the rows); from the loop itself
  the prompt lengths of the admissions inside the traced part of the window
  (the k-th `add_request` span is the k-th request of the cell's fixed
  order), and the share of prefill assignments
  that chose a held expert; under the names the accepted metric files read:
  `held_experts`, `decode_chunk`, `rows`;
- among its checks, those `serve_closed_swa_moe` makes, made by ITS
  functions on this family's reference, and one more.  NEAR TIES APART: a
  checked token whose OWN k-th and (k+1)-th choice values (s + b) lie within
  `check.tie_tau` of each other in some expert layer, one of the two experts
  held here, or whose own index_topk-th and (index_topk + 1)-th indexer
  scores lie within `check.index_tie_tau` of each other (relative) in some
  full layer, may be handed another expert's output or another position by
  the rounding of the program's bfloat16 hidden state: the reference says
  which tokens those are (`logits_and_near_ties`), and such a token is held
  to `check.tie_margin_sigma` while every other token stays at the other
  serving cells' `margin_sigma`.  ROWS OF LOGITS from the timed engine when
  the window has closed (`check.logit_rows` resident rows against
  `check.logit_sigma`).  ROUTING: the program's router on the reference's
  own router inputs (`check.routing_agreement`).  SELECTION: the program's
  indexer choice (`models.dsa_moe.index_scores` and `index_select`), handed
  the reference's own indexer queries, weights and keys for one prompt of
  `check.index_prompt` tokens, must select at least `check.index_agreement`
  of the positions the reference selects (`families/dsa_moe.index_
  agreement`).  ATTENTION: each layer's attention sublayer on the program's
  decode path (its own projections, cache write, indexer and selection or
  ring and window, read, gate and W_o), handed the REFERENCE's own input of
  that layer for the same prompt, at `check.attention_positions`, must
  stand within `check.attention_sigma` of the reference's sublayer output
  on the sliding layers and on the full layers where every position is
  chosen, and within `check.selected_attention_sigma` where the full
  layers choose (`families/dsa_moe.attention_agreement`): a fault of the
  window, the gate or the selection in any one layer is read there, where
  a row of logits reads it through every layer's rounding."""

from __future__ import annotations

import numpy as np

from perfbench import traffic
from perfbench.drivers import serve_closed
from perfbench.drivers.serve_closed_moe import _Remembering
from perfbench.drivers.serve_closed_swa_moe import (_Kept, _Noting,
                                                    _TieNoting, logit_rows,
                                                    ties_apart)


class _IndexTieNoting(_TieNoting):
    """`_TieNoting`, the indexer's near ties (`check.index_tie_tau`) noted
    beside the router's."""

    def __init__(self, reference, tau, index_tau):
        super().__init__(reference, tau)
        self._index_tau = index_tau

    def logits_at(self, weights, sizes, ids, positions):
        rows, ties = self._reference.logits_and_near_ties(
            weights, sizes, ids, positions, self._tau, self._index_tau)
        rows = np.asarray(rows)
        self.calls.append((rows, np.asarray(ties)))
        return rows


def agreements(ctx) -> dict:
    """The checks made on the reference's own inputs (module docstring):
    routing, the indexer's selection, and each layer's attention on its
    decode path.  `ctx.family.reference_sizes` says what the reference
    computes (a control tool hands it one that computes something else)."""
    fam, ck = ctx.family, ctx.cell["check"]
    weights = fam.reference_weights(fam.model)
    sizes = fam.reference_sizes(ctx.config)
    checks = {}
    ids = traffic.prompt_tokens(ctx.seed, 0, ck["routing_prompt"],
                                ctx.config["vocab_size"])
    share, pairs = fam.routing_agreement(fam.model, weights, sizes, ids,
                                         ctx.reference())
    checks[
        f"the program's router, on the reference's own router inputs, chooses "
        f"the reference's experts for {share:.4f} of {pairs} (token, expert "
        f"layer) pairs (at least {ck['routing_agreement']})"
    ] = share >= ck["routing_agreement"]
    ids = traffic.prompt_tokens(ctx.seed, 1, ck["index_prompt"],
                                ctx.config["vocab_size"])
    share, positions = fam.index_agreement(fam.model, weights, sizes, ids,
                                           ctx.reference())
    checks[
        f"the program's indexer, on the reference's own indexer inputs, selects "
        f"{share:.6f} of the reference's {positions} selected (query, position) "
        f"pairs (at least {ck['index_agreement']})"
    ] = share >= ck["index_agreement"]
    at = ck["attention_positions"]
    top_k = ctx.config["index_topk"]
    worst = {}
    for i, kind, readings in fam.attention_agreement(
            fam.model, weights, sizes, ids, ctx.reference(), at,
            ctx.cell["engine"]["block_size"]):
        ctx.say(f"attention of layer {i} ({kind}) on the reference's own "
                f"inputs at positions {at}: "
                + " ".join(f"{r:.4f}" for r in readings) + " sigma")
        for t, r in zip(at, readings):
            part = ("the sliding layers (their ring and window)"
                    if kind == "sliding" else
                    "the full layers, every position chosen" if t < top_k
                    else "the full layers, through the indexer's own choice")
            worst[part] = max(worst.get(part, 0.0), r)
    ctx.say("attention on the reference's own layer inputs, worst by part: "
            + "; ".join(f"{k} {v:.4f} sigma" for k, v in worst.items()))
    for part, r in worst.items():
        limit = ck["selected_attention_sigma" if "own choice" in part
                   else "attention_sigma"]
        checks[
            f"the program's decode attention of {part}, on the reference's "
            f"own layer inputs at positions {at} of one prompt of "
            f"{len(ids)}, stands {r:.4f} sigma from the reference's (limit "
            f"{limit})"] = r <= limit
    return checks


def run(ctx) -> dict:
    ctx.family = fam = _Remembering(ctx.family)
    ctx.tracer = tracer = _Noting(ctx.tracer)
    ck = ctx.cell["check"]
    noting = _IndexTieNoting(ctx.reference(), ck["tie_tau"], ck["index_tie_tau"])
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
    token_steps = d["macro_steps"] * d["last_chunk"]
    if not steps or not d.get("attn_indexer_positions_read"):
        raise RuntimeError("no expert-layer step or no indexer read was counted "
                           "in the window: this driver is for configurations "
                           "with experts and a sparse indexer")
    _first, held = fam.held_experts(ctx.config)
    full_live = d["attn_positions_live"] - d["attn_window_positions_live"]
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
        live_index_positions=full_live / token_steps,
        selected_positions=d["attn_sparse_positions_read"] / token_steps,
        live_window_positions=d["attn_window_positions_live"] / token_steps,
        admitted_prompt_lens=admitted,
        moe_prefill_held_share=(d["moe_prefill_held_assignments"]
                                / max(1, d["moe_prefill_assignments"])))
    ctx.say(f"expert load over the window's {steps} expert-layer steps: "
            f"{d['moe_held_assignments']} of {d['moe_assignments']} assignments "
            f"held here ({d['moe_held_assignments'] / max(1, d['moe_assignments']):.4f};"
            f" 1/{fam.routed_experts(ctx.config) // held} is even), "
            f"{d['moe_experts_touched'] / steps:.2f} of {held} held experts "
            f"touched a step, busiest expert "
            f"{d['moe_peak_expert_assignments'] / steps:.2f} tokens a step; "
            f"prefill: {d['moe_prefill_held_assignments']} of "
            f"{d['moe_prefill_assignments']} held")
    ctx.say(f"attention over the window's {token_steps} token steps: the "
            f"indexer read {d['attn_indexer_positions_read']} keys for "
            f"{full_live} live positions "
            f"({d['attn_indexer_positions_read'] / max(1, full_live):.4f} of "
            f"the live: a row's whole table), the full layers read "
            f"{d['attn_sparse_positions_read']} selected "
            f"({d['attn_sparse_positions_read'] / max(1, full_live):.4f}"
            f" of the live), the rings {d['attn_window_positions_read']} "
            f"({d['attn_window_positions_live']} live); prefill pairs scored "
            f"{d['dsa_prefill_pairs_scored']}, selected "
            f"{d['dsa_prefill_pairs_selected']}; {len(admitted)} admissions in "
            f"the traced part, prompts {sorted(set(admitted))}")
    out["checks"].update(agreements(ctx))
    return out
