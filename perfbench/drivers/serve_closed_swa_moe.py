"""`serve_closed` for a configuration with window and full attention mixed and
routed experts (`families/window_moe.py`): the same loop, window and
comparison, and beside them

- among its facts, what the family's rooflines need: from the program's own
  counters (`serving.decode_stats()`, counted on the device over the window's
  decode steps) the held experts that received a token and the assignments to
  held experts, each a mean per expert-layer step, and the live window
  positions (min(len, W) summed over the rows) a token step; from the loop
  itself the prompt lengths of the admissions inside the traced part of the
  window (the k-th `add_request` span is the k-th request of the cell's fixed
  order), and the share of prefill assignments that chose a held expert;
- under the names the accepted metric files read: `held_experts`,
  `decode_chunk`, `rows`;
- among its checks, three that `serve_closed`'s comparison of tokens cannot
  make.  NEAR TIES APART: a checked token whose OWN k-th and (k+1)-th router
  logits lie within `check.tie_tau` of each other in some expert layer, one
  of the two experts held here, may be handed another expert's output by the
  rounding of the program's bfloat16 hidden state (another result, not a
  less precise one): the reference says which tokens those are
  (`logits_and_near_ties`), and such a token is held to
  `check.tie_margin_sigma` while every other token stays at the other
  serving cells' `margin_sigma`.  ROWS OF LOGITS from the timed engine: when
  the window has closed, with the rows that did not just finish still live
  (20 of the cell's 32) and every ring wrapped, the
  next-token logits of `check.logit_rows` resident rows
  (`GenerationEngine.next_token_logits`: the macro-step's decode over the
  pools the timed programs wrote) must stand within `check.logit_sigma`
  standard deviations of the reference's row (max |program - reference| over
  the vocabulary; `check.tie_logit_sigma` for a near tie of the row's own
  routing).  ROUTING: the program's router, handed the reference's own
  router inputs for one prompt (`check.routing_prompt` tokens), must choose
  the reference's experts for at least `check.routing_agreement` of the
  (token, expert layer) pairs (`families/window_moe.routing_agreement`)."""

from __future__ import annotations

import re
import time

import numpy as np

from perfbench import traffic
from perfbench.drivers import _serve, serve_closed
from perfbench.drivers.serve_closed_moe import _Remembering

_TOKEN_CHECK = re.compile(r"^(\S+) \(prompt \d+\) token (\d+): ")


class _Noting:
    """The tracer, noting when it was started: the traced part of the window
    begins there."""

    def __init__(self, tracer):
        self._tracer, self.started_at = tracer, None

    def __getattr__(self, name):
        return getattr(self._tracer, name)

    def start(self):
        self.started_at = time.perf_counter()
        self._tracer.start()


class _Kept(_serve.Serving):
    """`Serving`, kept hold of: `serve_closed.run` keeps its engine and its
    requests to itself, and the rows of logits need both."""

    last = None

    def __init__(self, ctx):
        super().__init__(ctx)
        _Kept.last = self


class _TieNoting:
    """The reference, noting beside every row of logits it hands out whether
    the row's own routing is a near tie (`logits_and_near_ties`)."""

    def __init__(self, reference, tau):
        self._reference, self._tau, self.calls = reference, tau, []

    def __getattr__(self, name):
        return getattr(self._reference, name)

    def logits_at(self, weights, sizes, ids, positions):
        rows, ties = self._reference.logits_and_near_ties(
            weights, sizes, ids, positions, self._tau)
        rows = np.asarray(rows)
        self.calls.append((rows, np.asarray(ties)))
        return rows


def ties_apart(ck, checks, noted, requests) -> dict:
    """`serve_closed`'s token checks, those of a near tie re-judged at
    `tie_margin_sigma`: the reference's calls `noted` are in the order of the
    requests in `checks`, a row a checked position."""
    out, order = {}, []
    for what, ok in checks.items():
        m = _TOKEN_CHECK.match(what)
        if not m:
            out[what] = ok
            continue
        rid, k = m.group(1), int(m.group(2)) - 1
        if rid not in order:
            order.append(rid)
        rows, ties = noted[order.index(rid)]
        j = ck["positions"].index(k)
        if not ties[j]:
            out[what] = ok
            continue
        row = rows[j]
        gap = float(row.max() - row[requests[rid].tokens[k]]) / float(row.std())
        out[f"{m.group(0)}a NEAR TIE of its own routing (router logits within "
            f"{ck['tie_tau']}, one of the two experts held here), counted "
            f"apart: reference logit {gap:.4f} sigma under the maximum "
            f"(margin {ck['tie_margin_sigma']})"] = gap <= ck["tie_margin_sigma"]
    return out


def logit_rows(ctx, sv) -> dict:
    """Rows of logits from the engine as the window left it against the
    reference's (module docstring).  Rows that have decoded 9 to 128 tokens
    are taken: at least one whole macro-step through the rings since their
    admission, and the reference's pieces compile for the lengths the token
    check already used (prompt + 128)."""
    ck = ctx.cell["check"]
    fam, ref = ctx.family, ctx.reference()
    got = sv.engine.next_token_logits()
    live = sorted((sv.requests[rid] for rid in got), key=lambda r: r.rid)
    pool = [r for r in live if 9 <= len(r.tokens) <= 128] or live
    if not pool:
        return {"a resident row to compare with the reference": False}
    picked = [pool[int(i)] for i in traffic.rng(ctx.seed, 31).choice(
        len(pool), min(ck["logit_rows"], len(pool)), False)]
    weights = fam.reference_weights(sv.model)
    sizes = fam.reference_sizes(ctx.config)
    checks, errors = {}, []
    for r in picked:
        ids = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        at = len(ids) - 1
        ids = np.pad(ids, (0, max(0, ck["pad_to"] - len(ids))))
        want, tie = ref.logits_and_near_ties(weights, sizes, ids, [at],
                                             ck["tie_tau"])
        want, tie = np.asarray(want)[0], bool(np.asarray(tie)[0])
        err = float(np.abs(got[r.rid] - want).max() / want.std())
        errors.append(err)
        limit = ck["tie_logit_sigma"] if tie else ck["logit_sigma"]
        checks[f"{r.rid} (prompt {r.prompt_len}) after {len(r.tokens)} tokens, "
               f"in the engine as the window left it: its next logits stand "
               f"{err:.4f} sigma from the reference's row (limit {limit}"
               + (", a NEAR TIE of its own routing)" if tie else ")")
               ] = err <= limit
    ctx.say(f"reference: {len(errors)} rows of logits from the resident engine "
            f"({len(live)} rows live), worst {max(errors):.4f} sigma")
    return checks


def run(ctx) -> dict:
    ctx.family = fam = _Remembering(ctx.family)
    ctx.tracer = tracer = _Noting(ctx.tracer)
    ck = ctx.cell["check"]
    plain = ctx.reference()
    noting = _TieNoting(plain, ck["tie_tau"])
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
    if not steps or not d.get("attn_window_positions_read"):
        raise RuntimeError("no expert-layer step or no window read was counted "
                           "in the window: this driver is for configurations "
                           "with experts and a window class")
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
    ctx.say(f"attention over the window's {token_steps} token steps: "
            f"{d['attn_full_positions_read']} paged + "
            f"{d['attn_window_positions_read']} ring positions read, "
            f"{d['attn_positions_live']} live "
            f"({d['attn_window_positions_live']} of them in a window): read "
            f"amplification {d['attn_positions_read'] / max(1, d['attn_positions_live']):.3f};"
            f" {len(admitted)} admissions in the traced part, prompts "
            f"{sorted(set(admitted))}")
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
