"""What the two serving drivers share: the engine built from the cell's
settings, one single-threaded loop body (send, step, book-keeping per
request), and the comparison with the plain reference that decides `correct`.

Cell parameters (`engine`): max_batch, block_size, num_blocks; every other
flag of `GenerationEngine` stays at its default (atomic prefill, decode chunk
8, prefix cache off, bf16 pool).  `check`: sample (requests compared),
positions (which of the engine's tokens, 0-based), margin_sigma.
"""

from __future__ import annotations

import time

import numpy as np

from perfbench import stats, traffic


class Serving:
    def __init__(self, ctx):
        import jax

        from paddle_tpu import profiler, serving

        self.ctx, self.rec = ctx, ctx.rec
        self.cfg, self.p = ctx.config, ctx.cell["engine"]
        self._decode_stats = serving.decode_stats
        self._compile_stats = profiler.compile_stats
        t = time.perf_counter()
        self.model = ctx.family.build(self.cfg, ctx.seed, training=False)
        jax.block_until_ready([q._value for q in self.model.parameters()])
        ctx.say(f"model built in {time.perf_counter() - t:.1f} s")
        t = time.perf_counter()
        self.engine = serving.GenerationEngine(
            self.model, max_batch=self.p["max_batch"],
            block_size=self.p["block_size"], num_blocks=self.p["num_blocks"])
        ctx.say(f"engine built in {time.perf_counter() - t:.1f} s; pool "
                f"{serving.decode_stats()['pool_bytes'] / 1e9:.2f} GB")
        self.requests: dict = {}     # rid -> Request, everything ever sent
        self.in_flight: set = set()
        self.sent = 0
        self.live = []    # (time, rows, live KV tokens, engine queue) per step
        self.emissions = []   # (start of the call, tokens it returned)
        self._c0 = self._d0 = self.t_open = None

    # ------------------------------------------------------------ the loop
    def send(self, req):
        req.prompt = traffic.prompt_tokens(self.ctx.seed, self.sent,
                                           req.prompt_len,
                                           self.cfg["vocab_size"])
        self.sent += 1
        self.requests[req.rid] = req
        req.t_call = time.perf_counter()
        try:
            with self.rec.span("add_request"):
                first = self.engine.add_request(req.rid, req.prompt,
                                                max_new_tokens=req.max_new)
        except RuntimeError as e:     # wider than the per-sequence table
            req.refused = True
            self.ctx.say(f"request {req.rid} refused: {e}")
            return
        self.in_flight.add(req.rid)
        if first is None:
            req.queued = True
        else:
            t = time.perf_counter()
            self.emissions.append((t, 1))
            self._emit(req, t, [first])

    def step(self):
        rows = [self.requests[r] for r in self.in_flight
                if self.requests[r].t_first is not None]
        t0 = time.perf_counter()
        with self.rec.span("engine.step"):
            out = self.engine.step()
        t = time.perf_counter()
        self.emissions.append((t0, sum(
            len(v) if isinstance(v, list) else 1 for v in out.values())))
        self.live.append((t, len(rows),
                          sum(r.prompt_len + r.n_out for r in rows),
                          len(self.engine.pending_requests())))
        for rid, toks in out.items():
            self._emit(self.requests[rid], t,
                       toks if isinstance(toks, list) else [toks])

    def _emit(self, req, t, toks):
        if req.t_first is None:
            req.t_first = t
            if req.queued and req.due is not None and self.t_open is not None:
                # queued: due -> admitted; else the add_request call itself
                self.rec.add("request.admit", self.t_open + req.due, t)
            else:
                self.rec.add("request.admit", req.t_call, t)
        req.events.append((t, len(toks)))
        req.tokens.extend(toks)
        if req.n_out >= req.max_new:
            req.t_done = t
            self.in_flight.discard(req.rid)

    def open_window(self):
        self.t_open = self.ctx.open_window()
        self._c0 = self._compile_stats()
        self._d0 = self._decode_stats()
        return self.t_open

    # ----------------------------------------------------------- afterwards
    def counters(self):
        c1, d1 = self._compile_stats(), self._decode_stats()
        num = lambda a, b: {k: b[k] - a[k] for k in b  # noqa: E731
                            if isinstance(b[k], (int, float))
                            and not isinstance(b[k], bool)}
        d = num(self._d0, d1)
        d["last_chunk"] = d1["last_chunk"]
        return {"compile_stats": num(self._c0, c1), "decode_stats": d}

    def facts(self, lo, hi):
        """Sizes the roofline needs, over the traced part of the window."""
        steps = [(rows, live) for t, rows, live, _q in self.live
                 if lo <= t <= hi]
        return {
            "max_batch": self.p["max_batch"],
            "decode_chunk": self._decode_stats()["last_chunk"],
            "rows": np.mean([r for r, _l in steps]) if steps else 0.0,
            "live_kv_tokens": np.mean([l for _r, l in steps]) if steps else 0.0,
        }

    def check_against_reference(self, finished):
        """For a seeded sample of finished requests: the engine's tokens at
        the checked positions each have a reference logit within
        margin_sigma standard deviations (of that position's reference
        logits) of the reference maximum, the reference being given the
        prompt plus the engine's own earlier tokens.  The engine exposes
        tokens, not logits, so this is as tight as the comparison can be."""
        ck = self.ctx.cell["check"]
        last = max(ck["positions"])
        pool = sorted((r for r in finished if len(r.tokens) > last),
                      key=lambda r: r.rid)
        if not pool:
            return {"a finished request to compare with the reference": False}
        picked = [pool[int(i)] for i in
                  traffic.rng(self.ctx.seed).choice(len(pool), min(ck["sample"], len(pool)), False)]
        fam, ref = self.ctx.family, self.ctx.reference()
        weights = fam.reference_weights(self.model)
        sizes = fam.reference_sizes(self.cfg)
        pad_to = ck["pad_to"]
        checks, exact, worst = {}, 0, 0.0
        for r in picked:
            ids = np.concatenate([r.prompt, np.asarray(r.tokens[:last],
                                                       np.int32)])
            if len(ids) > pad_to:
                raise ValueError(f"check.pad_to {pad_to} < {len(ids)}")
            ids = np.pad(ids, (0, pad_to - len(ids)))  # causal: the tail is unseen
            at = [r.prompt_len - 1 + k for k in ck["positions"]]
            lg = np.asarray(ref.logits_at(weights, sizes, ids, at))
            for k, row in zip(ck["positions"], lg):
                tok = r.tokens[k]
                gap = float(row.max() - row[tok]) / float(row.std())
                exact += int(tok == int(row.argmax()))
                worst = max(worst, gap)
                checks[f"{r.rid} (prompt {r.prompt_len}) token {k + 1}: "
                       f"reference logit {gap:.4f} sigma under the maximum "
                       f"(margin {ck['margin_sigma']})"] = gap <= ck["margin_sigma"]
        self.ctx.say(f"reference: {exact} of {len(checks)} checked tokens are "
                     f"the reference argmax exactly; worst gap {worst:.4f} sigma")
        return checks


def report_requests(ctx, sv, counted, t_open, t_close, failed):
    """The sample counts and the generator's lateness, on earlier lines."""
    late = [r.t_call - (t_open + r.due) for r in counted
            if r.due is not None and r.t_call is not None]
    if late:
        ctx.say(f"generator lateness (call - due): median "
                f"{stats.median(late) * 1e3:.1f} ms, max "
                f"{max(late) * 1e3:.1f} ms over {len(late)} requests")
    thirds = [[q for t, _r, _l, q in sv.live
               if t_open + k * (t_close - t_open) / 3 <= t
               < t_open + (k + 1) * (t_close - t_open) / 3] for k in range(3)]
    ctx.say("engine queue, mean over each third of the window: "
            + ", ".join(f"{sum(q) / len(q):.2f}" if q else "-" for q in thirds)
            + "; rows resident, mean: "
            + f"{sum(r for t, r, _l, _q in sv.live if t >= t_open) / max(1, sum(1 for t, *_ in sv.live if t >= t_open)):.1f}")
    for name in ("add_request", "engine.step"):   # where the window's time went
        d = sorted(e - s for s, e in sv.rec.within(name, t_open, t_close))
        if d:
            ctx.say(f"window, host time in {name}: {len(d)} calls, sum "
                    f"{sum(d):.3f} s, min {d[0] * 1e3:.1f} ms, median "
                    f"{stats.median(d) * 1e3:.1f} ms, max {d[-1] * 1e3:.1f} ms")
    ctx.say(f"requests: {len(counted)} counted, {failed} failed, "
            f"{sum(r.queued for r in counted)} queued before admission; "
            f"window {t_close - t_open:.3f} s")
