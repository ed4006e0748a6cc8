"""Seeded traffic from a cell's `traffic` parameters: one general generator.

A length distribution is a small dict (`spec`):

  {"fixed": 128}
  {"choices": [64, 128], "weights": [0.5, 0.5]}
  {"uniform": [128, 384], "step": 16}
  {"lognormal": {"median": 96, "sigma": 0.8}, "clip": [16, 512],
   "snap": [16, 32, 64]}           # snap: nearest listed value, in log space

Lengths are drawn STRATIFIED: n draws are the distribution's quantiles at
(i + 0.5) / n, so every seed offers the same multiset of lengths; the seed
decides only their order (and, in an open loop, the gaps).  Prompt and output
lengths are paired by a shuffle with a FIXED key, so the multiset of
(prompt, output) pairs — and with it the set of shapes the program compiles —
is the same for every seed too.

Open loop: the arrival times of a Poisson process, given that n = rate x
seconds arrivals fall into the window, are n independent uniform times; that
is what is drawn, so every seed offers exactly the asked rate.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from statistics import NormalDist

import numpy as np

_PAIRING_KEY = 20260928  # fixed: pairs prompt with output lengths


def rng(seed, *more):
    """A generator from the run's seed (any whole number) and further keys."""
    return np.random.default_rng([int(seed) % (2 ** 63), *more])


def quantile(spec: dict, u: float) -> int:
    """The spec's length at quantile u in (0, 1)."""
    if "fixed" in spec:
        return int(spec["fixed"])
    if "choices" in spec:
        w = np.asarray(spec.get("weights") or [1.0] * len(spec["choices"]),
                       float)
        edges = np.cumsum(w) / w.sum()
        return int(spec["choices"][int(np.searchsorted(edges, u, "right"))
                                   if u < edges[-1] else len(edges) - 1])
    if "uniform" in spec:
        lo, hi = spec["uniform"]
        x = lo + u * (hi - lo)
    elif "lognormal" in spec:
        p = spec["lognormal"]
        x = p["median"] * math.exp(p["sigma"] * NormalDist().inv_cdf(u))
    else:
        raise ValueError(f"unknown length spec {spec}")
    if "clip" in spec:
        x = min(max(x, spec["clip"][0]), spec["clip"][1])
    if "snap" in spec:
        return int(min(spec["snap"], key=lambda v: abs(math.log(v / x))))
    step = int(spec.get("step", 1))
    return int(round(x / step) * step)


def stratified(spec: dict, n: int) -> list:
    """n lengths: the quantiles at (i + 0.5) / n, ascending."""
    return [quantile(spec, (i + 0.5) / n) for i in range(n)]


def paired_lengths(prompt_spec, output_spec, n) -> list:
    """The fixed multiset of n (prompt, output) pairs, in a fixed order."""
    prompts = stratified(prompt_spec, n)
    outputs = stratified(output_spec, n)
    order = np.random.default_rng(_PAIRING_KEY).permutation(n)
    return [(prompts[i], outputs[int(j)]) for i, j in enumerate(order)]


@dataclass
class Request:
    rid: str
    prompt_len: int
    max_new: int
    due: float | None = None     # seconds after the window opens (open loop)
    # filled by the driver
    t_call: float | None = None  # add_request called
    t_first: float | None = None
    t_done: float | None = None
    queued: bool = False
    refused: bool = False
    events: list = field(default_factory=list)   # (time, tokens emitted)
    tokens: list = field(default_factory=list)

    @property
    def n_out(self):
        return sum(n for _t, n in self.events)


def prompt_tokens(seed: int, index: int, length: int, vocab: int):
    """The tokens of request `index`: seeded, distinct per request, so no two
    prompts share a prefix beyond chance."""
    return rng(seed, index).integers(0, vocab, length).astype(np.int32)


def open_loop(traffic: dict, seconds: float, seed: int):
    """(ramp, window): the requests admitted before the window — one of each
    distinct (prompt, output) pair of the window's multiset, in a fixed order
    — and the window's requests with their due times."""
    n = max(1, int(round(traffic["rate"] * seconds)))
    pairs = paired_lengths(traffic["prompt"], traffic["output"], n)
    draw = rng(seed)
    order = draw.permutation(n)
    due = np.sort(draw.uniform(0.0, seconds, n))
    window = [Request(f"w{i}", *pairs[int(j)], due=float(due[i]))
              for i, j in enumerate(order)]
    distinct = sorted(set(pairs))
    keep = np.random.default_rng(_PAIRING_KEY).permutation(len(distinct))
    ramp = [Request(f"ramp{i}", *distinct[int(j)])
            for i, j in enumerate(keep)]
    return ramp, window


class ClosedLoop:
    """`clients` callers, each sending its next request when its last one
    finished.  The first wave's outputs come from `first_output` (spread so
    that completions do not arrive in waves); later ones cycle through the
    stratified multiset of `cycle` lengths.  The ORDER of the lengths is fixed
    too, not drawn from the seed: a request lives longer than a window, so
    the order decides which admissions fall inside it, and seeds that
    reordered them read 108 to 114 tokens/s where two runs of one seed agreed
    within 0.3% (PR 23).  The seed still makes the weights and every prompt's
    tokens."""

    def __init__(self, traffic: dict):
        self.clients = int(traffic["clients"])
        draw = np.random.default_rng(_PAIRING_KEY + 1)
        cycle = int(traffic.get("cycle", 4 * self.clients))
        pairs = paired_lengths(traffic["prompt"], traffic["output"], cycle)
        self._later = [pairs[int(j)] for j in draw.permutation(cycle)]
        first = paired_lengths(traffic["prompt"],
                               traffic.get("first_output", traffic["output"]),
                               self.clients)
        self._first = [first[int(j)] for j in draw.permutation(self.clients)]
        self._sent = 0

    def first_wave(self):
        out = [Request(f"c{i}", *p) for i, p in enumerate(self._first)]
        self._sent = len(out)
        return out

    def next_request(self):
        i = self._sent
        self._sent += 1
        return Request(f"c{i}", *self._later[(i - self.clients)
                                             % len(self._later)])

    def shapes(self):
        """Every distinct (prompt, output) pair this loop can ever send."""
        return sorted(set(self._first) | set(self._later))
