"""The arithmetic of the request metrics: percentiles with failed requests
counted as misses, time to first token, time per output token."""

from __future__ import annotations

import math
from statistics import median  # noqa: F401  (the readers' and drivers' median)


def percentile(values, q, missed=0):
    """The q-th percentile (0-100) by the nearest-rank rule over `values`
    plus `missed` requests that never produced one: a miss sorts after every
    value.  Returns math.inf when the rank falls among the misses."""
    n = len(values) + missed
    if n == 0:
        raise ValueError("percentile of nothing")
    rank = max(1, math.ceil(q / 100.0 * n))
    ordered = sorted(values)
    return ordered[rank - 1] if rank <= len(ordered) else math.inf


def samples_beyond(n, q):
    """How many samples lie beyond the q-th percentile's rank."""
    return n - max(1, math.ceil(q / 100.0 * n))


def ttft_ms(req, t_open):
    """Due -> first token, in ms; None while no first token was seen."""
    if req.t_first is None:
        return None
    return (req.t_first - (t_open + req.due)) * 1e3


def tpot_ms(req, not_before=None):
    """Time per output token after the first: (t_last - t_first) / (n - 1).
    The engine emits several tokens per step, so this is the per-request mean
    a reader of the stream feels.  For a request whose first tokens came
    before `not_before` (it was admitted before the window opened) the count
    starts at its first emission inside the window instead.  None when fewer
    than two emissions qualify."""
    events = [(t, n) for t, n in req.events
              if not_before is None or t >= not_before]
    if len(events) < 2:
        return None
    after = sum(n for _t, n in events[1:])
    return (events[-1][0] - events[0][0]) / after * 1e3


