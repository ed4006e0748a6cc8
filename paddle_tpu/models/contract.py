"""The model contract: what `serving.GenerationEngine` asks of a model.

The engine owns the slots, the block tables, the paged pools and the two
compiled programs (the admission's prefill program, the decode macro-step);
the model owns the mathematics.  Between them stands a `ServingContract`,
which a served model returns from `serving_contract()`:

- `spec` — the CACHE SPECIFICATION (`CacheSpec`): per layer, which pools
  the model keeps and what one token occupies in each (`PoolSpec`: `heads`
  rows of `width` values, and the type).  The engine allocates, pours,
  gathers and carries `[num_blocks, heads, block_size, width]` pools from
  it, all layers alike, and never asks what the values mean.
- `forward_cached` — the prompt's forward pass over naive caches (per layer
  one `[B, S, heads, width]` tensor per pool, the prefix first): the hidden
  state after the final norm and the grown caches.  The prefill program
  traces it; the eager fallbacks call it.
- `decode` — ONE step over the paged pools: embed `tokens`, run every
  layer writing this step's cache rows at `lens - 1` through `tables`,
  final norm.  The macro-step scans it.
- `logits` — the vocabulary projection of a hidden state.
- `pool_carry` / `pool_unpack` — per-layer pool lists to and from the form
  the model's `decode` wants to be scanned over (stacked for a LayerStack).

Two layouts of `pools` appear: the engine holds `pools[p][layer]` (one list
per `PoolSpec`, in `spec.pools` order); caches are `caches[layer][p]`.

`forward_cached` and `decode` also return `aux`, a dict of int32 scalars
the DEVICE counted for this call (an expert layer's assignments, ...), `{}`
for a model with nothing to count.  The engine sums them over the
macro-step's scan, reads them with the tokens at the sync that is already
there, and adds them to `decode_stats()` under the same keys (a prefill's
when its admission commits; the model names a prefill's counts apart from a
decode step's).  `active` ([B] bool, or `n_real` for a padded prompt) tells
the model which rows are committed work; masked rows are not counted.

Engine features built for K/V pools (int8 pool, prefix cache, chunked and
interleaved prefill, LoRA slots, speculation, a mesh, snapshot / park,
page shipping) ask `spec.kv_pair` and refuse any other specification by
name (docs/DECODE.md "The model contract"); the optional methods below
serve those features and need no implementation elsewhere.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["PoolSpec", "CacheSpec", "ServingContract"]


@dataclass(frozen=True)
class PoolSpec:
    """One pool of a layer's cache: a token occupies `heads` rows of `width`
    values of `dtype` ("bfloat16" | "float32").  `name` labels the pool's
    bytes in `decode_stats()` (`<name>_pool_bytes`)."""
    name: str
    heads: int
    width: int
    dtype: str


@dataclass(frozen=True)
class CacheSpec:
    """`n_layers` layers, each with the same `pools`."""
    n_layers: int
    pools: tuple

    @property
    def kv_pair(self) -> bool:
        """A K pool and a V pool of one shape: what the engine's optional
        features (and ops/paged_attention's attention) were built for."""
        if [p.name for p in self.pools] != ["k", "v"]:
            return False
        k, v = self.pools
        return (k.heads, k.width, k.dtype) == (v.heads, v.width, v.dtype)


class ServingContract:
    """Base of a model's contract; see the module docstring.  Subclasses set
    `spec`, `max_positions` (the longest sequence the position tables
    cover) and implement the four required methods."""

    spec: CacheSpec
    max_positions: int

    # ---- required
    def forward_cached(self, ids, caches, offset, n_real=None):
        """ids: Tensor [B, S]; caches[layer][p]: Tensor [B, L, heads, width]
        (L == `offset` positions already cached); n_real: traced count of
        real tokens when the prompt is right-padded (None: all).  Returns
        (hidden Tensor [B, S, h] after the final norm, caches grown to
        L + S, aux)."""
        raise NotImplementedError

    def decode(self, tokens, pools, tables, lens, active=None, **kv_only):
        """tokens [B, T] int32 (T == 1 unless `chunk=True`); pools in carry
        form; tables [B, W]; lens [B] INCLUDING these tokens; active [B]
        bool or None.  Returns (hidden Tensor [B, T, h] after the final
            norm, pools, aux).  `kv_only`: chunk, adapters, slots, scaling,
        passed only by features a K/V specification admits."""
        raise NotImplementedError

    def logits(self, h):
        raise NotImplementedError

    # ---- optional
    def pool_carry(self, pools):
        return [list(p) for p in pools]

    def pool_unpack(self, pools):
        return [list(p) for p in pools]

    def shard(self, mesh, mp_axis):
        raise NotImplementedError(
            f"{type(self).__name__} has no tensor-parallel placement")

    def adapter_layers(self):
        raise NotImplementedError(
            f"{type(self).__name__} has no LoRA target layers")
