"""The model contract: what `serving.GenerationEngine` asks of a model.

The engine owns the slots, the block tables, the paged pools and the two
compiled programs (the admission's prefill program, the decode macro-step);
the model owns the mathematics.  Between them stands a `ServingContract`,
which a served model returns from `serving_contract()`:

- `spec` — the CACHE SPECIFICATION (`CacheSpec`): the model's layers in
  CACHE CLASSES (`CacheClass`).  A class names its layers, the pools each
  of them keeps and what one token occupies in each (`PoolSpec`: `heads`
  rows of `width` values, and the type), and a LIFETIME: paged (every
  position is kept, in the blocks the allocator hands a request, found
  through the request's block table) or window(W) (only the last W
  positions are kept, in a ring of blocks the SLOT owns from the engine's
  construction on, whatever a request's length) or STATE A SLOT (no
  positions at all: `heads` rows of `width` values a layer for each engine
  slot, `[max_batch, heads, width]`, whatever a request's length: a
  convolution's tail, a recurrence's state).  The engine allocates, pours,
  gathers and carries `[blocks, heads, block_size, width]` pools (a state
  class's without the block axes) from it and never asks what the values
  mean.  Most models have one paged class of all their layers:
  `CacheSpec(n_layers, pools)`.
- `forward_cached` — the prompt's forward pass over naive caches (per layer
  one `[B, S, heads, width]` tensor per pool, the prefix first): the hidden
  state after the final norm and the grown caches; a state class's pools
  come back as `[B, 1, heads, width]`, the state after the prompt's last
  REAL position (`n_real - 1` of a right-padded prompt).  The prefill
  program traces it; the eager fallbacks call it.
- `decode` — ONE step over the paged pools: embed `tokens`, run every
  layer writing this step's cache rows at `lens - 1` through `tables`
  (and reading and rewriting row b of a state class's pools for ACTIVE
  rows only: row b is the state of the slot that row b serves), final
  norm.  The macro-step scans it.
- `logits` — the vocabulary projection of a hidden state.
- `pool_carry` / `pool_unpack` — per-layer pool lists to and from the form
  the model's `decode` wants to be scanned over (stacked for a LayerStack).

Two layouts of `pools` appear: the engine holds `pools[p][i]` (one list per
`PoolSpec`, in `spec.pools` order: class after class, each class's pools
in order; `i` counts the class's layers in `CacheClass.layers` order, or is
0 alone for a `stacked` class, whose one array has the layers in front);
caches are `caches[layer][p]`, `layer` the model's own index and `p` over
the pools of that layer's class (of its classes, in order, where a state
class names the layer too: `CacheSpec.layer_pools`).

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
name — a latent pool, any specification with a window class, whose ring
none of them may treat as pages, and any with a state class, which has no
pages at all (docs/DECODE.md "The model contract", "Cache classes"); the
optional methods below serve those features and need no implementation
elsewhere.
"""

from __future__ import annotations

from dataclasses import dataclass

__all__ = ["PoolSpec", "CacheClass", "CacheSpec", "ServingContract"]


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
class CacheClass:
    """The layers of a model that keep the same `pools` for the same
    LIFETIME.  `layers`: their indices in the model, ascending.  `window`
    None is the PAGED lifetime: every position of a request is kept, in
    blocks the engine's allocator hands the request for its prompt and
    output, and position t lives in block `table[t // block_size]` of the
    request's block table.  `window` W keeps only the last W positions: the
    class's pools hold a RING of `ring_blocks` blocks for each engine slot,
    assigned when the engine is built and never allocated by request
    length; position t lives in ring block `(t // block_size) % ring_blocks`
    and overwrites position t - ring_blocks * block_size.  `slot_state`
    is the third lifetime, STATE A SLOT: nothing is kept by position; each
    pool is `[max_batch, heads, width]` a layer, row i the state of engine
    slot i (a `PoolSpec` then says what a SLOT holds a layer, not a token).
    The admission's prefill program writes it (the state after the prompt's
    last real position), every decode step reads and rewrites the rows of
    its active rows, and a slot's next admission overwrites it.  Pool names
    are unique over a specification's classes (`<name>_pool_bytes`).

    `stacked`: the engine holds each of the class's pools as ONE array with
    a leading axis over the class's layers (`pools[p]` is then a list of one
    entry, `[layers, ...]`) and not as one array a layer: the form a model
    whose layers are one scanned stack computes on, so that nothing is
    stacked or cut at a macro-step's edge (for a paged pool of gigabytes
    that would be a second copy of it)."""
    layers: tuple
    pools: tuple
    window: int | None = None
    slot_state: bool = False
    stacked: bool = False

    def __post_init__(self):
        if self.slot_state and self.window is not None:
            raise ValueError("a cache class is a window or state a slot, "
                             "not both")

    @property
    def paged(self) -> bool:
        """Every position kept, in the blocks of the request's table."""
        return self.window is None and not self.slot_state

    def ring_blocks(self, block_size: int) -> int:
        """Blocks of a slot's ring: a query at position t reads t - W + 1
        .. t, which lie in at most ceil((W - 1) / block_size) + 1 blocks
        wherever t falls in its own (each decode token step writes ONE
        position, then reads)."""
        return -(-(self.window - 1) // block_size) + 1


@dataclass(frozen=True)
class CacheSpec:
    """A model's cache: `classes`, each a `CacheClass`.  `CacheSpec(n_layers,
    pools)` is the common case, ONE paged class of all `n_layers` layers
    with the same `pools`; `CacheSpec.of(classes)` takes several.  `pools`
    is then every class's pools in order, `n_layers` the model's depth."""
    n_layers: int
    pools: tuple
    classes: tuple = ()

    def __post_init__(self):
        if not self.classes:
            object.__setattr__(self, "classes", (CacheClass(
                tuple(range(self.n_layers)), tuple(self.pools)),))
        names = [p.name for p in self.pools]
        if len(set(names)) != len(names):
            raise ValueError(f"pool names must be unique: {names}")

    @classmethod
    def of(cls, classes):
        """Several classes.  The classes that keep positions (paged, window)
        cover layers 0..n once between them; a STATE class names layers
        besides, which may have a paged or window class too (a layer whose
        attention is paged and whose convolution keeps a tail has both)."""
        classes = tuple(classes)
        layers = sorted(i for c in classes if not c.slot_state
                        for i in c.layers)
        every = sorted({i for c in classes for i in c.layers})
        if every != list(range(len(every))) or (layers and layers != every):
            raise ValueError("classes that keep positions must cover layers "
                             f"0..n once: {layers} of {every}")
        return cls(len(every), tuple(p for c in classes for p in c.pools),
                   classes)

    @property
    def windowed(self) -> bool:
        """Some class keeps only a window (its pools are rings, not pages)."""
        return any(c.window is not None for c in self.classes)

    @property
    def slot_state(self) -> bool:
        """Some class is state a slot (its pools have no positions)."""
        return any(c.slot_state for c in self.classes)

    @property
    def per_class_tables(self) -> bool:
        """`decode` takes a TUPLE of tables, one a class, and not the one
        block table: several classes, or a single class that is not paged
        (a window class's table is the slots' rings, never the requests'
        pages; a state class has none, and None stands in its place)."""
        return len(self.classes) > 1 or not self.classes[0].paged

    def layer_pools(self, layer: int) -> tuple:
        """(class, pool) of everything `layer` keeps, class after class: the
        order of `caches[layer]`."""
        return tuple((c, p) for c in self.classes if layer in c.layers
                     for p in c.pools)

    @property
    def kv_pair(self) -> bool:
        """ONE paged class with a K pool and a V pool of one shape: what the
        engine's optional features (and ops/paged_attention's attention)
        were built for."""
        if (len(self.classes) != 1 or not self.classes[0].paged
                or self.classes[0].stacked):
            return False
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
        L + S, aux).  A state class's pools are not grown: they come in
        empty (such a model prefills a whole prompt, `offset` 0) and go out
        as [B, 1, heads, width], the state after position `n_real - 1` (the
        last position where `n_real` is None)."""
        raise NotImplementedError

    def decode(self, tokens, pools, tables, lens, active=None, **kv_only):
        """tokens [B, T] int32 (T == 1 unless `chunk=True`); pools in carry
        form; lens [B] INCLUDING these tokens; active [B] bool or None.
        `tables`: where a row's positions live.  For a specification of
        ONE PAGED class it is that class's table, [B, W] int32: row b's
        position t is in block `tables[b, t // block_size]` (an inactive
        row's entries all name its slot's scratch page).  For any other
        specification (`spec.per_class_tables`: several classes, or a
        window class, even alone) it is a tuple with one table a class, in
        `spec.classes` order: a paged class's as above, a window class's
        [B, ring_blocks], the ring of each row's SLOT (position t in block
        `table[b, (t // block_size) % ring_blocks]`; the same for active
        and inactive rows, since a ring is never shared), a state class's
        None (row b of its pools IS the state of row b's slot; the model
        rewrites it for active rows only, so that what a finished or empty
        lane computes is never read by a later request).  Returns (hidden
        Tensor [B, T, h] after the final norm, pools, aux).  `kv_only`:
        chunk, adapters, slots, scaling, passed only by features a K/V
        specification admits."""
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
