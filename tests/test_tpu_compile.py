"""The main path's Pallas kernels, compiled by the TPU's own compiler
(Mosaic) at llama_7b widths for a DESCRIBED v5e chip — no chip attached,
no chip time (on-chip-measurement guide, section 2, rehearsal 3).

Interpret-mode tests cannot see what Mosaic refuses (a primitive without
a TPU lowering, a block that overflows VMEM); these can, in a second or
two each.  A compile that passes is not a chip run: nothing executes.

The topology is described inside a module-scoped fixture, never at
import: only one process may hold the TPU library, and under xdist every
worker imports every test file.  All compiles run in this process, and
all of them live in this one file.
"""

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import _pl_utils

# llama_7b widths (models.llama.llama_7b)
HIDDEN, HEADS, HEAD_DIM, FFN = 4096, 32, 128, 11008
ROWS = 4096  # tokens per step: batch 2 x seq 2048
V5E_SLUG = "tpu_v5_lite"  # autotune.device_kind_slug() of a "TPU v5 lite"


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs land in /tmp
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the ops tier to its TPU branch (Mosaic, not the interpreter)
    and keep the persistent compile cache out of it: an executable for a
    described chip can be written there but never read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    from paddle_tpu.ops import autotune

    monkeypatch.setattr(_pl_utils, "on_tpu", lambda: True)
    # ... and to the tile table of the chip being described, which the
    # kernels would consult there (`ops/tuned/tpu_v5_lite.json`)
    monkeypatch.setattr(autotune, "device_kind_slug",
                        lambda device=None: V5E_SLUG)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    # conftest pins fp32-exact matmuls for the CPU numerics tests; the chip
    # runs the MXU default, and Mosaic refuses bf16 operands at "highest"
    with jax.default_matmul_precision("default"):
        yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes, sharding, dtype=jnp.bfloat16):
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=sharding) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "kernel not in the program"
    return compiled


def _flash(q, k, v):
    from paddle_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True)


def _flash_loss(q, k, v):
    return _flash(q, k, v).astype(jnp.float32).sum()


@pytest.mark.parametrize("batch,seq", [(2, 2048), (1, 4096)])
@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles(one_chip, mosaic, batch, seq, direction):
    fn = _flash if direction == "fwd" else jax.grad(_flash_loss, (0, 1, 2))
    shape = (batch, seq, HEADS, HEAD_DIM)
    _compile(fn, shape, shape, shape, sharding=one_chip)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_flash_attention_compiles_whatever_precision_the_caller_set(
        one_chip, mosaic, direction):
    """Mosaic refuses bfloat16 operands at "highest" ("Bad lhs type"), the
    setting `serving/cluster_worker.py` and the references run under, and a
    product of two bfloat16 values is exact in float32 at one pass: the
    kernels ask for that themselves."""
    fn = _flash if direction == "fwd" else jax.grad(_flash_loss, (0, 1, 2))
    shape = (1, 1024, HEADS, HEAD_DIM)
    with jax.default_matmul_precision("highest"):
        _compile(fn, shape, shape, shape, sharding=one_chip)


@pytest.mark.parametrize("seq", [5, 37, 200])
def test_flash_attention_compiles_for_any_prompt_length(one_chip, mosaic, seq):
    """The serving engine prefills prompts as they come.  A 37-row block is
    refused by Mosaic ("cannot statically prove that index in dimension 2
    is a multiple of 8"); causal self-attention pads to the block instead."""
    shape = (1, seq, HEADS, HEAD_DIM)
    _compile(_flash, shape, shape, shape, sharding=one_chip)


@pytest.mark.parametrize("direction,seq,longest", [
    ("fwd", 16384, 16000), ("bwd", 8192, 5248)])
def test_flash_attention_names_its_length_limit(one_chip, mosaic, direction,
                                                seq, longest):
    """Past these lengths Mosaic refuses the kernels (whole-sequence K/V,
    resp. Q/dO/lse/delta, of one head resident in VMEM).  The error is ours
    and names the limit; there is no switch to the O(S^2) reference."""
    fn = _flash if direction == "fwd" else jax.grad(_flash_loss, (0, 1, 2))
    shape = (1, seq, HEADS, HEAD_DIM)
    with pytest.raises(ValueError, match=f"longest .* is {longest}"):
        _compile(fn, shape, shape, shape, sharding=one_chip)


@pytest.mark.parametrize("seq", [5248, 4096])
def test_flash_attention_limit_is_the_compilers(one_chip, mosaic, seq):
    """The stated limit is tight: the longest length the guard admits
    compiles in a program where XLA cannot relieve the kernel (it moves
    operands of a bare kernel into VMEM itself, which hides the limit);
    so do, at the train cell's 4,096, the tiles the table holds for it
    (the dk/dv kernel's 1,024 x 256 beside 12 MiB of whole-sequence blocks)."""
    def loss(x, w):
        b, s, d = x.shape
        q, k, v = ((x @ w).reshape(b, s, HEADS, HEAD_DIM) for _ in range(3))
        return _flash_loss(q, k, v)

    x = jax.ShapeDtypeStruct((1, seq, HIDDEN), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((HIDDEN, HIDDEN), jnp.bfloat16, sharding=one_chip)
    jax.jit(jax.grad(loss, 1)).lower(x, w).compile()


@pytest.mark.parametrize("seq", [2048, 8192])
def test_flash_forward_compiles_with_a_value_width_of_its_own(one_chip, mosaic,
                                                              seq):
    """Latent attention's prefill (models/mla_moe.py) at the published
    widths: 128 heads, q/k 192 wide (128 + 64 rope lanes), v 128 wide, one
    prompt of the longest bucket the serving cell sends.  K and V of a head
    stay whole in VMEM: 8,192 x (192 + 128) x 2 B, twice."""
    qk, v = (1, seq, 128, 192), (1, seq, 128, 128)
    compiled = _compile(_flash, qk, qk, v, sharding=one_chip)
    assert f"bf16[1,128,{seq},128]" in compiled.as_text()


def _tuned_flash_entries():
    """(kernel, key fields, tile) of every flash entry of the v5e's table."""
    import json
    import os

    from paddle_tpu.ops import autotune

    path = os.path.join(os.path.dirname(autotune.__file__), "tuned",
                        V5E_SLUG + ".json")
    with open(path) as f:
        table = json.load(f)
    return [(kernel, key, (e["config"]["block_q"], e["config"]["block_k"]),
             e["ms"])
            for kernel in autotune.FLASH_KERNELS
            for key, e in sorted(table.get(kernel, {}).items())]


@pytest.mark.parametrize("kernel,key,tile,ms", _tuned_flash_entries(),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_every_tuned_flash_tile_is_one_mosaic_accepts(one_chip, mosaic, kernel,
                                                      key, tile, ms):
    """A tile recorded in the table was measured on the chip, on THAT
    kernel: it has its measured time, the kernels find it under the key
    they build (`_block_sizes`), `validate_flash_tile` admits it in the
    blocks' own type, and Mosaic compiles the kernel with it (32 heads: a
    kernel holds one head at a time; a `window` entry with the window)."""
    import importlib

    from paddle_tpu.ops import autotune

    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    dims = dict(kv.split("=") for kv in key.split("|"))
    seq, width = int(dims["seq_q"]), int(dims["head_dim"])
    v_width, dtype = int(dims.get("v_dim", width)), jnp.dtype(dims["dtype"])
    causal = dims["causal"] == "True"
    window = int(dims["window"]) if "window" in dims else None
    assert int(dims["seq_k"]) == seq
    assert ms > 0, "not a measurement"
    assert fa._block_sizes(seq, seq, width, dtype, causal, v_dim=v_width,
                           window=window, kernel=kernel) == tile
    assert autotune.validate_flash_tile(*tile, seq, seq, width, dtype=dtype,
                                        v_dim=v_width) is None
    q = k = (1, 32, seq, width)
    v = (1, 32, seq, v_width)
    if kernel == "flash_fwd":
        _compile(lambda q, k, v: fa._fwd(q, k, v, 0.1, causal, *tile,
                                         window)[0],
                 q, k, v, sharding=one_chip, dtype=dtype)
        return
    assert window is None, "the backward kernels take no window"
    run = {"flash_bwd_dq": fa._bwd_dq, "flash_bwd_dkv": fa._bwd_dkv}[kernel]
    lanes = jax.ShapeDtypeStruct((1, 32, seq, 128), jnp.float32,
                                 sharding=one_chip)
    args = [jax.ShapeDtypeStruct(s, dtype, sharding=one_chip)
            for s in (q, k, v, v)]
    compiled = jax.jit(lambda *a: run(*a, 0.1, causal, *tile)).lower(
        *args, lanes, lanes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_the_cells_shapes_are_in_the_table():
    """The shapes the benchmark's cells hand the kernels each have their
    entry: the train step's three kernels at 4,096 x 128, the latent
    prefill's three buckets (q/k 192, v 128; forward only)."""
    have = {(kernel, key) for kernel, key, _, _ in _tuned_flash_entries()}
    train = "causal=True|dtype=bfloat16|head_dim=128|seq_k=4096|seq_q=4096"
    assert {(k, train) for k in ("flash_fwd", "flash_bwd_dq",
                                 "flash_bwd_dkv")} <= have
    for seq in (2048, 4096, 8192):
        assert ("flash_fwd", "causal=True|dtype=bfloat16|head_dim=192|"
                f"seq_k={seq}|seq_q={seq}|v_dim=128") in have
    # the window / full model's prefill buckets (models/window_moe.py): 48
    # heads on full layers (the key has no head count: 2,048 and 4,096 are
    # the entries above), 72 under a window of 512 on sliding ones
    for seq in (2048, 4096, 8192):
        plain = f"causal=True|dtype=bfloat16|head_dim=128|seq_k={seq}|seq_q={seq}"
        assert ("flash_fwd", plain) in have
        assert ("flash_fwd", plain + "|window=512") in have


@pytest.mark.parametrize("heads,window", [(48, None), (72, 512)])
@pytest.mark.parametrize("seq", [2048, 8192])
def test_flash_forward_compiles_at_the_window_models_grouped_heads(
        one_chip, mosaic, seq, heads, window):
    """models/window_moe.py's prefill at laguna-s-2.1's widths: 48 query
    heads on a full layer and 72 under a window of 512 on a sliding one, over
    8 K/V heads of 128 (groups of 6 and 9 through the forward's index map,
    no repeated K/V), one prompt of the shortest and the longest bucket, at
    the tile the table holds for the shape."""
    from paddle_tpu.ops import autotune, flash_attention

    q, kv = (1, seq, heads, 128), (1, seq, 8, 128)
    compiled = _compile(
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=window),
        q, kv, kv, sharding=one_chip)
    assert f"bf16[1,{heads},{seq},128]" in compiled.as_text()
    # 72-head shapes validate like any other: the tile is a head's
    for tile in ((128, 128), (512, 512), (256, 1024)):
        assert autotune.validate_flash_tile(*tile, seq, seq, 128,
                                            dtype=jnp.bfloat16) is None
    assert "divide" in autotune.validate_flash_tile(512, 3000, seq, seq, 128,
                                                    dtype=jnp.bfloat16)


def test_the_window_read_holds_no_conditional_and_no_pool_copy(one_chip,
                                                               mosaic):
    """A sliding layer's write -> read inside a scan (the macro-step's
    shape) at the laguna cell's geometry: 32 rings of 5 x 128 positions,
    72 / 8 heads.  The ring's width is fixed, so there is no ladder: the
    program holds no conditional, and copies a pool at its edge at most."""
    import re

    from paddle_tpu.ops import paged_attention as pa

    b, n, nkv, h, ring, bs = 32, 72, 8, 128, 5, 128

    def steps(q, kc, vc, new, tables, lens):
        def one(carry, _):
            kc, vc, lens, acc = carry
            pos = (lens - 1)[:, None]
            kc = pa.ring_write_chunk(kc, new, tables, pos)
            vc = pa.ring_write_chunk(vc, new, tables, pos)
            o = pa.paged_window_attention(q + acc.astype(q.dtype), kc, vc,
                                          tables, lens, 512)
            return (kc, vc, lens + 1, acc + o.astype(jnp.float32)), None

        carry, _ = jax.lax.scan(
            one, (kc, vc, lens, jnp.zeros(q.shape, jnp.float32)), None,
            length=2)
        return carry

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((b * ring, nkv, bs, h))
    text = jax.jit(steps, donate_argnums=(1, 2)).lower(
        s((b, 1, n, h)), pool, pool, s((b, 1, nkv, h)),
        s((b, ring), jnp.int32), s((b,), jnp.int32)).compile().as_text()
    assert "conditional(" not in text
    copies = re.findall(rf"= bf16\[{b * ring},{nkv},{bs},{h}\]\S* copy\(", text)
    # at most the program's edge: each pool once in and once out (the order
    # the slot writes prefer), never one a token step and read
    assert len(copies) <= 4, len(copies)


def test_flash_tile_validation_counts_the_blocks_own_type():
    """The latent prefill's longest bucket: K and V of a head are 8,192 x
    (192 -> 256 lanes + 128) x 2 B, twice = 12 MiB in bfloat16, which fits
    the 16 MiB with a 128-row tile; reckoned at 4 bytes a value (as the
    validator did before PR 30, for every type) no tile fits and the
    table's entry could never be read."""
    from paddle_tpu.ops import autotune

    at_8k = dict(seq_q=8192, seq_k=8192, head_dim=192, v_dim=128)
    assert autotune.validate_flash_tile(128, 128, **at_8k,
                                        dtype=jnp.bfloat16) is None
    assert "VMEM" in autotune.validate_flash_tile(128, 128, **at_8k,
                                                  dtype=jnp.float32)
    assert "VMEM" in autotune.validate_flash_tile(128, 128, **at_8k)
    assert autotune.flash_candidates(8192, 8192, 192, dtype=jnp.bfloat16,
                                     v_dim=128)
    assert autotune.flash_candidates(8192, 8192, 192, v_dim=128) == []


@pytest.mark.parametrize("rows,hidden", [(8192, 7680), (32, 7680),
                                         (8192, 1536), (8192, 512)])
def test_fused_rms_norm_compiles_at_the_latent_models_widths(one_chip, mosaic,
                                                             rows, hidden):
    """8,192 rows of 7,680: the row cap is 136, which does not tile 8,192,
    and one block of the whole array was 120 MB of VMEM; the block is now
    the largest multiple of 8 under the cap that does (128)."""
    from paddle_tpu.ops import fused_rms_norm

    _compile(lambda x, w: fused_rms_norm(x, w), (rows, hidden), (hidden,),
             sharding=one_chip)


@pytest.mark.parametrize("case", ["fwd", "residual", "bwd"])
def test_fused_rms_norm_compiles(one_chip, mosaic, case):
    from paddle_tpu.ops import fused_rms_norm

    x, w = (ROWS, HIDDEN), (HIDDEN,)
    if case == "fwd":
        _compile(lambda x, w: fused_rms_norm(x, w), x, w, sharding=one_chip)
    elif case == "residual":
        _compile(lambda x, r, w: fused_rms_norm(x, w, residual=r), x, x, w,
                 sharding=one_chip)
    else:
        # the backward is analytic jnp; the kernel rides its forward rule,
        # which stays in the program only if the loss needs the output
        _compile(jax.grad(lambda x, w: (fused_rms_norm(x, w)
                                        .astype(jnp.float32) ** 2).sum(),
                          (0, 1)),
                 x, w, sharding=one_chip)


def test_swiglu_compiles(one_chip, mosaic):
    from paddle_tpu.ops import swiglu

    _compile(swiglu, (ROWS, FFN), (ROWS, FFN), sharding=one_chip)


# Every activation MatmulEpiloguePattern can hand the kernel.  The models in
# paddle_tpu/models reach the pattern with exact-erf GELU (bert, gpt) and
# with silu (llama, where SwiGLUPattern takes it first); exact GELU is
# declined by the pattern — see the next test — so what remains fusible is:
@pytest.mark.parametrize("act", ["relu", "silu", "gelu_tanh"])
def test_matmul_bias_act_compiles(one_chip, mosaic, act):
    from paddle_tpu.ops import matmul_bias_act

    _compile(lambda x, w, b: matmul_bias_act(x, w, b, act),
             (ROWS, HIDDEN), (HIDDEN, FFN), (FFN,), sharding=one_chip)


def test_matmul_bias_act_exact_gelu_stays_with_xla(one_chip, mosaic):
    """Mosaic has no lowering for erf ("Unimplemented primitive in Pallas
    TPU lowering: erfc"), so exact GELU never enters the kernel: the op
    computes it with plain XLA, numerics unchanged."""
    from paddle_tpu.ops import matmul_bias_act

    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
            for s in ((ROWS, HIDDEN), (HIDDEN, FFN), (FFN,))]
    compiled = jax.jit(
        lambda x, w, b: matmul_bias_act(x, w, b, "gelu")).lower(*args).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_kernel_names_reach_the_hlo_instruction_names(one_chip, mosaic):
    """`name=` on each `pl.pallas_call` becomes the custom call's HLO
    instruction name — what the trace's `XLA Ops` line prints.  The three
    flash kernels are told apart from the norm and swiglu by name, and the
    regex of `perfbench/layer_metrics/flash_roofline_share.json` matches
    exactly them."""
    import json
    import os
    import re

    from paddle_tpu.ops import fused_rms_norm, swiglu

    def loss(q, k, v, x, w, gate, up):
        return (_flash_loss(q, k, v)
                + fused_rms_norm(x, w).astype(jnp.float32).sum()
                + swiglu(gate, up).astype(jnp.float32).sum())

    qkv, x, ffn = (1, 4096, HEADS, HEAD_DIM), (ROWS, HIDDEN), (ROWS, FFN)
    # value_and_grad: the loss keeps the forward kernels from being dropped
    text = _compile(jax.value_and_grad(loss, (0, 1, 2, 3, 5)), qkv, qkv, qkv, x,
                    (HIDDEN,), ffn, ffn, sharding=one_chip).as_text()
    names = {re.sub(r"\.\d+$", "", m) for m in re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text)}
    kernels = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv", "rms_norm_fwd",
               "swiglu_fwd")
    for kernel in kernels:
        assert any(kernel in n for n in names), (kernel, names)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "perfbench", "layer_metrics",
                           "flash_roofline_share.json")) as f:
        pat = re.compile(json.load(f)["reader"]["over"]["op_match"])
    # the trace reduction names an operation "<instruction> <opcode> -> ..."
    matched = {n for n in names if pat.search(n + " custom-call -> ")}
    assert matched == {n for n in names if "flash_" in n} and len(matched) == 3


# the two serving cells that read K/V pages: (rows, query heads, K/V heads,
# head width, table width, block size, pool blocks)
PAGED_CELLS = {"laguna-s-2.1": (32, 48, 8, 128, 67, 128, 2176),
               "internlm2-1.8b": (32, 16, 8, 128, 96, 16, 3104)}


def _write_attend_scan(write, attend, geometry, one_chip):
    """The write -> attend sequence of a decode layer inside a scan (the
    macro-step's shape), compiled; its text."""
    b, n, nkv, h, w, bs, nb = geometry

    def steps(q, kc, vc, new, tables, lens):
        def one(carry, _):
            kc, vc, lens, acc = carry
            pos = (lens - 1)[:, None]
            kc = write(kc, new[:, None], tables, pos)
            vc = write(vc, new[:, None], tables, pos)
            o = attend((q + acc.astype(q.dtype))[:, None], kc, vc, tables,
                       lens)[:, 0]
            return (kc, vc, lens + 1, acc + o.astype(jnp.float32)), None

        carry, _ = jax.lax.scan(
            one, (kc, vc, lens, jnp.zeros(q.shape, jnp.float32)), None,
            length=2)
        return carry

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((nb, nkv, bs, h))
    return jax.jit(steps, donate_argnums=(1, 2)).lower(
        s((b, n, h)), pool, pool, s((b, nkv, h)), s((b, w), jnp.int32),
        s((b,), jnp.int32)).compile().as_text()


def _pool_copies(text, nb, nkv, bs, h):
    import re

    return re.findall(rf"= bf16\[{nb},{nkv},{bs},{h}\]\S* copy\(", text)


def test_decode_attention_branches_take_the_pool_as_it_is_written(one_chip,
                                                                  mosaic):
    """The XLA form (`_write_slots` -> `_paged_chunk_xla`: what a mesh, an
    int8 pool's neighbours and T > 1 run) at decode-sat's geometry: the
    ladder's branches hold NO copy of a pool.  Without
    `paged_attention._as_written` each branch copies both whole pools (102
    MB each, per layer and token step): the loop keeps a pool in the order
    its slot writes prefer, a branch takes its operands in the default
    order unless told."""
    import re

    from paddle_tpu.ops import paged_attention as pa

    b, n, nkv, h, w, bs, nb = PAGED_CELLS["internlm2-1.8b"]
    text = _write_attend_scan(
        pa._write_slots,
        lambda q, kc, vc, tables, lens: pa._paged_chunk_xla(
            q, kc, vc, tables, lens, None),
        PAGED_CELLS["internlm2-1.8b"], one_chip)
    branches = re.search(r"conditional\(.*branch_computations=\{([^}]*)\}",
                         text).group(1).split(", ")
    assert len(branches) == len(pa.page_ladder(w)) == 4
    for name in branches:
        body = text.split(f"\n{name} (", 1)[1].split("\n}\n", 1)[0]
        assert f"[{nb},{nkv},{bs},{h}]" in body        # the pool is read here
        assert not _pool_copies(body, nb, nkv, bs, h), name


@pytest.mark.parametrize("cell", sorted(PAGED_CELLS))
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_paged_decode_kernel_compiles_at_the_cells_geometries(
        one_chip, mosaic, cell, dtype):
    """The kernel through Mosaic as `paged_decode_attention` selects it:
    scalar-prefetched tables, page DMAs predicated on the row's own page
    count, groups of 6 and 2 query heads padded to the sublane tile, PV at
    the exact product; under the name the trace will show."""
    from paddle_tpu.ops import paged_attention as pa

    b, n, nkv, h, w, bs, nb = PAGED_CELLS[cell]
    dt = jnp.dtype(dtype)

    def s(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((nb, nkv, bs, h))
    assert pa.reads_own_pages(pool)
    text = jax.jit(pa.paged_decode_attention).lower(
        s((b, n, h)), pool, pool, s((b, w), jnp.int32),
        s((b,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "%paged_decode" in text
    assert "conditional(" not in text


@pytest.mark.parametrize("cell", sorted(PAGED_CELLS))
def test_the_kernels_write_attend_scan_copies_no_pool(one_chip, mosaic, cell):
    """The twin of the test above for the kernel: a Mosaic call takes its
    operands in the default order, and behind `_write_slots` XLA copied
    both whole pools in front of it, per layer and token step (570 MB each
    at laguna's geometry).  `paged_write_chunk` writes a pool the kernel
    reads as rows (`_write_rows`), the loop keeps the default order, and no
    operation of a pool's shape is copied: not in the loop, not at the
    program's edge."""
    from paddle_tpu.ops import paged_attention as pa

    b, n, nkv, h, w, bs, nb = PAGED_CELLS[cell]
    text = _write_attend_scan(pa.paged_write_chunk, pa.paged_chunk_attention,
                              PAGED_CELLS[cell], one_chip)
    assert "%paged_decode" in text and "conditional(" not in text
    assert not _pool_copies(text, nb, nkv, bs, h)
    # ... which is the write's doing: the slot scatter in front of the same
    # kernel brings the copies back
    text = _write_attend_scan(pa._write_slots, pa.paged_chunk_attention,
                              PAGED_CELLS[cell], one_chip)
    assert len(_pool_copies(text, nb, nkv, bs, h)) >= 2


# the latent cell's pool, one row a token that is key and value at once
# (openpangu-ultra-moe-718b): (rows, query heads, the pool's row, of it the
# value, table width, block size, pool blocks); the model's own row is 576
# wide, `MlaMoeConfig.pool_width` rounds it up to whole 128-lane rows
LATENT_CELL = (32, 128, 640, 512, 67, 128, 2144)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_paged_decode_kernel_compiles_at_the_latent_cells_geometry(
        one_chip, mosaic, dtype):
    """The kernel's shared-row case through Mosaic as
    `models.mla_moe.absorbed_attention` selects it: 128 query heads on ONE
    640-wide row a token (M = 128 fills the matrix unit's rows), one DMA a
    page, the value the page's first 512 lanes, probabilities in the rows'
    type; under the name the trace will show.  The model's own 576-wide row
    is refused by Mosaic ("Slice shape along dimension 3 must be aligned to
    tiling (128), but is 576": the ref is 640 wide in HBM and VMEM alike),
    so the predicate refuses it and the pool is allocated 640 wide."""
    from paddle_tpu.models import mla_moe
    from paddle_tpu.ops import paged_attention as pa

    b, n, row, rank, w, bs, nb = LATENT_CELL
    cfg = mla_moe.MlaMoeConfig()
    assert (cfg.num_attention_heads, cfg.pool_width, cfg.kv_lora_rank) == (
        n, row, rank)
    dt = jnp.dtype(dtype)

    def s(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((nb, 1, bs, row))
    assert pa.reads_own_pages(pool)
    assert not pa.reads_own_pages(s((nb, 1, bs, cfg.latent_width)))
    text = jax.jit(lambda q, pool, tables, lens: mla_moe.absorbed_attention(
        q, pool, tables, lens, rank=rank, width=192)).lower(
            s((b, n, cfg.latent_width)), pool, s((b, w), jnp.int32),
            s((b,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and "%paged_decode" in text
    assert "conditional(" not in text
    assert f"f32[{b},{n},{w * bs}]" not in text         # no score array
    assert not _pool_copies(text, nb, 1, bs, row)


def _latent_write_attend_scan(write, attend, row, one_chip):
    """`_write_attend_scan` for a pool that is key and value at once."""
    b, n, _row, rank, w, bs, nb = LATENT_CELL

    def steps(q, pool, new, tables, lens):
        def one(carry, _):
            pool, lens, acc = carry
            pool = write(pool, new[:, None, None], tables, (lens - 1)[:, None])
            o = attend(q + acc.astype(q.dtype), pool, tables, lens)
            acc = acc.at[..., :rank].add(o)
            return (pool, lens + 1, acc), None

        carry, _ = jax.lax.scan(
            one, (pool, lens, jnp.zeros(q.shape, jnp.float32)), None,
            length=2)
        return carry

    def s(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return jax.jit(steps, donate_argnums=(1,)).lower(
        s((b, n, row)), s((nb, 1, bs, row)), s((b, row)),
        s((b, w), jnp.int32), s((b,), jnp.int32)).compile().as_text()


def test_the_latent_write_attend_scan_copies_no_pool(one_chip, mosaic):
    """A latent decode layer's write -> attend inside a scan at the pangu
    cell's geometry: written as rows and read by the kernel, the 640-wide
    pool keeps the default order and NO operation of its shape is a copy,
    in the loop or at the program's edge, and no [32, 128, 8576] float32
    score array exists.  (One head a page: `_write_slots` keeps the same
    order, unlike a K pool of 8 heads, so the twin that brings the copies
    back is not the slot scatter.)  The twin is the parent's pool: the
    model's own 576-wide row, which the predicate refuses.  XLA stores it
    positions-minor, copies the whole pool in and out at the scan's edge
    (PERF.md section 7 (g), closed by this) and runs XLA's form, whose
    scores go through HBM."""
    from paddle_tpu.ops import paged_attention as pa

    b, n, row, rank, w, bs, nb = LATENT_CELL

    def attend(q, pool, tables, lens):
        return pa.paged_shared_row_attention(q, pool, tables, lens, rank=rank,
                                             scale=0.07)

    scores = f"f32[{b},{n},{w * bs}]"
    for write in (pa.paged_write_chunk, pa._write_slots):
        text = _latent_write_attend_scan(write, attend, row, one_chip)
        assert "%paged_decode" in text and scores not in text
        assert not _pool_copies(text, nb, 1, bs, row)
    text = _latent_write_attend_scan(pa.paged_write_chunk, attend, 576,
                                     one_chip)
    assert "%paged_decode" not in text and scores in text
    assert len(_pool_copies(text, nb, 1, bs, 576)) >= 2


def test_the_latent_models_macro_step_copies_no_pool(one_chip, mosaic):
    """`jit_decode_macro_step` of a latent-attention engine at the pangu
    cell's cache geometry (32 rows, 128 heads on a row of 512 + 64 values,
    blocks of 128, a 67-page table, 2,144 + 32 blocks a pool; the other
    widths small, one dense and one expert layer): each layer's attention is
    `paged_decode`, no pool is copied anywhere in the program (the parent's
    copied every pool in and out at the loop's edge), and neither the
    gathered pages nor the float32 scores of the table's width exist."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.mla_moe import MlaMoeForCausalLM, mla_moe_tiny

    b, n, row, rank, w, bs, nb = LATENT_CELL
    paddle.seed(0)
    model = MlaMoeForCausalLM(mla_moe_tiny(
        hidden_size=256, num_hidden_layers=2, num_attention_heads=n,
        q_lora_rank=64, kv_lora_rank=rank, qk_nope_head_dim=128,
        qk_rope_head_dim=64, v_head_dim=128, max_position_embeddings=16384,
        dtype="bfloat16", held_experts=(2, 4)))
    model.eval()
    eng = serving.GenerationEngine(model, max_batch=b, block_size=bs,
                                   num_blocks=nb)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._step_avals())
    text = eng._build_step(8).lower(*avals).compile().as_text()
    assert len(re.findall(r"%paged_decode\S* = ", text)) == 2
    assert f"bf16[{nb + b},1,{bs},{row}]" in text       # the pool is here
    assert not re.findall(rf"= bf16\[\d+,1,{bs},{row}\]\S* copy\(", text)
    assert not re.findall(rf"= bf16\[\d+,{row}\]\S* copy\(", text)
    assert f"f32[{b},{n},{w * bs}]" not in text
    assert f"bf16[{b},{w * bs},{row}]" not in text      # no gathered pages


@pytest.mark.parametrize("pool,reads", [
    *((f"{cell} K/V", True) for cell in sorted(PAGED_CELLS)),
    ("latent row of 640", True), ("latent row of 576", False),
    ("ring", True), ("int8", False), ("heads of 64", False),
    ("heads of 192", False), ("float16", False), ("pages of 8 bfloat16", False),
    ("stacked K/V", True)])
def test_reads_own_pages_answers_by_what_it_sees(mosaic, pool, reads):
    """The one predicate of the write, the read and the counter, over the
    cells' pools: whole 128-lane rows, pages of whole sublane tiles, a plain
    bfloat16 / float32 pool, one token a row.  PR 35 loosened nothing: the
    latent pool passes because its row is allocated 640 wide; a K/V pool of
    a head width the kernel was never compiled for does not."""
    from paddle_tpu.ops import paged_attention as pa

    shapes = {
        **{f"{cell} K/V": (g[6], g[2], g[5], g[3])
           for cell, g in PAGED_CELLS.items()},
        "latent row of 640": (2176, 1, 128, 640),
        "latent row of 576": (2176, 1, 128, 576),
        "ring": (160, 8, 128, 128), "int8": (64, 8, 16, 128),
        "heads of 64": (64, 8, 16, 64), "heads of 192": (64, 1, 128, 192),
        "float16": (64, 8, 16, 128), "pages of 8 bfloat16": (64, 8, 8, 128),
        "stacked K/V": (12, 2176, 2, 128, 128)}
    dtype = {"int8": jnp.int8, "float16": jnp.float16}.get(pool, jnp.bfloat16)
    cache = jax.ShapeDtypeStruct(shapes[pool], dtype)
    if pool == "int8":
        cache = pa.QuantPool(cache, jax.ShapeDtypeStruct(shapes[pool][:2],
                                                         jnp.float32))
    assert pa.reads_own_pages(cache) == reads
    assert not pa.reads_own_pages(cache, 2)             # T > 1: XLA's form


def test_the_window_models_macro_step_copies_no_pool(one_chip, mosaic):
    """`jit_decode_macro_step` of a window / full attention engine with
    laguna-s-2.1's cache geometry (48 / 72 query heads over 8 K/V heads of
    128, blocks of 128, a 67-page table, rings of 5; the other widths and
    the row count small): the paged layers go through `paged_decode`, and
    no pool, paged or ring, is copied anywhere in the program (the parent's
    held 20 such copies, two a pool at the loop's edge)."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.window_moe import (WindowMoeForCausalLM,
                                              window_moe_tiny)

    paddle.seed(0)
    rows = 8
    model = WindowMoeForCausalLM(window_moe_tiny(
        hidden_size=256, num_attention_heads_per_layer=(48, 72, 72, 72, 48),
        num_key_value_heads=8, head_dim=128, sliding_window=512,
        max_position_embeddings=16384, dtype="bfloat16",
        held_experts=(2, 6)))
    model.eval()
    eng = serving.GenerationEngine(model, max_batch=rows, block_size=128,
                                   num_blocks=rows * 67)
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._step_avals())
    text = eng._build_step(8).lower(*avals).compile().as_text()
    assert len(re.findall(r"%paged_decode\S* = ", text)) >= 2
    assert not re.findall(r"= bf16\[\d+,8,128,128\]\S* copy\(", text)


def test_the_convolved_latent_models_programs_copy_no_pool_and_no_expert_stack(
        one_chip, mosaic):
    """`jit_decode_macro_step` of a `models/cca_moe.py` engine at zaya1-8b's
    widths and cache geometry (8 / 2 heads of 128, experts of 2,048, blocks
    of 128, a 67-page table; depth 2, 4 experts, a small vocabulary and 8
    rows: 0.2 GB of weights on this sandbox's CPU): K and V
    ride the layers' scan as ONE flat pool each, written and read in place
    through the block table shifted by layer, so no pool is copied or cut
    (held a layer, and stacked at the program's edge, the pools' second copy
    alone would be the chip: 2 x 2.28 GB at depth 16); the experts' stack
    ([layers x experts, 2048, 4096]) is indexed inside the pass that runs and
    never copied either; the K/V read is `paged_decode`.  What does copy the
    pools is `next_token_logits`' program, which must leave them as they
    are: its temporaries are the two pools, the reason the cell runs depth
    12."""
    import re

    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models.cca_moe import CcaMoeConfig, CcaMoeForCausalLM

    paddle.seed(0)
    rows, layers = 8, 2
    model = CcaMoeForCausalLM(CcaMoeConfig(num_hidden_layers=layers,
                                           vocab_size=4096, num_experts=4))
    model.eval()
    eng = serving.GenerationEngine(model, max_batch=rows, block_size=128,
                                   num_blocks=rows * 67)
    blocks = rows * 67 + rows

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
            tree)

    step = eng._build_step(8).lower(*described(eng._step_avals())).compile()
    text = step.as_text()
    assert len(re.findall(r"%paged_decode\S* = ", text)) == 1     # one body
    assert not re.findall(rf"= bf16\[({layers},)?({blocks}|{layers * blocks}),"
                          r"2,128,128\]\S* copy\(", text)
    assert not re.findall(r"= bf16\[\d+,2048,(4096|2048)\]\S* copy\(", text)
    pools = 2 * layers * blocks * 2 * 128 * 128 * 2
    assert step.memory_analysis().temp_size_in_bytes < pools // 8
    # the admission's program: the flash kernel, and nothing of the stack
    # copied (8,192 positions: the cell's longest bucket)
    fn = eng._prefill_program(8192, 0)
    text = fn.lower(described([t._value for t in eng._state]),
                    jax.ShapeDtypeStruct((1, 8192), jnp.int32,
                                         sharding=one_chip),
                    jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
                    None).compile().as_text()
    assert "flash_fwd" in text
    assert not re.findall(r"= bf16\[\d+,2048,(4096|2048)\]\S* copy\(", text)


def _tuned_paged_entries():
    import json
    import os

    from paddle_tpu.ops import autotune

    path = os.path.join(os.path.dirname(autotune.__file__), "tuned",
                        V5E_SLUG + ".json")
    with open(path) as f:
        table = json.load(f)
    return [(key, e["config"]["pages_per_step"], e["ms"])
            for key, e in sorted(table.get("paged_decode", {}).items())]


@pytest.mark.parametrize("key,pages,ms", _tuned_paged_entries(),
                         ids=lambda v: v if isinstance(v, str) else "")
def test_every_tuned_pages_a_step_is_one_the_kernel_finds_and_mosaic_accepts(
        one_chip, mosaic, key, pages, ms):
    """An entry of `paged_decode` was measured on the chip: it has its
    time, the kernel finds it under the key it builds, and Mosaic compiles
    the kernel with it; both cells' geometries have theirs."""
    from paddle_tpu.ops import paged_attention as pa

    def geometry(key):
        dims = dict(kv.split("=") for kv in key.split("|"))
        return (*(int(dims[k]) for k in ("block_size", "num_kv_heads",
                                         "head_dim")),
                int(dims["rank"]) if "rank" in dims else None,
                jnp.dtype(dims["dtype"]))

    bs, nkv, h, rank, dt = geometry(key)
    assert ms > 0, "not a measurement"
    assert pa._pages_per_step(bs, nkv, h, dt, rank) == pages
    # the K/V cells' pages, and the latent cell's shared row (a `rank`)
    cells = {(g[5], g[2], g[3], None) for g in PAGED_CELLS.values()} | {
        (LATENT_CELL[5], 1, LATENT_CELL[2], LATENT_CELL[3])}
    assert (bs, nkv, h, rank) in cells
    assert {geometry(k)[:4] for k, _, _ in _tuned_paged_entries()} == cells

    def s(shape, dtype=dt):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((1024, nkv, bs, h))
    tables, lens = s((32, 2 * pages), jnp.int32), s((32,), jnp.int32)
    if rank is None:
        lowered = jax.jit(lambda *a: pa._paged_decode_pallas(*a, 0.1)).lower(
            s((32, 2 * nkv, h)), pool, pool, tables, lens)
    else:
        lowered = jax.jit(lambda q, pool, *a: pa._paged_decode_pallas(
            q, pool, None, *a, 0.1, rank=rank)).lower(
                s((32, LATENT_CELL[1], h)), pool, tables, lens)
    assert "tpu_custom_call" in lowered.compile().as_text()
