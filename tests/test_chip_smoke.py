"""chip_smoke.py off the chip: it must refuse to run, and its phase functions
must pass at a tiny size on the CPU (on-chip-measurement guide, section 2,
rehearsals 1 and 2) — wrong paths, arguments and sharding rules are found
here, at no chip time.  Plus the one rule for where the compile cache lives.
"""

import os
import subprocess
import sys

import jax
import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import chip_smoke  # noqa: E402
from paddle_tpu._core import compile_cache  # noqa: E402
from paddle_tpu.models.llama import llama_7b, llama_tiny  # noqa: E402


@pytest.mark.parametrize("script,args", [
    ("chip_smoke.py", []), ("chip_smoke.py", ["--four-chips"]),
    ("bench.py", [])])  # bench.py's measuring path; --smoke is its CPU twin
def test_no_accelerator_no_result(script, args):
    """Run where jax finds no TPU, the script exits nonzero and prints no
    success line: no `"ok": true`, no metric payload, nothing on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, os.path.join(_REPO, script), *args],
                         capture_output=True, text=True, timeout=300, env=env)
    assert out.returncode != 0, out.stdout[-500:]
    assert out.stdout.strip() == "", out.stdout[-500:]
    assert "TPU" in out.stderr or "accelerator" in out.stderr


def test_train_and_serve_phases_on_cpu_tiny():
    dev = jax.devices()[0]
    cfg = llama_tiny(dtype="bfloat16")
    train = chip_smoke.train_phase(cfg, batch=2, seq=64, steps=4, seed=0,
                                   device=dev)
    assert train["losses"][-1] < train["losses"][0]
    # no Pallas on the CPU: the step IS its plain-jnp twin
    assert train["losses"][0] == train["twin"]
    serve = chip_smoke.serve_phase(cfg, prompt_lens=(5, 16, 37, 64, 100),
                                   max_new_tokens=12, seed=0, device=dev,
                                   num_blocks=64)
    assert sorted(serve["results"]) == ["r0", "r1", "r2", "r3", "r4"]


def test_a_failed_check_raises():
    """No try/except around a phase: a check that fails ends the run."""
    cfg = llama_tiny(dtype="bfloat16")
    wrong = jax.devices()[1]  # nothing of the step lives there
    with pytest.raises(RuntimeError, match="chip_smoke check failed"):
        chip_smoke.train_phase(cfg, batch=2, seq=64, steps=2, seed=0,
                               device=wrong)


def test_four_chip_phase_on_virtual_devices():
    out = chip_smoke.four_chip_phase(llama_tiny(dtype="bfloat16"), batch=2,
                                     seq=64, steps=3, seed=0,
                                     devices=jax.devices())
    assert len(out["losses"]) == len(out["reference"]) == 3


def test_depth_is_what_sixteen_gigabytes_force():
    """llama_7b widths on a 16 GB chip: AdamW + fp32 master weights leave
    room for two layers; bf16 serving is capped, not forced."""
    limit = int(15.75 * 2**30)
    depth, why = chip_smoke.choose_depth("train", llama_7b(), limit, batch=1,
                                         seq=2048)
    assert depth == 2 and "3 would need" in why
    assert chip_smoke.choose_depth("serve", llama_7b(), limit,
                                   ceiling=8)[0] == 8
    with pytest.raises(RuntimeError, match="even one layer"):
        chip_smoke.choose_depth("train", llama_7b(), 2**30, batch=1, seq=2048)


def test_compile_cache_directory_rule(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR set -> code sets no directory, whatever it is
    asked; unset -> the fixed <checkout>/.jax_cache."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setattr(cc, "reset_cache", lambda: None)

    monkeypatch.setattr(compile_cache, "_configured_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert compile_cache.configure("/somewhere/else") == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(calls)

    calls.clear()
    monkeypatch.setattr(compile_cache, "_configured_dir", None)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(_REPO, ".jax_cache")
    assert compile_cache.default_dir() == want
    assert compile_cache.enable() == want
    assert ("jax_compilation_cache_dir", want) in calls


def test_mla_moe_logits_phase_on_cpu_tiny(monkeypatch):
    """Rehearsal 1 of `--mla-moe-logits`: the same drive at a tiny float32
    size, where program and reference agree to rounding and route alike."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "perfbench"))
    from test_perfbench_mla_moe import TINY

    monkeypatch.setattr(chip_smoke, "MLA_MOE_LOGIT_TOL", 1e-4)
    monkeypatch.setattr(chip_smoke, "MLA_MOE_TIE_TOL", 1e-4)
    # 4 experts top-2 over 128 pairs: a bfloat16 router parts on a few only
    monkeypatch.setattr(chip_smoke, "ROUTE_AGREEMENT_MIN", 1.0)
    out = chip_smoke.mla_moe_logits_phase(
        TINY, seed=3, device=jax.devices()[0], prompt_lens=(16, 32, 64),
        decoded=(8, 16), block_size=8)
    assert len(out["errors"]) == 9 and max(out["errors"]) < 1e-4
    # the probes ran and the program passed; the softmax control reads
    # worse, the router control has no near tie to trip on at this size
    assert out["route_agreement"] == 1.0 >= out["route_agreement_bfloat16"]
    assert (out["softmax_rms_float32_spread_4"] < chip_smoke.SOFTMAX_RMS_TOL
            < out["softmax_rms_bfloat16_spread_4"])
    # off a chip the decode attention is XLA's form, in the engine (the
    # table's whole width of every row) and in the probe
    assert out["softmax_form"] == "xla" and out["kernel_traces"] == 0 < out["xla_traces"]
    assert out["softmax_rms_xla_spread_4"] == out["softmax_rms_float32_spread_4"]
    assert out["positions_read"] > 1.5 * out["positions_live"] > 0


def test_window_moe_logits_phase_on_cpu_tiny(monkeypatch):
    """Rehearsal 1 of `--window-moe-logits`: the same drive at a tiny float32
    size (window 8, rings of 3 x 4 positions that wrap before the first
    decode boundary), where program and reference agree to rounding, and the
    references with the window ignored or the gate left out do not."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "perfbench"))
    from test_perfbench_window_moe import TINY

    monkeypatch.setattr(chip_smoke, "WINDOW_MOE_LOGIT_TOL", 1e-3)
    monkeypatch.setattr(chip_smoke, "WINDOW_MOE_TIE_TOL", 1e-3)
    monkeypatch.setattr(chip_smoke, "WINDOW_ROUTE_AGREEMENT_MIN", 1.0)
    out = chip_smoke.window_moe_logits_phase(
        TINY, seed=3, device=jax.devices()[0], prompt_lens=(16, 32, 61),
        decoded=(8, 16), block_size=4)
    assert len(out["errors"]) == 9 and max(out["errors"]) < 1e-3
    # every control reads beyond the limit the program's own rows stay under
    assert min(out["ignore_window"]) > 1e-3 and min(out["no_gate"]) > 1e-3
    assert out["route_agreement"] == 1.0 >= out["route_agreement_bfloat16"]
    # a window of 8 has too few probabilities for bfloat16 to show beyond
    # the limit (the probe's own sizes do: the test below)
    assert (out["window_softmax_rms_float32"] < chip_smoke.SOFTMAX_RMS_TOL
            and out["window_softmax_rms_float32"]
            < out["window_softmax_rms_bfloat16"])
    # rows of 7, 8, 32, 48, 77: the ring (12) read whole, min(len, 8) live
    assert (out["positions_read"], out["positions_live"]) == (5 * 12, 7 + 4 * 8)


def test_cca_moe_logits_phase_on_cpu_tiny(monkeypatch):
    """Rehearsal 1 of `--cca-moe-logits`: the same drive at a tiny float32
    size (blocks of 4, prompts padded to their buckets, the state a slot many
    steps old at the second decode boundary), where program and reference
    agree to rounding, the references with a mechanism left out do not, the
    convolutions' tail carried across a block boundary is the whole
    sequence's, and dropped it is not."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                    "perfbench"))
    from test_perfbench_cca_moe import TINY

    monkeypatch.setattr(chip_smoke, "CCA_MOE_LOGIT_TOL", 1e-3)
    monkeypatch.setattr(chip_smoke, "CCA_MOE_TIE_TOL", 1e-3)
    monkeypatch.setattr(chip_smoke, "CCA_ROUTE_AGREEMENT_MIN", 1.0)
    monkeypatch.setattr(chip_smoke, "CCA_CONV_TAIL_TOL", 1e-4)
    out = chip_smoke.cca_moe_logits_phase(
        TINY, seed=3, device=jax.devices()[0], prompt_lens=(13, 30, 61),
        decoded=(8, 16), block_size=4)
    assert len(out["errors"]) == 9 and max(out["errors"]) < 1e-3
    # the controls whose mechanism every token meets read beyond the limit
    # the program's own rows stay under (skip_zero needs a token that chose
    # skip among those the rows see: the cell's rows have thousands)
    for control in ("no_conv0", "no_shift", "no_carry"):
        assert min(out[control]) > 1e-3, control
    assert max(out["skip_zero"]) > 1e-3
    assert out["route_agreement"] == 1.0 >= out["route_agreement_bfloat16"]
    assert out["conv_tail_rms"] < 1e-4 < 0.05 < out["conv_tail_rms_dropped"]
    assert (out["dense_softmax_rms_float32"] < chip_smoke.SOFTMAX_RMS_TOL)


def test_window_softmax_probe_on_cpu():
    """Rehearsal 1 of the window read's probe at its own widths (72 / 8 heads
    x 128, window 512, rings of 5 x 128), rows below, at and past the window
    (the longest has wrapped its ring three times): the program's read under
    the limit, the bfloat16-softmax control over it."""
    out = chip_smoke.window_softmax_probe(seed=2147484099,
                                          lens=(300, 512, 2100))
    assert (out["window_softmax_rms_float32"] < chip_smoke.SOFTMAX_RMS_TOL
            < out["window_softmax_rms_bfloat16"])
    assert (out["positions_read"], out["positions_live"]) == (3 * 640,
                                                              300 + 512 + 512)


@pytest.mark.parametrize("shape", chip_smoke.DENSE_PROBE_SHAPES,
                         ids=lambda shape: shape[0].split()[0])
@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_dense_softmax_probe_on_cpu(shape, path):
    """Rehearsal 1 of `--dense-softmax`, at the probe's own sizes (they are
    small), through each path: the XLA form the CPU selects (the ladder's
    width: 32 of 96 pages, 67 of 67) and the kernel interpreted (each row's
    own pages).  The reading is under the limit, no worse than the XLA form
    beside it, and the bfloat16-softmax control over it."""
    import paddle_tpu as paddle

    name, heads, kv_heads, head_dim, block_size, table_width, lens = shape
    paddle.set_flags({"FLAGS_use_pallas":
                      "true" if path == "kernel" else "auto"})
    try:
        out = chip_smoke.dense_softmax_probe(
            seed=2147484099, name=name, heads=heads, kv_heads=kv_heads,
            head_dim=head_dim, block_size=block_size, table_width=table_width,
            lens=lens)
    finally:
        paddle.set_flags({"FLAGS_use_pallas": "auto"})
    assert out["path"] == path
    assert (out["dense_softmax_rms_float32"] < chip_smoke.SOFTMAX_RMS_TOL
            < out["dense_softmax_rms_bfloat16"])
    ladder = {16: 32, 128: 67}[block_size]
    own = sum(-(-n // block_size) for n in lens)
    assert (out["positions_read"], out["positions_live"]) == (
        (own if path == "kernel" else 3 * ladder) * block_size, sum(lens))


@pytest.fixture(scope="module")
def flash_probe():
    """Rehearsal 1 of `--flash-softmax` at a twelfth of its lengths: the
    probe's two shapes keep their widths and head grouping (q/k 192 with v
    128, forward only; 128 with two query heads a key/value head, forward
    and backward)."""
    return chip_smoke.flash_softmax_probe(
        seed=2147484123, sampled=2,
        shapes=(("latent prefill", 512, 4, 4, 192, 128, False),
                ("train step", 512, 4, 2, 128, 128, True)))


@pytest.mark.parametrize("result", [
    "out.latent_prefill", "out.train_step", "dq.train_step", "dk.train_step",
    "dv.train_step"])
def test_flash_softmax_probe_on_cpu(flash_probe, result):
    """The bfloat16 kernels (bfloat16 operands, `p` and `ds` rounded once)
    read under the limit against a float64 softmax on the same inputs, and
    the control that rounds the SCORES to bfloat16 reads over it."""
    assert (flash_probe[f"flash_rms_{result}"] < chip_smoke.SOFTMAX_RMS_TOL
            < flash_probe[f"bfloat16_scores_rms_{result}"])


def test_flash_softmax_probe_reads_the_trace_counters(flash_probe):
    assert flash_probe["flash_bf16_operand_traces"] == 3  # fwd, fwd, grad
    assert flash_probe["flash_f32_operand_traces"] == 0
