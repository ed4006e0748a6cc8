"""Serving-tier crash-consistency matrix (serving/snapshot.py,
docs/CHECKPOINT.md): a subprocess SERVING loop — snapshotting every
macro-step through the shared commit protocol — is hard-killed (SIGKILL
via FLAGS_checkpoint_kill_point) at every injected protocol point, and
the parent asserts the prior snapshot always restores, then proves the
killed-and-resumed engine's greedy AND seeded-sampled streams (including
a mid-flight join and prefix-cache state) match an uninterrupted run
token for token.  The training-side matrix lives in
test_checkpoint_crash.py; this file reuses the same kill points against
the engine-snapshot commit — one protocol, one matrix."""

import json
import os
import signal
import subprocess
import sys

import pytest

from paddle_tpu.distributed.checkpoint.manager import KILL_POINTS

_SERVER = r"""
import json
import os
import sys

import jax

jax.config.update("jax_platforms", "cpu")
# pinned like tests/conftest.py and run_tier1's worker bootstrap
jax.config.update("jax_default_matmul_precision", "highest")

from paddle_tpu._core import compile_cache

compile_cache.enable()  # the suite's one cache (tests/conftest.py)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.serving import (EngineSnapshot, GenerationEngine,
                                restore_engine)

snap_dir, out_path, kill_point, kill_at, mode = sys.argv[1:6]
kill_at = int(kill_at)

paddle.seed(41)
cfg = llama_tiny(vocab_size=128, hidden_size=32, intermediate_size=64,
                 num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=4, max_position_embeddings=64,
                 dtype="float32")
m = LlamaForCausalLM(cfg)
m.eval()

# every macro-step boundary commits a snapshot; the SIGKILL then lands
# inside a deterministic commit (same flag-driven injection the training
# matrix uses)
paddle.set_flags({"FLAGS_engine_snapshot_dir": snap_dir,
                  "FLAGS_engine_snapshot_interval": 1})
store = EngineSnapshot(snap_dir)
max_new = 40 if mode in ("long", "preempt") else 10
if store.latest_step() is not None:
    eng = restore_engine(m, snap_dir)  # auto-resume: newest VALID snapshot
else:
    eng = GenerationEngine(m, max_batch=2, block_size=8, num_blocks=16,
                           decode_chunk=2, prefix_cache=True)
    eng.add_request("g", [5, 9, 17, 33, 2], max_new_tokens=max_new)
if mode == "preempt":
    eng.install_preemption_handler()

while eng.has_work():
    eng.step()
    print("STEP", eng._macro_steps, flush=True)
    if mode == "preempt" and eng.preemption_saved:
        print("PREEMPTED", store.latest_step(), flush=True)
        break
    # mid-flight join at boundary 1.  A resume FROM boundary 1 re-submits
    # here with the restored nonce counter, so the sampled stream is the
    # one the uninterrupted run drew — the counter itself is state.
    if eng._macro_steps == 1 and eng.result("s") is None:
        eng.add_request("s", [7, 11, 3], max_new_tokens=8,
                        temperature=5.0, seed=3)
    if kill_point and eng._macro_steps == kill_at:
        # armed AFTER this boundary's snapshot: the NEXT boundary's
        # commit hits the named protocol point and SIGKILLs
        paddle.set_flags({"FLAGS_checkpoint_kill_point": kill_point})

with open(out_path, "w") as f:
    json.dump({"g": eng.result("g"), "s": eng.result("s"),
               "latest": store.latest_step()}, f)
print("DONE", store.latest_step())
"""


def _run_server(tmp_path, snap_dir, out, kill_point="", kill_at=0,
                mode="std", popen=False):
    script = tmp_path / "server.py"
    script.write_text(_SERVER)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=repo_root + os.pathsep + os.environ.get("PYTHONPATH", ""))
    cmd = [sys.executable, str(script), str(snap_dir), str(out),
           kill_point, str(kill_at), mode]
    if popen:
        return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True, env=env)
    return subprocess.run(cmd, capture_output=True, text=True, timeout=300,
                          env=env)


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """Uninterrupted serving run: the token streams every killed-and-
    resumed variant must reproduce bit-for-bit."""
    td = tmp_path_factory.mktemp("snap_ref")
    out = td / "ref.json"
    r = _run_server(td, td / "snaps", out)
    assert "DONE" in r.stdout, (r.stdout + r.stderr)[-2000:]
    return json.loads(out.read_text())


@pytest.mark.parametrize("kill_point", KILL_POINTS)
def test_serving_kill_matrix_prior_snapshot_restorable(tmp_path, kill_point,
                                                       reference):
    """SIGKILL inside the engine-snapshot commit at each protocol point:
    the newest VALID snapshot is the boundary BEFORE the torn commit
    (or the freshly committed one for after-commit), and the resumed
    serving loop finishes both the greedy and the mid-flight sampled
    stream exactly as the uninterrupted run did."""
    from paddle_tpu.serving import EngineSnapshot

    snaps = tmp_path / "snaps"
    r = _run_server(tmp_path, snaps, tmp_path / "x.json",
                    kill_point=kill_point, kill_at=2)
    assert r.returncode == -signal.SIGKILL, (r.stdout + r.stderr)[-2000:]
    expected = 3 if kill_point == "after-commit" else 2
    assert EngineSnapshot(str(snaps)).latest_step() == expected

    out = tmp_path / "resumed.json"
    r2 = _run_server(tmp_path, snaps, out)
    assert "DONE" in r2.stdout, (r2.stdout + r2.stderr)[-2000:]
    resumed = json.loads(out.read_text())
    assert resumed["g"] == reference["g"]
    assert resumed["s"] == reference["s"]


def test_sigterm_preemption_end_to_end(tmp_path):
    """Production preemption shape: a REAL SIGTERM to a serving process
    flips the flag, the next macro-step boundary commits the final
    snapshot, the process exits cleanly, and the resumed process
    finishes the stream bit-identically vs an uninterrupted long run."""
    ref_out = tmp_path / "ref.json"
    r = _run_server(tmp_path, tmp_path / "snaps_ref", ref_out, mode="long")
    assert "DONE" in r.stdout, (r.stdout + r.stderr)[-2000:]
    ref = json.loads(ref_out.read_text())

    snaps = tmp_path / "snaps"
    proc = _run_server(tmp_path, snaps, tmp_path / "p.json", mode="preempt",
                       popen=True)
    try:
        for line in proc.stdout:
            if line.startswith("STEP"):
                proc.send_signal(signal.SIGTERM)  # handler flips a flag only
                break
        out, _ = proc.communicate(timeout=300)
    finally:
        proc.kill()
    assert "PREEMPTED" in out, out[-2000:]

    res_out = tmp_path / "resumed.json"
    r2 = _run_server(tmp_path, snaps, res_out, mode="long")
    assert "DONE" in r2.stdout, (r2.stdout + r2.stderr)[-2000:]
    assert json.loads(res_out.read_text())["g"] == ref["g"]
