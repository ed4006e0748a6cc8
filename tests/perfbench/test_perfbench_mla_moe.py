"""The latent-attention / routed-expert configuration's benchmark files: they
load through the harness as it is, the new driver runs the new cell end to end
at a tiny size on the CPU (rehearsal 1: keys and counts only), and the roofline
functions return the hand-reckoned numbers of the published widths."""

import json
import os
import shutil
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from perfbench_tiny import BENCH, REPO, SEED, real_cell  # noqa: E402

sys.path.insert(0, REPO)
from perfbench import roofline, roofline_mla_moe as rm, run, traffic  # noqa: E402

CELL = "serve-pangu-decode-ctx8k"
TINY = {"name": "tiny-moe", "family": "mla_moe", "source": "test",
        "hidden_size": 64, "intermediate_size": 160, "moe_intermediate_size": 48,
        "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 32,
        "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "n_routed_experts": 4, "n_shared_experts": 1,
        "num_experts_per_tok": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 2.5, "sandwich_norm": True, "vocab_size": 256,
        "max_position_embeddings": 512, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "num_nextn_predict_layers": 0, "torch_dtype": "float32",
        "reduced": ["n_routed_experts"], "published": {"n_routed_experts": 8},
        "share": {"first_expert": 2}}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(BENCH, CELL)


def test_the_new_files_load_and_say_what_the_issue_asked(cell):
    cfg = cell["config_file"]
    assert cell["driver"] == "serve_closed_moe" and cfg["family"] == "mla_moe"
    assert cell["engine"] == {"max_batch": 32, "block_size": 128,
                              "num_blocks": 32 * 67}
    tr = cell["traffic"]
    assert tr["clients"] == 32 and tr["cycle"] == 128 and tr["warm_steps"] == 2
    assert tr["prompt"] == {"choices": [2048, 4096, 8192]}
    assert tr["output"] == {"uniform": [128, 384], "step": 64}
    assert cell["check"]["pad_to"] == 8320 and cell["check"]["positions"] == [0, 8]
    assert 0.8 < cell["check"]["routing_agreement"] < 1.0
    assert set(cell["per_layer"]) == set(cell["metric_files"]) and len(cell["per_layer"]) == 7
    assert len(cell["why"]) <= 200
    # the first wave warms every shape the loop can send: 9 of them
    loop, bs = traffic.ClosedLoop(tr), cell["engine"]["block_size"]
    shape = lambda pr, out: (pr, -(-(pr + out) // bs))  # noqa: E731
    sent = {shape(*s) for s in loop.shapes()}
    assert len(sent) == 9
    assert sent == {shape(r.prompt_len, r.max_new) for r in loop.first_wave()}
    assert max(n for _p, n in sent) == 67


def test_every_width_is_the_catalogs_and_the_cuts_are_listed(cell):
    cfg = cell["config_file"]
    catalog = {"attention_bias": False, "first_k_dense_replace": 3,
               "hidden_act": "silu", "hidden_size": 7680,
               "intermediate_size": 18432, "kv_lora_rank": 512,
               "max_position_embeddings": 131072,
               "model_type": "pangu_ultra_moe", "moe_intermediate_size": 2048,
               "n_routed_experts": 256, "n_shared_experts": 1,
               "norm_topk_prob": True, "num_attention_heads": 128,
               "num_experts_per_tok": 8, "num_hidden_layers": 61,
               "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
               "q_lora_rank": 1536, "qk_nope_head_dim": 128,
               "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
               "rope_theta": 25600000, "routed_scaling_factor": 2.5,
               "sandwich_norm": True, "tie_word_embeddings": False,
               "v_head_dim": 128, "vocab_size": 153600}
    differs = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size", "num_nextn_predict_layers"}
    assert {k: catalog[k] for k in differs} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"],
            cfg["n_routed_experts"], cfg["vocab_size"]) == (5, 1, 16, 19200)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert [w for w in bench["workloads"] if w["name"] == CELL][0]["why"] == cell["why"]


def test_roofline_returns_the_hand_reckoned_numbers(cell):
    cfg = cell["config_file"]
    assert rm.attention_params(cfg) == 196_575_232
    assert rm.expert_params(cfg) == 47_185_920
    assert rm.dense_layer_params(cfg) == 196_575_232 + 424_673_280
    assert round(rm.dense_layer_params(cfg) / 1e6, 1) == 621.2
    assert round(rm.expert_layer_params(cfg) / 1e6, 1) == 1000.7
    assert round(rm.model_params(cfg) / 1e6) == 4919
    assert rm.latent_bytes_per_token(cfg) == 5760
    # the family's import registered the function where run.read_metric looks
    import perfbench.families.mla_moe  # noqa: F401

    fn = roofline.FUNCTIONS["mla_moe_decode_token_step_min_s"]
    facts = {"rows": 32, "live_kv_tokens": 32 * 5000,
             "moe_touched_per_layer_step": 10.0, "moe_held_per_layer_step": 16.0}
    fixed = (rm.dense_layer_params(cfg) + 4 * rm.expert_layer_fixed_params(cfg)
             + 19200 * 7680)
    by = 2 * (fixed + 32 * 7680 + 4 * 10 * 47_185_920) + 160_000 * 5760
    assert fn(cfg, facts, "TPU v5e") == pytest.approx(by / 819e9)
    assert 9.5e-3 < fn(cfg, facts, "TPU v5e") < 12e-3   # the issue's "about 11 ms"
    # with every expert idle and no cache it is the fixed matrices alone
    idle = dict(facts, live_kv_tokens=0, moe_touched_per_layer_step=0.0,
                moe_held_per_layer_step=0.0)
    assert fn(cfg, idle, "TPU v5e") == pytest.approx(2 * (fixed + 32 * 7680) / 819e9)
    # enough rows and the FLOP bound takes over
    busy = dict(idle, rows=4096)
    assert fn(cfg, busy, "TPU v5e") == pytest.approx(2.0 * fixed * 4096 / 197e12)


def test_the_family_refuses_what_the_model_cannot_express(cell):
    from perfbench.families import mla_moe as fam

    cfg = dict(cell["config_file"])
    fam.check(cfg)
    for key, bad in (("attention_bias", True), ("hidden_act", "gelu"),
                     ("num_key_value_heads", 8), ("num_nextn_predict_layers", 1),
                     ("torch_dtype", "float16")):
        with pytest.raises(ValueError):
            fam.check({**cfg, key: bad})
    with pytest.raises(ValueError, match="outside the router"):
        fam.check({**cfg, "share": {"first_expert": 250}})
    c = fam.model_config(cfg)
    assert c.held == (48, 16) and c.n_routed_experts == 256 and c.vocab_size == 19200
    with pytest.raises(ValueError, match="no cell trains"):
        fam.build(cfg, 0, training=True)


def _tiny_root(tmp_path):
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), tmp_path / "layer_metrics")
    for d in ("configs", "workloads"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "tiny-moe.json").write_text(json.dumps(TINY))
    cell = real_cell(CELL)
    cell.update(config="tiny-moe", trace_seconds=0.2,
                engine={"max_batch": 4, "block_size": 16, "num_blocks": 32},
                check={"sample": 2, "positions": [0, 8], "pad_to": 96,
                       "margin_sigma": 0.1, "routing_prompt": 48,
                       "routing_agreement": 0.95})
    cell["traffic"].update(clients=4, prompt={"choices": [16, 32]}, cycle=8,
                           steps_per_second=40,
                           output={"uniform": [16, 32], "step": 16},
                           first_output={"uniform": [16, 32], "step": 16})
    (tmp_path / "workloads" / "pangu.json").write_text(json.dumps(cell))
    return tmp_path


def test_the_new_cell_runs_end_to_end_tiny_through_serve_closed_moe(tmp_path, capsys):
    root, cell = _tiny_root(tmp_path), real_cell(CELL)
    plain = run.run_cell(str(root), "pangu", SEED, 0.5, False)
    assert plain["correct"] is True and plain["failed"] == 0
    assert set(plain["metrics"]) == set(cell["end_to_end"])
    said = capsys.readouterr().out
    assert "expert load over the window's" in said and "1/2 is even" in said
    # 2 expert layers x 48 tokens, the same numbers on both sides: every pair
    assert "the reference's experts for 1.0000 of 96 (token, expert layer)" in said
    traced = run.run_cell(str(root), "pangu", SEED, 0.5, True,
                          trace_dir=str(root / "trace"))
    assert traced["correct"] is True
    names = set(traced["metrics"])
    # no device plane on the CPU: the trace readers (the two shares of the
    # device's time) report nothing; spans and counters do
    assert names == {"engine_step_ms", "admit_ms.serve_tok", "batch_occupancy",
                     "window_compiles.serve_tok", "expert_peak_load.serve_tok"}
    assert traced["metrics"]["window_compiles.serve_tok"]["value"] == 0
    assert 1.0 <= traced["metrics"]["expert_peak_load.serve_tok"]["value"] <= 4.0
    json.dumps(traced)


def test_benchmark_json_only_gained_entries():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]] == [
        "train-mistral7b-seq4k", "serve-internlm2-decode-sat", CELL]
    assert [c["name"] for c in bench["configs"]][-1] == "openpangu-ultra-moe-718b"
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in real_cell(CELL)["per_layer"] + ["serve_tok_s", "tpot_p90_ms"]:
        assert by_name[name]["workloads"][-1] == CELL, name
    assert by_name["decode_roofline_share"]["workloads"] == ["serve-internlm2-decode-sat"]
    new = [m["name"] for m in bench["per_layer"]][-2:]
    assert new == ["mla_moe_decode_roofline_share", "expert_peak_load.serve_tok"]
    assert by_name["expert_peak_load.serve_tok"]["layer"] == "model step"
