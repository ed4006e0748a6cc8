"""The sparse-indexer / latent-window configuration's benchmark files: they load
through the harness as it is, the new cell's traffic is pangu's parameter for
parameter, every number of the configuration is the catalog's but the listed
cuts, the roofline returns the hand-reckoned numbers and counts the program's
own model, the family refuses what its model cannot express, the new driver
runs the cell end to end at a tiny size on the CPU (rehearsal 1: keys and
counts only), and the router and indexer checks tell float32 from
bfloat16."""

import json
import os
import re
import shutil
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from perfbench_tiny import BENCH, REPO, SEED, real_cell  # noqa: E402

sys.path.insert(0, REPO)
from perfbench import roofline, roofline_dsa_moe as rd, run, traffic  # noqa: E402

CELL = "serve-dots3-decode-ctx8k"
PANGU, ZAYA = "serve-pangu-decode-ctx8k", "serve-zaya-decode-ctx8k"
FULL, SLIDING = "full_attention", "sliding_attention"
TINY = {"name": "tiny-dsa", "family": "dsa_moe", "source": "test",
        "model_type": "dots3_note", "vocab_size": 256, "hidden_size": 64,
        "intermediate_size": 160, "moe_intermediate_size": 48,
        "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "layer_types": [FULL, FULL, SLIDING, SLIDING, SLIDING, FULL],
        "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 16,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "rope_theta": 10000, "swa_num_attention_heads": 3,
        "swa_q_lora_rank": 24, "swa_kv_lora_rank": 32,
        "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
        "swa_v_head_dim": 16, "swa_rope_theta": 100, "sliding_window_size": 9,
        "index_n_heads": 4, "index_head_dim": 16, "index_topk": 12,
        "apply_mla_qkv_lora_rescale": True, "n_routed_experts": 4,
        "n_shared_experts": 1, "num_experts_per_tok": 3,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
        "torch_dtype": "float32", "reduced": ["n_routed_experts"],
        "published": {"n_routed_experts": 8}, "share": {"first_expert": 2}}
# the catalog's `config` of dots3-note-prev, every key
_PERIOD = [FULL, SLIDING, SLIDING, SLIDING]
CATALOG = {
    "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
    "attention_gate_type": "headwise", "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
    "kv_lora_rank": 512, "layer_types": [FULL] + _PERIOD * 11 + [FULL],
    "max_position_embeddings": 524288, "model_type": "dots3_note",
    "moe_intermediate_size": 1536, "moe_layer_freq": 1,
    "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 46, "num_key_value_heads": 128, "q_lora_rank": 1024,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 80000000, "routed_scaling_factor": 1,
    "scoring_func": "sigmoid", "sliding_window_size": 513,
    "swa_attention_gate_type": "headwise", "swa_kv_lora_rank": 1024,
    "swa_num_attention_heads": 64, "swa_num_key_value_heads": 64,
    "swa_q_lora_rank": 1024, "swa_qk_nope_head_dim": 192,
    "swa_qk_rope_head_dim": 64, "swa_rope_theta": 50000, "swa_v_head_dim": 128,
    "tie_word_embeddings": False, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 152064}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(BENCH, CELL)


def test_the_new_cells_traffic_is_pangus_but_for_its_window(cell):
    """Two architectures under one mix: every parameter of the traffic and
    the engine is serve-pangu-decode-ctx8k's, only the window's length
    (`steps_per_second`) is each cell's."""
    assert cell["driver"] == "serve_closed_dsa_moe"
    assert cell["config_file"]["family"] == "dsa_moe"
    pangu = real_cell(PANGU)
    assert cell["engine"] == pangu["engine"] == {
        "max_batch": 32, "block_size": 128, "num_blocks": 32 * 67}
    ours, theirs = dict(cell["traffic"]), dict(pangu["traffic"])
    for t in (ours, theirs):
        t.pop("steps_per_second"), t.pop("steps_per_second_why")
    assert ours == theirs
    assert cell["check"]["pad_to"] == 8320 and cell["check"]["positions"] == [0, 8]
    ck = cell["check"]
    assert ck["margin_sigma"] == pangu["check"]["margin_sigma"] == 0.1
    assert ck["index_prompt"] == 8192 and ck["routing_prompt"] == 2048
    assert ck["margin_sigma"] < ck["logit_sigma"] < ck["tie_logit_sigma"]
    for why in ("margin_why", "tie_why", "logit_why", "routing_why",
                "index_why", "attention_why"):
        assert re.search(r"\d\.\d{4}", ck[why]), why     # its readings
    # the attention check reads inside and past the window and the top-k
    at, top_k = ck["attention_positions"], cell["config_file"]["index_topk"]
    window = cell["config_file"]["sliding_window_size"]
    assert min(at) < window < max(at) < ck["index_prompt"]
    assert any(t < top_k for t in at) and any(t >= top_k for t in at)
    assert ck["attention_sigma"] < ck["selected_attention_sigma"]
    assert set(cell["per_layer"]) == set(cell["metric_files"])
    assert len(cell["why"]) <= 200
    # every prompt is as long as the indexer's top-k or longer and three
    # windows long: token 9 reads a selection and a ring that has wrapped
    loop, cfg = traffic.ClosedLoop(cell["traffic"]), cell["config_file"]
    assert min(p for p, _n in loop.shapes()) >= cfg["index_topk"]
    assert min(p for p, _n in loop.shapes()) >= 3 * cfg["sliding_window_size"]


def test_every_number_is_the_catalogs_and_the_cuts_are_listed(cell):
    cfg = cell["config_file"]
    differs = {k for k, v in CATALOG.items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers",
                                              "n_routed_experts", "vocab_size"}
    assert {k: CATALOG[k] for k in differs} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (5, 32, 19008)
    assert cfg["vocab_size"] * 8 == CATALOG["vocab_size"]
    assert cfg["layer_types"][:5] == [FULL, FULL, SLIDING, SLIDING, SLIDING]
    for key in ("published", "share", "deployment", "assumed"):
        assert cfg[key], key
    for key in ("lora rescale", "indexer", "indexer rope", "routing groups",
                "correction bias", "norms"):
        assert key in cfg["assumed"], key
    assert cfg["share"]["first_expert"] == 96 and cfg["share"]["expert_ways"] == 8
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["source"] == ("https://huggingface.co/dots-studio/"
                               "dots3-note-prev/blob/main/config.json")
    assert [w for w in bench["workloads"] if w["name"] == CELL][0]["why"] == cell["why"]


def test_the_rooflines_parameter_count_is_the_built_models(cell):
    """At the published widths: 144,049,920 a full layer's attention (the
    indexer's 9,371,904 with it), 90,834,944 a sliding layer's, 23,592,960 an
    expert, 4,087,154,176 in all = 8.17 GB; and the whole count equal to the
    program's own model's, by shape: no weight is made."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.models.dsa_moe import DsaMoeForCausalLM
    from perfbench.families import dsa_moe as fam

    cfg = cell["config_file"]
    assert rd.attention_params(cfg, False) == 144_049_920
    assert rd.indexer_params(cfg) == 9_371_904
    assert rd.attention_params(cfg, True) == 90_834_944
    assert rd.expert_params(cfg) == 23_592_960
    assert rd.model_params(cfg) == 4_087_154_176
    assert round(rd.weight_bytes(cfg) / 1e9, 2) == 8.17
    assert (rd.row_bytes(cfg, False), rd.row_bytes(cfg, True),
            rd.index_key_bytes(cfg)) == (1152, 2176, 256)
    # resident: weights, 2,144 + 32 pages of 128 positions of a 640-lane row
    # and a 128-lane key on 2 layers, 32 rings of 5 blocks of 1,152 lanes on 3
    resident = (rd.weight_bytes(cfg) + 2176 * 128 * 2 * (640 + 128) * 2
                + 32 * 5 * 128 * 3 * 1152 * 2)
    assert resident / (15.75 * 2 ** 30) > 0.25

    def shapes():
        model = DsaMoeForCausalLM(fam.model_config(cfg))
        return {k: v._value for k, v in model.state_dict().items()}

    try:
        built = jax.eval_shape(shapes)
    finally:
        paddle.seed(0)      # the trace drew keys from the global generator
    assert sum(int(np.prod(a.shape)) for a in built.values()) \
        == rd.model_params(cfg)
    assert built["model.layers.1.mlp.gate_up"].shape == (32, 5120, 3072)
    assert built["model.layers.1.mlp.score_correction_bias"].shape == (256,)
    assert built["model.layers.1.self_attn.indexer.wq.weight"].shape == (1024, 8192)
    assert built["model.layers.2.self_attn.kv_b_proj.weight"].shape == (1024, 20480)
    assert {str(a.dtype) for a in built.values()} == {"bfloat16"}


def test_roofline_returns_the_hand_reckoned_numbers(cell):
    import perfbench.families.dsa_moe  # noqa: F401  (registers them)

    cfg = cell["config_file"]
    fn = roofline.FUNCTIONS["dsa_moe_decode_token_step_min_s"]
    facts = {"rows": 32, "moe_touched_per_layer_step": 20.4,
             "moe_held_per_layer_step": 32.0, "live_index_positions": 157_000,
             "selected_positions": 60_000, "live_window_positions": 16_416}
    fixed = rd.fixed_params(cfg)
    assert fixed == (144_049_920 * 2 + 90_834_944 * 3 + 5 * 2 * 5120
                     + 3 * 13824 * 5120 + 4 * (23_592_960 + 5121 * 256)
                     + 19009 * 5120)
    by = (2 * (fixed + 32 * 5120 + 4 * 20.4 * 23_592_960)
          + 2 * (157_000 * 256 + 60_000 * 1152) + 3 * 16_416 * 2176)
    assert fn(cfg, facts, "TPU v5e") == pytest.approx(by / 819e9)
    assert 7.0e-3 < fn(cfg, facts, "TPU v5e") < 8.0e-3      # 7.5 ms at 819 GB/s
    busy = dict(facts, rows=8192, moe_held_per_layer_step=8000.0)
    assert fn(cfg, busy, "TPU v5e") > by / 819e9             # FLOPs bind
    # an 8k admission: about 17 TFLOP of products, 1.1 of the indexer's
    # scores and attention over the selection
    assert 18e12 < rd.prefill_flops(cfg, 8192) < 21e12
    assert rd.selected_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048
    fn = roofline.FUNCTIONS["dsa_moe_prefill_min_s"]
    facts = {"admitted_prompt_lens": [2048, 8192], "moe_prefill_held_share": 0.1}
    mean = (rd.prefill_flops(cfg, 2048, 0.1) + rd.prefill_flops(cfg, 8192, 0.1)) / 2
    assert fn(cfg, facts, "TPU v5e") == pytest.approx(mean / 197e12)


def test_the_family_refuses_what_the_model_cannot_express(cell):
    from perfbench.families import dsa_moe as fam

    cfg = dict(cell["config_file"])
    fam.check(cfg)
    for key, bad in (("attention_bias", True), ("hidden_act", "gelu"),
                     ("tie_word_embeddings", True),
                     ("attention_gate_type", "elementwise"),
                     ("swa_attention_gate_type", None),
                     ("num_key_value_heads", 8),
                     ("swa_num_key_value_heads", 16),
                     ("scoring_func", "softmax"), ("topk_method", "greedy"),
                     ("moe_layer_freq", 2), ("rope_scaling", {"type": "yarn"}),
                     ("torch_dtype", "float16"),
                     ("layer_types", [FULL] * 3), ("n_group", 8),
                     ("num_nextn_predict_layers", 1)):
        with pytest.raises(ValueError):
            fam.check({**cfg, key: bad})
    with pytest.raises(ValueError, match="outside the router"):
        fam.check({**cfg, "share": {"first_expert": 250}})
    c = fam.model_config(cfg)
    assert c.held == (96, 32) and c.n_routed_experts == 256
    assert c.vocab_size == 19008 and c.index_topk == 2048
    assert c.layer_types == (FULL, FULL, SLIDING, SLIDING, SLIDING)
    with pytest.raises(ValueError, match="no cell trains"):
        fam.build(cfg, 0, training=True)
    assert fam.held_experts(cfg) == (96, 32) and fam.routed_experts(cfg) == 256


def _tiny_root(tmp_path):
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), tmp_path / "layer_metrics")
    for d in ("configs", "workloads"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "tiny-dsa.json").write_text(json.dumps(TINY))
    cell = real_cell(CELL)
    cell.update(config="tiny-dsa", trace_seconds=0.2,
                engine={"max_batch": 4, "block_size": 4, "num_blocks": 64},
                check={"sample": 2, "positions": [0, 8], "pad_to": 96,
                       "margin_sigma": 0.1, "tie_tau": 0.01,
                       "index_tie_tau": 1e-4, "tie_margin_sigma": 1.5,
                       "logit_rows": 3, "logit_sigma": 0.01,
                       "tie_logit_sigma": 2.0, "routing_prompt": 96,
                       "routing_agreement": 0.95, "index_prompt": 96,
                       "index_agreement": 0.999,
                       "attention_positions": [8, 9, 11, 12, 20, 95],
                       "attention_sigma": 1e-3,
                       "selected_attention_sigma": 1e-3})
    cell["traffic"].update(clients=4, prompt={"choices": [16, 32]}, cycle=8,
                           steps_per_second=40,
                           output={"uniform": [16, 32], "step": 16},
                           first_output={"uniform": [16, 32], "step": 16})
    (tmp_path / "workloads" / "dsa.json").write_text(json.dumps(cell))
    return tmp_path


def test_the_new_cell_runs_end_to_end_tiny_through_its_driver(tmp_path, capsys):
    """The cell once, traced, through tools/cell_controls.py: the run, then
    the driver's own rows of logits and agreement checks against references
    that leave a mechanism out or lower a type, each of which must read NOT
    correct (float32 on both sides here, so the tiny cell's limits tell even
    the lowered types)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import cell_controls

    root = _tiny_root(tmp_path)
    told = []
    out = cell_controls.run(str(root), "dsa", SEED, 0.5, rows=1,
                            say=told.append, trace_dir=str(root / "trace"))
    traced = json.loads(told[0])
    assert traced["correct"] is True and traced["failed"] == 0
    controls, must_fail = cell_controls.BY_DRIVER["serve_closed_dsa_moe"]
    assert [name for name, _c in controls] == list(out)
    assert out["none (the reference as it is)"][0] is True
    # a bfloat16 router chooses as float32 does over the tiny cell's 384
    # (token, layer) pairs of 8 experts; at 64 experts it does not
    # (test_routing_agreement_tells_float32_from_bfloat16)
    passed = [name for name in must_fail if out[name][0]]
    assert passed == ["router in bfloat16"], passed
    # the attention check alone tells the window, the gate and the selection
    for name in ("window ignored", "gate left out", "every position attended"):
        assert not all(ok for what, ok in out[name][1].items()
                       if "decode attention" in what), name
    said = capsys.readouterr().out
    assert "expert load over the window's" in said and "1/2 is even" in said
    assert "the indexer read" in said and "admissions in the traced part" in said
    assert said.count("on the reference's own layer inputs at positions") == 3
    assert said.count("in the engine as the window left it") >= 1
    assert "FAILED" not in said
    # 4 expert layers x 96 tokens; 2 full layers x (96 - 12) queries x 12
    # (every reference piece at the length of the token check's, pad_to)
    assert "the reference's experts for 1.0000 of 384 (token, expert layer)" in said
    assert "selects 1.000000 of the reference's 2016 selected" in said
    # the end-to-end readings are said, not reported, in a traced run
    assert re.search(r"end-to-end readings of this TRACED run \(not reported "
                     r"as metrics\): serve_tok_s [\d.]+, tpot_p90_ms [\d.]+, "
                     r"setup_s [\d.]+", said)
    # no device plane on the CPU: the trace readers report nothing; spans
    # and counters do
    assert set(traced["metrics"]) == {
        "engine_step_ms", "admit_ms.serve_tok", "batch_occupancy",
        "window_compiles.serve_tok", "expert_peak_load.serve_tok",
        "sparse_select_share.serve_tok"}
    share = traced["metrics"]["sparse_select_share.serve_tok"]["value"]
    # rows of 16-64 positions choosing 12: a share well under 1
    assert 12 / 64 < share < 0.6


_ROUTED = {**TINY, "n_routed_experts": 16, "published": {"n_routed_experts": 64},
           "share": {"first_expert": 16}, "num_experts_per_tok": 6}


@pytest.fixture(scope="module")
def routed_model():
    import paddle_tpu as paddle
    from paddle_tpu.models.dsa_moe import DsaMoeForCausalLM
    from perfbench.families import dsa_moe as fam

    paddle.seed(5)
    model = DsaMoeForCausalLM(fam.model_config(_ROUTED))
    fam.perturb_norms(model, 5)
    fam.draw_bias(model, 5)
    model.eval()
    return model


@pytest.mark.parametrize("lower,low,high", [
    (None, 1.0, 1.0), ("dtype", 0.0, 0.999), ("router_dtype", 0.0, 0.999)])
def test_routing_agreement_tells_float32_from_bfloat16(routed_model, lower,
                                                        low, high):
    """On the reference's own router inputs the program's router (sigmoid,
    chosen with the correction bias) agrees on every (token, expert layer)
    pair; with the reference's router (or all of it) in bfloat16 it does
    not."""
    from perfbench import reference_dsa_moe as ref
    from perfbench.families import dsa_moe as fam

    model = routed_model
    sizes = fam.reference_sizes(_ROUTED)
    if lower:
        sizes[lower] = "bfloat16"
    ids = np.random.default_rng(5).integers(0, 256, 128).astype(np.int32)
    share, pairs = fam.routing_agreement(model, fam.reference_weights(model),
                                         sizes, ids, ref)
    assert pairs == 4 * 128 and low <= share <= high, share


def test_benchmark_json_only_gained_entries_since_pr_37():
    """This PR's entries in BENCHMARK.json come AFTER the earlier ones in
    every list, stated about positions and never about a list's end; the
    cell is appended to the lists the accepted tests let it join and to no
    list they hold to other cells; no bound and not `run_seconds` moved."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    names = [w["name"] for w in bench["workloads"]]
    assert names[:6] == ["train-mistral7b-seq4k", "serve-internlm2-decode-sat",
                         PANGU, "serve-laguna-decode-ctx8k", ZAYA, CELL]
    assert [c["name"] for c in bench["configs"]][4:6] == ["zaya1-8b",
                                                          "dots3-note-prev"]
    assert bench["workloads"][5] == {
        "name": CELL, "config": "dots3-note-prev", "traffic": "decode-ctx8k",
        "chips": 1, "why": real_cell(CELL)["why"]}
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in ("serve_tok_s", "tpot_p90_ms", "engine_step_ms",
                 "admit_ms.serve_tok", "batch_occupancy",
                 "window_compiles.serve_tok", "device_idle_share.serve_tok",
                 "expert_peak_load.serve_tok"):
        cells = by_name[name]["workloads"]
        assert cells.index(CELL) == cells.index(ZAYA) + 1, name
    for name in ("kv_read_amplification.serve_tok", "experts_touched.serve_tok",
                 "swa_moe_decode_roofline_share", "mla_moe_decode_roofline_share",
                 "cca_moe_decode_roofline_share", "decode_roofline_share",
                 "train_device_mfu"):
        assert CELL not in by_name[name]["workloads"], name
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("experts_touched.serve_tok")
    assert names[at + 1:at + 4] == ["dsa_moe_decode_roofline_share",
                                    "dsa_moe_prefill_roofline_share",
                                    "sparse_select_share.serve_tok"]
    for name in names[at + 1:at + 4]:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tok_s"
        assert set(by_name[name]) == {"name", "unit", "better", "source",
                                      "layer", "moves", "workloads"}
    assert by_name["sparse_select_share.serve_tok"]["source"] == "program_counter"
    assert by_name["dsa_moe_decode_roofline_share"]["layer"] == "kernels"
    assert set(real_cell(CELL)["per_layer"]) == {
        m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert bench["run_seconds"] == 45
    assert {m["name"]: m["bound"] for m in bench["end_to_end"]} == {
        "train_tok_s": 0.01, "serve_tok_s": 0.075, "tpot_p90_ms": 0.065,
        "setup_s": 0.1}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
