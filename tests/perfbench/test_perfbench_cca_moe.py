"""The convolved-latent attention / top-1 expert configuration's benchmark
files: they load through the harness as it is, the configuration's numbers are
the catalog row's, the roofline's parameter count is the built model's own (by
shape, no weights made), the family refuses what its model cannot express, the
new driver runs the new cell end to end at a tiny size on the CPU (rehearsal
1: keys and counts only), the controls read NOT correct through the driver's
own comparison, and BENCHMARK.json only gained entries, stated by positions."""

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
from perfbench import roofline, roofline_cca_moe as rc, run, traffic  # noqa: E402

CELL = "serve-zaya-decode-ctx8k"
LAGUNA, PANGU = "serve-laguna-decode-ctx8k", "serve-pangu-decode-ctx8k"
TINY = {"name": "tiny-cca", "family": "cca_moe", "source": "test",
        "model_type": "zaya", "hidden_size": 64, "num_hidden_layers": 3,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "cca_time0": 2, "cca_time1": 2, "partial_rotary_factor": 0.5,
        "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                       "rope_theta": 10000,
                                       "rope_type": "default"},
                            "rope_type": "default"},
        "num_experts": 8, "num_experts_per_tok": 1, "moe_intermediate_size": 48,
        "router_hidden_size": 16, "vocab_size": 256,
        "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
        "attention_bias": False, "lm_head_bias": False, "hidden_act": "silu",
        "tie_word_embeddings": True, "sliding_window": None,
        "layer_types": ["hybrid"] * 5, "torch_dtype": "float32",
        "reduced": ["num_hidden_layers"], "published": {"num_hidden_layers": 5}}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(BENCH, CELL)


def test_the_new_files_load_and_say_what_the_issue_asked(cell):
    cfg = cell["config_file"]
    assert cell["driver"] == "serve_closed_cca_moe" and cfg["family"] == "cca_moe"
    assert cell["chips"] == 1
    assert cell["engine"] == {"max_batch": 32, "block_size": 128,
                              "num_blocks": 32 * 67}
    # pangu's and laguna's traffic, parameter for parameter: three expert
    # models under one mix (only the window's length is each cell's)
    mixes = [dict(real_cell(name)["traffic"]) for name in (CELL, PANGU, LAGUNA)]
    for t in mixes:
        t.pop("steps_per_second"), t.pop("steps_per_second_why")
    assert mixes[0] == mixes[1] == mixes[2]
    ck = cell["check"]
    assert ck["pad_to"] == 8320 and ck["positions"] == [0, 8]
    assert ck["margin_sigma"] == real_cell(PANGU)["check"]["margin_sigma"] == 0.1
    assert ck["margin_sigma"] < ck["logit_sigma"] < ck["tie_logit_sigma"]
    assert ck["margin_sigma"] < ck["tie_margin_sigma"]
    assert 0 < ck["tie_tau"] < 1 and 0.9 < ck["routing_agreement"] < 1
    for why in ("margin_why", "tie_why", "logit_why", "routing_why"):
        assert "my chip runs, PR 34" in ck[why], why
    assert "my chip runs, PR 34" in cell["traffic"]["steps_per_second_why"]
    assert "my chip run" in cell["sizing"]
    assert set(cell["per_layer"]) == set(cell["metric_files"])
    assert cell["per_layer"][-3:] == ["cca_moe_decode_roofline_share",
                                      "cca_moe_prefill_roofline_share",
                                      "experts_touched.serve_tok"]
    assert len(cell["why"]) <= 200
    # the first wave warms every shape the loop can send
    loop, bs = traffic.ClosedLoop(cell["traffic"]), cell["engine"]["block_size"]
    shape = lambda pr, out: (pr, -(-(pr + out) // bs))  # noqa: E731
    sent = {shape(*s) for s in loop.shapes()}
    assert sent == {shape(r.prompt_len, r.max_new) for r in loop.first_wave()}
    assert max(n for _p, n in sent) == 67


def test_every_number_is_the_catalogs_and_the_cut_is_listed(cell):
    cfg = cell["config_file"]
    catalog = {
        "attention_bias": False, "cca_time0": 2, "cca_time1": 2, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048,
        "layer_types": ["hybrid"] * 40, "lm_head_bias": False,
        "max_position_embeddings": 131072, "model_type": "zaya",
        "moe_intermediate_size": 2048, "num_attention_heads": 8,
        "num_experts": 16, "num_experts_per_tok": 1, "num_hidden_layers": 40,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.5,
        "rms_norm_eps": 1e-05,
        "rope_parameters": {
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"},
        "router_hidden_size": 256, "sliding_window": None,
        "tie_word_embeddings": True, "vocab_size": 262272}
    differs = {k for k, v in catalog.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers"}
    assert cfg["published"] == {"num_hidden_layers": 40}
    assert cfg["num_hidden_layers"] in (12, 16)
    for key in ("published", "deployment", "assumed", "source"):
        assert cfg[key], key
    assert cfg["source"] == ("https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/"
                             "config.json")
    # every form the config does not fix is listed, one line each, with the
    # paper it is read from
    for form in ("convolution grouping", "q-k mean", "norm with a temperature",
                 "value shift", "router MLP", "carry across depth",
                 "balancing bias", "skip choice", "scaled residual"):
        assert "arXiv:25" in cfg["assumed"][form], form
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert entry["file"] == "perfbench/configs/zaya1-8b.json"
    assert [w for w in bench["workloads"] if w["name"] == CELL][0]["why"] == cell["why"]


def test_the_rooflines_parameter_count_is_the_built_models(cell):
    """207,583,763 a layer at the published widths (16 x 12,582,912 experts +
    5,242,880 W_qk, W_v, W_o + 332,800 convolutions + 661,009 router + 20,482
    norms, scales, tau), and the whole count equal to the program's own
    model's, by shape: no weight is made."""
    import jax

    from perfbench.families import cca_moe as fam

    cfg = cell["config_file"]
    assert rc.expert_params(cfg) == 12_582_912
    assert rc.attention_matrices(cfg) == 5_242_880
    assert rc.conv_params(cfg) == 332_800
    assert rc.router_params(cfg) == 661_009
    assert rc.vector_params(cfg) == 20_482
    assert rc.layer_params(cfg) == 207_583_763
    layers = cfg["num_hidden_layers"]
    assert rc.model_params(cfg) == layers * 207_583_763 + 537_133_056 + 2048
    assert rc.kv_bytes_per_token(cfg) == layers * 1024
    assert rc.state_bytes_per_slot(cfg) == layers * 5376
    if layers == 16:
        assert round(rc.weight_bytes(cfg) / 1e9, 2) == 7.72
    # resident: weights, 2,144 + 32 pages of 128 positions, the slots' state
    resident = (rc.weight_bytes(cfg) + 2176 * 128 * rc.kv_bytes_per_token(cfg)
                + 32 * rc.state_bytes_per_slot(cfg))
    assert resident / (15.75 * 2 ** 30) > 0.25

    import paddle_tpu as paddle
    from paddle_tpu.models.cca_moe import CcaMoeForCausalLM

    def shapes():
        model = CcaMoeForCausalLM(fam.model_config(cfg))
        return {k: v._value for k, v in model.state_dict().items()}

    try:
        built = jax.eval_shape(shapes)
    finally:
        paddle.seed(0)      # the trace drew keys from the global generator
    assert sum(int(np.prod(a.shape)) for a in built.values()) \
        == rc.model_params(cfg)
    assert built["model.expert_gate_up"].shape == (layers * 16, 2048, 4096)
    assert {str(a.dtype) for a in built.values()} == {"bfloat16"}


def test_roofline_returns_the_hand_reckoned_numbers(cell):
    import perfbench.families.cca_moe  # noqa: F401  (registers them)

    cfg = {**cell["config_file"], "num_hidden_layers": 16}
    fn = roofline.FUNCTIONS["cca_moe_decode_token_step_min_s"]
    facts = {"rows": 32, "live_kv_tokens": 157_000,
             "moe_touched_per_layer_step": 13.7, "moe_held_per_layer_step": 30.0}
    fixed = 16 * (5_242_880 + 332_800 + 661_009 + 20_482) + 537_133_056 + 2048
    assert rc.fixed_params(cfg) == fixed
    by = (2 * (fixed + 32 * 2048 + 16 * 13.7 * 12_582_912)
          + 157_000 * 16_384 + 2 * 32 * 16 * 5376)
    assert fn(cfg, facts, "TPU v5e") == pytest.approx(by / 819e9)
    assert 11.0e-3 < fn(cfg, facts, "TPU v5e") < 11.8e-3    # the issue's 11.4 ms
    busy = dict(facts, rows=8192, moe_held_per_layer_step=8000.0)
    assert fn(cfg, busy, "TPU v5e") > by / 819e9             # FLOPs bind
    # a 4.8k-token admission is about 3.5 TFLOP (the issue's 3.7)
    assert 3.3e12 < rc.prefill_flops(cfg, 4800, 16 / 17) < 3.8e12
    fn = roofline.FUNCTIONS["cca_moe_prefill_min_s"]
    facts = {"admitted_prompt_lens": [2048, 8192], "moe_prefill_held_share": 0.9}
    mean = (rc.prefill_flops(cfg, 2048, 0.9) + rc.prefill_flops(cfg, 8192, 0.9)) / 2
    assert fn(cfg, facts, "TPU v5e") == pytest.approx(mean / 197e12)
    assert rc.prefill_flops(cfg, 2048, 0.5) < rc.prefill_flops(cfg, 2048)


def test_the_family_refuses_what_the_model_cannot_express(cell):
    from perfbench.families import cca_moe as fam

    cfg = dict(cell["config_file"])
    fam.check(cfg)
    for key, bad in (("attention_bias", True), ("lm_head_bias", True),
                     ("hidden_act", "gelu"), ("tie_word_embeddings", False),
                     ("sliding_window", 4096), ("torch_dtype", "float16"),
                     ("layer_types", ["hybrid", "hybrid_sliding"] * 20),
                     ("layer_types", ["hybrid"] * 3),
                     ("partial_rotary_factor", 1.0),
                     ("qk_norm", True), ("num_nextn_predict_layers", 1)):
        with pytest.raises(ValueError):
            fam.check({**cfg, key: bad})
    rope = json.loads(json.dumps(cfg["rope_parameters"]))
    rope["hybrid"]["rope_type"] = "yarn"
    with pytest.raises(ValueError, match="rope_type"):
        fam.check({**cfg, "rope_parameters": rope})
    with pytest.raises(ValueError, match="outside the router"):
        fam.check({**cfg, "share": {"first_expert": 12, "held_experts": 8}})
    with pytest.raises(ValueError, match="kernels of 2"):
        fam.model_config({**cfg, "cca_time0": 4})
    with pytest.raises(ValueError, match="top-1"):
        fam.model_config({**cfg, "num_experts_per_tok": 2})
    c = fam.model_config(cfg)
    assert c.held == (0, 16) and c.num_experts == 16 and c.vocab_size == 262272
    assert c.rope_theta == 5e6 and c.partial_rotary_factor == 0.5
    assert c.channels == 1280 and c.router_hidden_size == 256
    with pytest.raises(ValueError, match="no cell trains"):
        fam.build(cfg, 0, training=True)
    assert fam.held_experts(cfg) == (0, 16) and fam.routed_experts(cfg) == 16
    share = {**cfg, "share": {"first_expert": 8, "held_experts": 8}}
    assert fam.held_experts(share) == (8, 8) and rc.held_experts(share) == 8


def _tiny_root(tmp_path):
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), tmp_path / "layer_metrics")
    for d in ("configs", "workloads"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "tiny-cca.json").write_text(json.dumps(TINY))
    cell = real_cell(CELL)
    cell.update(config="tiny-cca", trace_seconds=0.2,
                engine={"max_batch": 4, "block_size": 4, "num_blocks": 64},
                check={"sample": 2, "positions": [0, 8], "pad_to": 96,
                       "margin_sigma": 0.1, "tie_tau": 0.0005,
                       "tie_margin_sigma": 1.5, "logit_rows": 3,
                       "logit_sigma": 0.01, "tie_logit_sigma": 2.0,
                       "routing_prompt": 48, "routing_agreement": 0.95})
    cell["traffic"].update(clients=4, prompt={"choices": [16, 32]}, cycle=8,
                           steps_per_second=38,    # 19: rows in mid-flight
                           output={"uniform": [16, 32], "step": 16},
                           first_output={"uniform": [16, 32], "step": 16})
    (tmp_path / "workloads" / "zaya.json").write_text(json.dumps(cell))
    return tmp_path


def test_the_new_cell_runs_end_to_end_tiny_through_its_driver(tmp_path, capsys):
    root, cell = _tiny_root(tmp_path), real_cell(CELL)
    plain = run.run_cell(str(root), "zaya", SEED, 0.5, False)
    assert plain["correct"] is True and plain["failed"] == 0
    assert set(plain["metrics"]) == set(cell["end_to_end"])
    said = capsys.readouterr().out
    assert "expert load over the window's" in said and "chose skip" in said
    assert "state a slot" in said and "admissions in the traced part" in said
    # float32 on both sides: the resident engine's rows ARE the reference's
    n, live = map(int, re.search(r"(\d+) rows of logits from the resident "
                                 r"engine \((\d+) rows live\)", said).groups())
    assert 1 <= n <= min(3, live) <= 4
    assert said.count("in the engine as the window left it") == n
    assert "FAILED" not in said
    # 3 layers x 48 tokens, the same numbers on both sides: every pair
    assert "the reference's choice for 1.0000 of 144 (token, layer) pairs" in said
    traced = run.run_cell(str(root), "zaya", SEED, 0.5, True,
                          trace_dir=str(root / "trace"))
    assert traced["correct"] is True
    # no device plane on the CPU: the trace readers (the three shares of the
    # device's time) report nothing; spans and counters do
    assert set(traced["metrics"]) == {
        "engine_step_ms", "admit_ms.serve_tok", "batch_occupancy",
        "window_compiles.serve_tok", "expert_peak_load.serve_tok",
        "experts_touched.serve_tok"}
    assert traced["metrics"]["window_compiles.serve_tok"]["value"] == 0
    assert 1.0 <= traced["metrics"]["expert_peak_load.serve_tok"]["value"] <= 8.0
    assert 0.0 < traced["metrics"]["experts_touched.serve_tok"]["value"] <= 1.0
    json.dumps(traced)


def test_the_controls_read_not_correct_through_the_drivers_own_comparison(tmp_path):
    """tools/cell_controls.py: one run of the tiny cell, then the driver's
    rows of logits and routing limit against references that leave a
    mechanism out or lower a type.  float32 on both sides here, so the rows'
    limit (0.01) tells even the lowered types; on the chip PERF.md section 6
    has what each limit tells."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    import cell_controls

    said = []
    out = cell_controls.run(str(_tiny_root(tmp_path)), "zaya", SEED, 0.5,
                            rows=3, say=said.append)
    controls, must_fail = cell_controls.BY_DRIVER["serve_closed_cca_moe"]
    assert [name for name, _c in controls] == list(out)
    assert out["none (the reference as it is)"][0] is True
    assert set(must_fail) == set(out) - {"none (the reference as it is)"}
    for name in must_fail:
        assert out[name][0] is False, name
    assert sum("[control]" in line for line in said) >= 7 * 3


@pytest.mark.parametrize("lower,low,high", [
    (None, 1.0, 1.0), ("dtype", 0.0, 0.999), ("router_dtype", 0.0, 0.999)])
def test_routing_agreement_tells_float32_from_bfloat16(lower, low, high):
    """On the reference's own router inputs the program's MLP router makes
    the reference's choice for every (token, layer) pair; with the
    reference's router (or all of it) lowered to bfloat16 it does not."""
    import paddle_tpu as paddle
    from paddle_tpu.models.cca_moe import CcaMoeForCausalLM
    from perfbench import reference_cca_moe as ref
    from perfbench.families import cca_moe as fam

    cfg = {**TINY, "num_experts": 64, "num_hidden_layers": 5}
    paddle.seed(5)
    model = CcaMoeForCausalLM(fam.model_config(cfg))
    fam.perturb(model, 5)
    model.eval()
    sizes = fam.reference_sizes(cfg)
    if lower:
        sizes[lower] = "bfloat16"
    ids = np.random.default_rng(5).integers(0, 256, 256).astype(np.int32)
    share, pairs = fam.routing_agreement(model, fam.reference_weights(model),
                                         sizes, ids, ref)
    assert pairs == 5 * 256 and low <= share <= high, share


def test_benchmark_json_only_gained_entries_since_pr_31():
    """This PR's entries in BENCHMARK.json come AFTER PR 31's in every list,
    stated about positions and never about a list's end, so that the file may
    go on gaining entries; no bound and not `run_seconds` moved."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]][:5] == [
        "train-mistral7b-seq4k", "serve-internlm2-decode-sat", PANGU, LAGUNA,
        CELL]
    assert [c["name"] for c in bench["configs"]][3:5] == ["laguna-s-2.1",
                                                          "zaya1-8b"]
    assert bench["workloads"][4] == {
        "name": CELL, "config": "zaya1-8b", "traffic": "decode-ctx8k",
        "chips": 1, "why": real_cell(CELL)["why"]}
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    joined = ["serve_tok_s", "tpot_p90_ms", "engine_step_ms",
              "admit_ms.serve_tok", "batch_occupancy",
              "window_compiles.serve_tok", "device_idle_share.serve_tok",
              "expert_peak_load.serve_tok"]
    for name in joined:
        cells = by_name[name]["workloads"]
        assert cells.index(CELL) == cells.index(LAGUNA) + 1, name
    # the lists the accepted tests hold to laguna's or pangu's cell alone
    for name in ("kv_read_amplification.serve_tok",
                 "swa_moe_decode_roofline_share",
                 "swa_moe_prefill_roofline_share",
                 "mla_moe_decode_roofline_share", "decode_roofline_share",
                 "train_device_mfu"):
        assert CELL not in by_name[name]["workloads"], name
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("kv_read_amplification.serve_tok")
    assert names[at + 1:at + 4] == ["cca_moe_decode_roofline_share",
                                    "cca_moe_prefill_roofline_share",
                                    "experts_touched.serve_tok"]
    for name in names[at + 1:at + 4]:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tok_s"
        assert set(by_name[name]) == {"name", "unit", "better", "source",
                                      "layer", "moves", "workloads"}
    assert by_name["experts_touched.serve_tok"] == {
        "name": "experts_touched.serve_tok", "unit": "ratio", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "serve_tok_s", "workloads": [CELL]}
    assert by_name["cca_moe_decode_roofline_share"]["source"] == "device_trace"
    assert by_name["cca_moe_prefill_roofline_share"]["layer"] == "kernels"
    assert set(real_cell(CELL)["per_layer"]) == {
        m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    assert bench["run_seconds"] == 45
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert bounds == {"train_tok_s": 0.01, "serve_tok_s": 0.075,
                      "tpot_p90_ms": 0.065, "setup_s": 0.1}
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 0
