"""The window / full attention configuration's benchmark files: they load
through the harness as it is, the new driver runs the new cell end to end at a
tiny size on the CPU (rehearsal 1: keys and counts only), the family refuses
what its model cannot express, the roofline functions return the hand-reckoned
numbers of the published widths, and the router check tells float32 from
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
from perfbench import roofline, roofline_window_moe as rw, run, traffic  # noqa: E402

CELL = "serve-laguna-decode-ctx8k"
FULL, SLIDING = "full_attention", "sliding_attention"
TINY = {"name": "tiny-swa", "family": "window_moe", "source": "test",
        "model_type": "laguna", "hidden_size": 64, "intermediate_size": 160,
        "moe_intermediate_size": 48, "shared_expert_intermediate_size": 48,
        "num_hidden_layers": 5, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
        "num_experts": 4, "num_experts_per_tok": 3, "norm_topk_prob": True,
        "moe_routed_scaling_factor": 2.5, "vocab_size": 256,
        "max_position_embeddings": 512, "rms_norm_eps": 1e-6,
        "attention_bias": False, "tie_word_embeddings": False,
        "gating": "per-head", "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "moe_apply_router_weight_on_input": False,
        "moe_router_logit_softcapping": 0,
        "layer_types": [FULL, SLIDING, SLIDING, SLIDING] * 2,
        "num_attention_heads_per_layer": [4, 6, 6, 6] * 2,
        "mlp_layer_types": ["dense"] + ["sparse"] * 7,
        "gating_types": ["per_head"] * 8,
        "rope_parameters": {
            FULL: {"rope_theta": 10000, "rope_type": "yarn", "factor": 4,
                   "original_max_position_embeddings": 16, "beta_slow": 1,
                   "beta_fast": 4, "attention_factor": 1.1386294361119891,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 100,
                      "partial_rotary_factor": 1}},
        "torch_dtype": "float32", "reduced": ["num_experts"],
        "published": {"num_experts": 8}, "share": {"first_expert": 2}}


@pytest.fixture(scope="module")
def cell():
    return run.load_cell(BENCH, CELL)


def test_the_new_files_load_and_say_what_the_issue_asked(cell):
    cfg = cell["config_file"]
    assert cell["driver"] == "serve_closed_swa_moe" and cfg["family"] == "window_moe"
    assert cell["engine"] == {"max_batch": 32, "block_size": 128,
                              "num_blocks": 32 * 67}
    # the traffic of serve-pangu-decode-ctx8k, parameter for parameter: two
    # architectures under one mix (only the window's length is each cell's)
    ours, theirs = dict(cell["traffic"]), dict(real_cell("serve-pangu-decode-ctx8k")["traffic"])
    for t in (ours, theirs):
        t.pop("steps_per_second"), t.pop("steps_per_second_why")
    assert ours == theirs
    assert cell["check"]["pad_to"] == 8320 and cell["check"]["positions"] == [0, 8]
    # a clean token at the other serving cells' margin; a near tie of a
    # token's own routing counted apart; rows of logits from the resident
    # engine; the router: each limit with its reason in the file
    ck = cell["check"]
    assert ck["margin_sigma"] == real_cell("serve-pangu-decode-ctx8k")["check"]["margin_sigma"] == 0.1
    assert ck["tie_tau"] == 0.05 and ck["tie_margin_sigma"] == 1.5
    assert ck["margin_sigma"] < ck["logit_sigma"] == 0.3 < ck["tie_logit_sigma"] == 2.3
    assert ck["logit_rows"] == 4 and "near tie" in ck["logit_why"]
    assert ck["routing_agreement"] == 0.97 and "0.9391" in ck["routing_why"]
    for why in ("margin_why", "tie_why", "logit_why", "routing_why"):
        assert "READINGS" not in ck[why] and "my chip runs, PR 31" in ck[why], why
    assert set(cell["per_layer"]) == set(cell["metric_files"]) and len(cell["per_layer"]) == 9
    assert len(cell["why"]) <= 200
    # the first wave warms every shape the loop can send: 9 of them
    loop, bs = traffic.ClosedLoop(cell["traffic"]), cell["engine"]["block_size"]
    shape = lambda pr, out: (pr, -(-(pr + out) // bs))  # noqa: E731
    sent = {shape(*s) for s in loop.shapes()}
    assert len(sent) == 9
    assert sent == {shape(r.prompt_len, r.max_new) for r in loop.first_wave()}
    assert max(n for _p, n in sent) == 67
    # every prompt is at least four windows long: token 9 is decoded through
    # a ring that has wrapped
    assert min(p for p, _n in loop.shapes()) >= 4 * cfg["sliding_window"]


def test_every_number_is_the_catalogs_and_the_cuts_are_listed(cell):
    cfg = cell["config_file"]
    period = [FULL, SLIDING, SLIDING, SLIDING]
    catalog = {
        "model_type": "laguna", "vocab_size": 100352, "hidden_size": 3072,
        "intermediate_size": 12288, "num_hidden_layers": 48,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts": 256, "num_experts_per_tok": 10,
        "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
        "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
        "tie_word_embeddings": False, "gating": "per-head",
        "sliding_window": 512,
        "rope_parameters": {
            FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                   "original_max_position_embeddings": 8192, "beta_slow": 1,
                   "beta_fast": 32, "attention_factor": 1.4852030263919618,
                   "partial_rotary_factor": 0.5},
            SLIDING: {"rope_type": "default", "rope_theta": 10000,
                      "partial_rotary_factor": 1}},
        "layer_types": period * 12,
        "moe_apply_router_weight_on_input": False,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "gating_types": ["per_head"] * 48, "moe_routed_scaling_factor": 2.5,
        "num_attention_heads_per_layer": [48, 72, 72, 72] * 12,
        "moe_router_logit_softcapping": 0}
    differs = {k for k, v in catalog.items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {"num_hidden_layers", "num_experts",
                                              "vocab_size"}
    assert {k: catalog[k] for k in differs} == cfg["published"]
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) \
        == (5, 32, 12544)
    assert cfg["vocab_size"] * 8 == catalog["vocab_size"]
    for key in ("published", "share", "deployment", "assumed"):
        assert cfg[key], key
    assert next(iter(cfg["assumed"])) == "attention gate"
    assert cfg["share"]["chips_per_layer"] == cfg["share"]["expert_ways"] == 8
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [c for c in bench["configs"] if c["name"] == cell["config"]][0]
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == cfg["source"]
    assert [w for w in bench["workloads"] if w["name"] == CELL][0]["why"] == cell["why"]


def test_roofline_returns_the_hand_reckoned_numbers(cell):
    cfg = cell["config_file"]
    assert rw.attention_params(cfg, 48) == 44_187_648
    assert rw.attention_params(cfg, 72) == 63_135_744
    assert rw.expert_params(cfg) == rw.shared_expert_params(cfg) == 9_437_184
    assert rw.layers(cfg) == [(False, 48, False), (True, 72, True),
                              (True, 72, True), (True, 72, True),
                              (False, 48, True)]
    assert round(rw.layer_params(cfg, 48, False) / 1e6, 1) == 157.4
    assert round(rw.layer_params(cfg, 72, True) / 1e6, 1) == 375.3
    assert round(rw.layer_params(cfg, 48, True) / 1e6, 1) == 356.4
    assert rw.model_params(cfg) == 1_716_953_088
    assert round(rw.weight_bytes(cfg) / 1e9, 2) == 3.43
    assert rw.kv_bytes_per_position(cfg, sliding=False) == 8192
    assert rw.kv_bytes_per_position(cfg, sliding=True) == 12288
    # pairs a mask admits: the triangle, or the window's band
    assert rw.causal_pairs(8192) == 8192 * 8193 // 2
    assert rw.causal_pairs(8192, 512) == sum(min(i + 1, 512) for i in range(8192))
    assert rw.causal_pairs(300, 512) == 300 * 301 // 2
    # an 8k prompt is about 10 TFLOP; its sliding layers' attention 0.45 with
    # the window and 3.7 without (the issue's 0.6 / 3.7)
    assert 9.5e12 < rw.prefill_flops(cfg, 8192) < 10.5e12
    band = 3 * 4 * 72 * 128 * rw.causal_pairs(8192, 512)
    whole = 3 * 4 * 72 * 128 * rw.causal_pairs(8192)
    assert 0.4e12 < band < 0.5e12 and 3.6e12 < whole < 3.8e12
    import perfbench.families.window_moe  # noqa: F401  (registers them)

    fn = roofline.FUNCTIONS["swa_moe_prefill_min_s"]
    facts = {"admitted_prompt_lens": [2048, 8192], "moe_prefill_held_share": 0.125}
    mean = (rw.prefill_flops(cfg, 2048, 0.125) + rw.prefill_flops(cfg, 8192, 0.125)) / 2
    assert fn(cfg, facts, "TPU v5e") == pytest.approx(mean / 197e12)
    assert rw.prefill_flops(cfg, 2048) == rw.prefill_flops(cfg, 2048, 32 / 256)
    fn = roofline.FUNCTIONS["swa_moe_decode_token_step_min_s"]
    facts = {"rows": 32, "live_kv_tokens": 32 * 4900,
             "live_window_positions": 32 * 512,
             "moe_touched_per_layer_step": 23.0, "moe_held_per_layer_step": 40.0}
    fixed = (157_433_856 + 3 * (63_135_744 + 9_437_184 + 786_432)
             + (44_187_648 + 9_437_184 + 786_432) + 12544 * 3072)
    assert rw.fixed_params(cfg) == fixed
    by = (2 * (fixed + 32 * 3072 + 4 * 23 * 9_437_184)
          + 32 * 4900 * 8192 + 32 * 512 * 12288)
    assert fn(cfg, facts, "TPU v5e") == pytest.approx(by / 819e9)
    assert 4.5e-3 < fn(cfg, facts, "TPU v5e") < 5.5e-3   # the issue's 5.1 ms
    idle = dict(facts, live_kv_tokens=0, live_window_positions=0,
                moe_touched_per_layer_step=0.0, moe_held_per_layer_step=0.0)
    assert fn(cfg, idle, "TPU v5e") == pytest.approx(2 * (fixed + 32 * 3072) / 819e9)
    busy = dict(idle, rows=4096)
    assert fn(cfg, busy, "TPU v5e") == pytest.approx(2.0 * fixed * 4096 / 197e12)


def test_the_family_refuses_what_the_model_cannot_express(cell):
    from perfbench.families import window_moe as fam

    cfg = dict(cell["config_file"])
    fam.check(cfg)
    for key, bad in (("attention_bias", True), ("hidden_act", "gelu"),
                     ("tie_word_embeddings", True), ("gating", "elementwise"),
                     ("gating_types", ["per_token"] * 48),
                     ("moe_apply_router_weight_on_input", True),
                     ("moe_router_logit_softcapping", 30.0),
                     ("decoder_sparse_step", 2), ("mlp_only_layers", [0, 1]),
                     ("num_attention_heads", 64), ("torch_dtype", "float16"),
                     ("layer_types", ["full_attention"] * 3),
                     ("qk_norm", True), ("num_nextn_predict_layers", 1)):
        with pytest.raises(ValueError):
            fam.check({**cfg, key: bad})
    rope = json.loads(json.dumps(cfg["rope_parameters"]))
    rope["full_attention"]["rope_type"] = "llama3"
    with pytest.raises(ValueError, match="rope_type"):
        fam.check({**cfg, "rope_parameters": rope})
    rope["full_attention"].update(rope_type="yarn", mscale=1.0)
    with pytest.raises(ValueError, match="rope keys"):
        fam.check({**cfg, "rope_parameters": rope})
    with pytest.raises(ValueError, match="outside the router"):
        fam.check({**cfg, "share": {"first_expert": 250}})
    c = fam.model_config(cfg)
    assert c.held == (96, 32) and c.num_experts == 256 and c.vocab_size == 12544
    assert c.layer_types == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    assert c.num_attention_heads_per_layer == (48, 72, 72, 72, 48)
    assert c.mlp_layer_types == ("dense",) + ("sparse",) * 4
    assert c.router_scoring == "softmax" and c.sliding_window == 512
    with pytest.raises(ValueError, match="no cell trains"):
        fam.build(cfg, 0, training=True)
    assert fam.held_experts(cfg) == (96, 32) and fam.routed_experts(cfg) == 256


def _tiny_root(tmp_path):
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), tmp_path / "layer_metrics")
    for d in ("configs", "workloads"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "tiny-swa.json").write_text(json.dumps(TINY))
    cell = real_cell(CELL)
    cell.update(config="tiny-swa", trace_seconds=0.2,
                engine={"max_batch": 4, "block_size": 4, "num_blocks": 64},
                check={"sample": 2, "positions": [0, 8], "pad_to": 96,
                       "margin_sigma": 0.1, "tie_tau": 0.05,
                       "tie_margin_sigma": 1.5, "logit_rows": 3,
                       "logit_sigma": 0.01, "tie_logit_sigma": 2.0,
                       "routing_prompt": 48, "routing_agreement": 0.95})
    cell["traffic"].update(clients=4, prompt={"choices": [16, 32]}, cycle=8,
                           steps_per_second=40,
                           output={"uniform": [16, 32], "step": 16},
                           first_output={"uniform": [16, 32], "step": 16})
    (tmp_path / "workloads" / "laguna.json").write_text(json.dumps(cell))
    return tmp_path


def test_the_new_cell_runs_end_to_end_tiny_through_its_driver(tmp_path, capsys):
    root, cell = _tiny_root(tmp_path), real_cell(CELL)
    plain = run.run_cell(str(root), "laguna", SEED, 0.5, False)
    assert plain["correct"] is True and plain["failed"] == 0
    assert set(plain["metrics"]) == set(cell["end_to_end"])
    said = capsys.readouterr().out
    assert "expert load over the window's" in said and "1/2 is even" in said
    assert "ring positions read" in said and "admissions in the traced part" in said
    # float32 on both sides: the resident engine's rows ARE the reference's
    n, live = map(int, re.search(r"(\d+) rows of logits from the resident "
                                 r"engine \((\d+) rows live\)", said).groups())
    assert 1 <= n <= min(3, live) <= 4
    assert said.count("in the engine as the window left it") == n
    assert "FAILED" not in said
    # 4 expert layers x 48 tokens, the same numbers on both sides: every pair
    assert "the reference's experts for 1.0000 of 192 (token, expert layer)" in said
    traced = run.run_cell(str(root), "laguna", SEED, 0.5, True,
                          trace_dir=str(root / "trace"))
    assert traced["correct"] is True
    names = set(traced["metrics"])
    # no device plane on the CPU: the trace readers (the three shares of the
    # device's time) report nothing; spans and counters do
    assert names == {"engine_step_ms", "admit_ms.serve_tok", "batch_occupancy",
                     "window_compiles.serve_tok", "expert_peak_load.serve_tok",
                     "kv_read_amplification.serve_tok"}
    assert traced["metrics"]["window_compiles.serve_tok"]["value"] == 0
    assert 1.0 <= traced["metrics"]["expert_peak_load.serve_tok"]["value"] <= 4.0
    assert 1.0 < traced["metrics"]["kv_read_amplification.serve_tok"]["value"] < 4.0
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
    out = cell_controls.run(str(_tiny_root(tmp_path)), "laguna", SEED, 0.5,
                            rows=2, say=said.append)
    assert [name for name, _c in cell_controls.CONTROLS] == list(out)
    assert out["none (the reference as it is)"][0] is True
    for name in ("window ignored", "gate left out", "everything in bfloat16"):
        correct, checks = out[name]
        assert correct is False, name
        assert any("in the engine as the window left it" in what and not ok
                   for what, ok in checks.items()), name
    assert sum("[control]" in line for line in said) >= 6 * 3


@pytest.mark.parametrize("tied,gap_sigma,ok", [
    (False, 0.5, False),    # a clean token stays at margin_sigma: still FAILED
    (True, 0.5, True),      # a near tie is counted apart, inside its margin
    (True, 1.8, False),     # and outside it
    (False, 0.0, True)])
def test_near_ties_are_counted_apart_and_clean_tokens_keep_their_margin(
        tied, gap_sigma, ok):
    from types import SimpleNamespace

    from perfbench.drivers import serve_closed_swa_moe as driver

    ck = {"positions": [0, 8], "margin_sigma": 0.1, "tie_tau": 0.05,
          "tie_margin_sigma": 1.5}
    row = np.zeros(100, np.float32)
    row[7] = 10.0                       # the reference's maximum
    for _ in range(50):                 # the write moves the row's std
        row[3] = 10.0 - gap_sigma * row.std()
    gap = float(row.max() - row[3]) / float(row.std())
    assert abs(gap - gap_sigma) < 1e-3
    rows = np.stack([np.where(np.arange(100) == 7, 10.0, 0.0), row])
    was = {"c5 (prompt 16) token 1: reference logit 0.0000 sigma under the "
           "maximum (margin 0.1)": True,
           f"c5 (prompt 16) token 9: reference logit {gap:.4f} sigma under the "
           "maximum (margin 0.1)": gap <= 0.1,
           "every request sent in the window was accepted": True}
    requests = {"c5": SimpleNamespace(tokens=[7] + [0] * 7 + [3])}
    now = driver.ties_apart(ck, was, [(rows, np.array([False, tied]))], requests)
    assert len(now) == 3 and list(now.values())[-1] is True
    assert list(now)[0] == list(was)[0] and now[list(was)[0]] is True
    (what, verdict), = [(k, v) for k, v in now.items() if "token 9" in k]
    assert verdict is ok
    assert ("NEAR TIE" in what) is tied and ("margin 1.5" in what) is tied


@pytest.mark.parametrize("lower,low,high", [
    (None, 1.0, 1.0), ("dtype", 0.0, 0.999), ("router_dtype", 0.0, 0.999)])
def test_routing_agreement_tells_float32_from_bfloat16(lower, low, high):
    """On the reference's own router inputs the program's softmax router
    agrees on every (token, expert layer) pair; with the reference's router
    (or all of it) lowered to bfloat16 it clearly does not: top-6 of 64 by
    softmax at width 64 has near ties a bfloat16 product resolves otherwise."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.window_moe import WindowMoeForCausalLM
    from perfbench import reference_window_moe as ref
    from perfbench.families import window_moe as fam

    cfg = {**TINY, "num_experts": 16, "published": {"num_experts": 64},
           "share": {"first_expert": 16}, "num_experts_per_tok": 6}
    paddle.seed(5)
    model = WindowMoeForCausalLM(fam.model_config(cfg))
    fam.perturb_norms(model, 5)
    model.eval()
    sizes = fam.reference_sizes(cfg)
    if lower:
        sizes[lower] = "bfloat16"
    ids = np.random.default_rng(5).integers(0, 256, 128).astype(np.int32)
    share, pairs = fam.routing_agreement(model, fam.reference_weights(model),
                                         sizes, ids, ref)
    assert pairs == 4 * 128 and low <= share <= high, share


def test_benchmark_json_only_gained_entries_since_pr_27():
    """This PR's entries in BENCHMARK.json come AFTER PR 27's in every list,
    stated about positions and not about a list's end, so that the file may
    go on gaining entries; no bound and not `run_seconds` moved."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    pangu = "serve-pangu-decode-ctx8k"
    assert [w["name"] for w in bench["workloads"]][:4] == [
        "train-mistral7b-seq4k", "serve-internlm2-decode-sat", pangu, CELL]
    assert [c["name"] for c in bench["configs"]][2:4] == [
        "openpangu-ultra-moe-718b", "laguna-s-2.1"]
    by_name = {m["name"]: m for m in bench["per_layer"] + bench["end_to_end"]}
    for name in real_cell(pangu)["per_layer"] + ["serve_tok_s", "tpot_p90_ms"]:
        cells = by_name[name]["workloads"]
        assert pangu in cells and cells.index(pangu) == (
            0 if name in ("mla_moe_decode_roofline_share",
                          "expert_peak_load.serve_tok") else 1), name
    for name in real_cell(CELL)["per_layer"] + ["serve_tok_s", "tpot_p90_ms"]:
        cells = by_name[name]["workloads"]
        assert CELL in cells and pangu not in cells[cells.index(CELL):], name
    assert by_name["mla_moe_decode_roofline_share"]["workloads"] == [pangu]
    assert by_name["decode_roofline_share"]["workloads"] == ["serve-internlm2-decode-sat"]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("expert_peak_load.serve_tok")
    assert names[at - 1] == "mla_moe_decode_roofline_share"
    assert names[at + 1:at + 4] == ["swa_moe_decode_roofline_share",
                                    "swa_moe_prefill_roofline_share",
                                    "kv_read_amplification.serve_tok"]
    for name in names[at + 1:at + 4]:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tok_s"
    assert by_name["kv_read_amplification.serve_tok"]["layer"] == "model step"
    assert by_name["swa_moe_prefill_roofline_share"]["source"] == "device_trace"
    assert bench["run_seconds"] == 45
    bounds = {m["name"]: m["bound"] for m in bench["end_to_end"]}
    assert bounds == {"train_tok_s": 0.01, "serve_tok_s": 0.075,
                      "tpot_p90_ms": 0.065, "setup_s": 0.1}
