"""What the perfbench test files share: a benchmark directory of its own,
cut to CPU size."""

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "perfbench")
TINY = {"name": "tiny", "family": "dense_gqa", "source": "test", "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 256, "max_position_embeddings": 512,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
        "torch_dtype": "float32", "reduced": []}
SEED = 2 ** 31 + 11   # more than 32 signed bits hold


def real_cell(name):
    with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
        return json.load(f)


def tiny_root(tmp_path):
    """The real metric files, a tiny configuration, and the three real cells
    cut to CPU size, in a directory of their own."""
    shutil.copytree(os.path.join(BENCH, "layer_metrics"), tmp_path / "layer_metrics")
    for d in ("configs", "workloads"):
        (tmp_path / d).mkdir()
    (tmp_path / "configs" / "tiny.json").write_text(json.dumps(TINY))
    engine = {"max_batch": 4, "block_size": 16, "num_blocks": 32}
    check = {"sample": 2, "positions": [0, 8], "pad_to": 64, "margin_sigma": 0.1}
    train = real_cell("train-mistral7b-seq4k")
    train["train"].update(batch=2, seq=32, loss_every=3, learning_rate=0.01)
    sat = real_cell("serve-internlm2-decode-sat")
    sat.update(engine=engine, check=check)
    sat["traffic"].update(clients=4, prompt={"fixed": 16}, cycle=8,
                          steps_per_second=40,   # 20 iterations in "0.5 s"
                          output={"uniform": [16, 48], "step": 16},
                          first_output={"uniform": [16, 48], "step": 16})
    chat = real_cell("serve-internlm2-chat-r80")
    chat.update(engine=engine, check=check)
    chat["traffic"].update(rate=20, prompt={"choices": [16, 32]}, drain_cap_s=30,
                           output={"lognormal": {"median": 24, "sigma": 0.5},
                                   "clip": [16, 48], "snap": [16, 32, 48]})
    for name, cell in (("train", train), ("sat", sat), ("chat", chat)):
        cell.update(config="tiny", trace_seconds=0.2)
        (tmp_path / "workloads" / f"{name}.json").write_text(json.dumps(cell))
    return tmp_path
