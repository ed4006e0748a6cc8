"""BERT/ERNIE family (BASELINE.json finetune north-stars) on the nn stack."""

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import BertForMaskedLM, BertForSequenceClassification, bert_tiny


def _batch(vocab, b=4, s=16, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, vocab, (b, s)).astype(np.int32)
    ids[:, -3:] = 0  # padding tail exercises the attention mask
    return paddle.to_tensor(ids)


def test_sequence_classification_finetune_loss_decreases():
    paddle.seed(0)
    cfg = bert_tiny()
    m = BertForSequenceClassification(cfg, num_classes=3)
    opt = paddle.optimizer.AdamW(5e-4, parameters=m.parameters())
    ids = _batch(cfg.vocab_size)
    labels = paddle.to_tensor(np.array([0, 1, 2, 1], np.int32))
    step = TrainStep(m, opt, lambda mm, i, l: mm(i, labels=l)[0])
    losses = [float(step(ids, labels)) for _ in range(10)]
    assert losses[-1] < losses[0] * 0.7, losses


def test_masked_lm_and_padding_mask():
    paddle.seed(1)
    cfg = bert_tiny()
    m = BertForMaskedLM(cfg)
    m.eval()
    ids = _batch(cfg.vocab_size, seed=1)
    with paddle.no_grad():
        logits = m(ids)
    assert list(logits.shape) == [4, 16, cfg.vocab_size]
    assert np.isfinite(np.asarray(logits._value, np.float32)).all()
    # padded positions must not influence the [CLS] pooled output
    ids2 = np.asarray(ids._value).copy()
    ids2[:, -3:] = 0  # same padding, different garbage beyond mask is absent
    clf = BertForSequenceClassification(cfg)
    clf.eval()
    with paddle.no_grad():
        mask = (ids2 != 0).astype(np.int32)
        a = np.asarray(clf(paddle.to_tensor(ids2), attention_mask=paddle.to_tensor(mask))._value)
        ids3 = ids2.copy()
        ids3[:, -1] = 7  # perturb a PADDED position; mask still marks it pad
        b = np.asarray(clf(paddle.to_tensor(ids3), attention_mask=paddle.to_tensor(mask))._value)
    # the masked position cannot reach [CLS] through attention
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_ernie_alias():
    from paddle_tpu.models import ErnieForSequenceClassification, ErnieModel

    assert ErnieModel is not None and ErnieForSequenceClassification is not None


@pytest.mark.slow
def test_gpt_trains_and_shards():
    """GPT family: compiled pretrain step decreases loss; Megatron-sharded
    tp x dp step matches single-device numerics."""
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny, shard_gpt
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.sharded_step import ShardedTrainStep

    rng = np.random.default_rng(0)
    cfg = gpt_tiny()
    ids_np = rng.integers(0, cfg.vocab_size, (4, 32)).astype(np.int32)

    paddle.seed(3)
    m = GPTForCausalLM(cfg)
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
    step = TrainStep(m, opt, lambda mm, i: mm(i, labels=i)[0])
    ids = paddle.to_tensor(ids_np)
    losses = [float(step(ids)) for _ in range(5)]
    assert losses[-1] < losses[0], losses

    paddle.seed(3)
    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4), ["dp", "mp"])
    m2 = shard_gpt(GPTForCausalLM(cfg), mesh)
    opt2 = paddle.optimizer.AdamW(1e-3, parameters=m2.parameters())
    step2 = ShardedTrainStep(m2, opt2, lambda mm, i: mm(i, labels=i)[0], mesh)
    losses2 = [float(step2(ids)) for _ in range(5)]
    np.testing.assert_allclose(losses2, losses, rtol=2e-3, atol=2e-3)


def test_bert_tokenizer_feeds_model():
    """WordPiece tokenizer (the strings/faster_tokenizer workload, host
    side) feeding the BERT classifier end to end."""
    from paddle_tpu.text import BertTokenizer

    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]", "the", "cat", "sat", "mat",
             "un", "##able", "##happy", "on", "!"]
    tok = BertTokenizer(vocab)
    assert tok.tokenize("The cat sat!") == ["the", "cat", "sat", "!"]
    assert tok.tokenize("unhappy") == ["un", "##happy"]
    assert tok.tokenize("zebra") == ["[UNK]"]

    enc = tok(["the cat sat on the mat", "unhappy cat"], max_length=12)
    assert enc["input_ids"].shape == (2, 12)
    assert enc["attention_mask"][0].sum() == 8  # CLS + 6 toks + SEP
    # pair encoding sets token types
    enc2 = tok("the cat", text_pairs="sat on", max_length=10)
    assert enc2["token_type_ids"].max() == 1

    cfg = bert_tiny(vocab_size=len(vocab) + 10)
    m = BertForSequenceClassification(cfg)
    m.eval()
    with paddle.no_grad():
        logits = m(
            paddle.to_tensor(enc["input_ids"]),
            token_type_ids=paddle.to_tensor(enc["token_type_ids"]),
            attention_mask=paddle.to_tensor(enc["attention_mask"]),
        )
    assert np.isfinite(np.asarray(logits._value)).all()
