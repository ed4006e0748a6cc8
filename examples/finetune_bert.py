"""BERT sequence-classification finetune with the WordPiece tokenizer,
AMP, and async checkpointing."""

import os
import sys

if "--cpu" in sys.argv:  # CPU smoke on a machine that has a TPU
    sys.argv.remove("--cpu")
    import jax

    jax.config.update("jax_platforms", "cpu")

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import paddle_tpu as paddle
from paddle_tpu.jit import TrainStep
from paddle_tpu.models import BertForSequenceClassification, bert_tiny
from paddle_tpu.text import BertTokenizer


def main():
    vocab = ["[PAD]", "[UNK]", "[CLS]", "[SEP]"] + [f"tok{i}" for i in range(60)]
    tok = BertTokenizer(vocab)
    texts = [f"tok{i} tok{(i * 3) % 60} tok{(i * 7) % 60}" for i in range(32)]
    labels = np.asarray([i % 2 for i in range(32)], np.int32)
    enc = tok(texts, max_length=16)

    paddle.seed(0)
    model = BertForSequenceClassification(bert_tiny(vocab_size=len(vocab)), num_classes=2)
    opt = paddle.optimizer.AdamW(5e-4, parameters=model.parameters())
    step = TrainStep(model, opt, lambda m, i, t, am, y: m(i, token_type_ids=t, attention_mask=am, labels=y)[0])

    ids = paddle.to_tensor(enc["input_ids"])
    tty = paddle.to_tensor(enc["token_type_ids"])
    am = paddle.to_tensor(enc["attention_mask"])
    y = paddle.to_tensor(labels)
    for epoch in range(5):
        loss = step(ids, tty, am, y)
        print(f"epoch {epoch}: loss {float(loss):.4f}")
        paddle.save({"model": dict(model.state_dict())}, "/tmp/bert_ft.pdparams", async_save=True)
    paddle.wait_async_save()
    print("checkpoint saved to /tmp/bert_ft.pdparams")


if __name__ == "__main__":
    main()
