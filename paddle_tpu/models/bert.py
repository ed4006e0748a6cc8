"""BERT / ERNIE encoder family.

Capability target: the BASELINE.json north-star finetune configs (BERT-base +
ERNIE-3.0 data-parallel finetune) — reference model definitions live in
PaddleNLP on top of the framework; here the family is built on this
framework's nn stack the same way (nn.TransformerEncoder).  ERNIE 1.0/3.0
base shares the BERT encoder architecture (different pretraining + task
heads), so ErnieModel is the same graph with its config defaults.

TPU-first notes: bf16-friendly (fp32 LayerNorm statistics come from the nn
LayerNorm), attention through scaled_dot_product_attention (flash kernel on
TPU), whole-model runs under jit.TrainStep for finetuning.
"""

from __future__ import annotations

from dataclasses import dataclass

import paddle_tpu as paddle
import paddle_tpu.nn as nn

__all__ = [
    "BertConfig",
    "BertModel",
    "BertForSequenceClassification",
    "BertForMaskedLM",
    "ErnieConfig",
    "ErnieModel",
    "ErnieForSequenceClassification",
    "bert_tiny",
]


@dataclass
class BertConfig:
    vocab_size: int = 30522
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0


ErnieConfig = BertConfig  # same encoder family (see module docstring)


class BertEmbeddings(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.word_embeddings = nn.Embedding(config.vocab_size, config.hidden_size)
        self.position_embeddings = nn.Embedding(config.max_position_embeddings, config.hidden_size)
        self.token_type_embeddings = nn.Embedding(config.type_vocab_size, config.hidden_size)
        self.layer_norm = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = paddle.arange(s, dtype="int32").unsqueeze(0).expand([b, s])
        if token_type_ids is None:
            token_type_ids = paddle.zeros([b, s], dtype="int32")
        emb = (
            self.word_embeddings(input_ids)
            + self.position_embeddings(position_ids)
            + self.token_type_embeddings(token_type_ids)
        )
        return self.dropout(self.layer_norm(emb))


class BertPooler(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.dense = nn.Linear(config.hidden_size, config.hidden_size)

    def forward(self, hidden_states):
        return paddle.tanh(self.dense(hidden_states[:, 0]))


class BertModel(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.config = config
        self.embeddings = BertEmbeddings(config)
        layer = nn.TransformerEncoderLayer(
            config.hidden_size,
            config.num_attention_heads,
            config.intermediate_size,
            dropout=config.hidden_dropout_prob,
            activation=config.hidden_act,
            attn_dropout=config.attention_probs_dropout_prob,
        )
        self.encoder = nn.TransformerEncoder(layer, config.num_hidden_layers)
        self.pooler = BertPooler(config)

    def forward(self, input_ids, token_type_ids=None, position_ids=None, attention_mask=None):
        if attention_mask is None:
            attention_mask = (input_ids != self.config.pad_token_id).astype("int32")
        # additive mask broadcast over [B, S(q), N, S(k)] (BSNH attention layout)
        ext = ((1 - attention_mask.astype("float32")) * -1e4).unsqueeze(1).unsqueeze(1)
        h = self.embeddings(input_ids, token_type_ids, position_ids)
        h = self.encoder(h, ext)
        return h, self.pooler(h)


class BertForSequenceClassification(nn.Layer):
    def __init__(self, config: BertConfig, num_classes: int = 2):
        super().__init__()
        self.bert = BertModel(config)
        self.dropout = nn.Dropout(config.hidden_dropout_prob)
        self.classifier = nn.Linear(config.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None, labels=None):
        _, pooled = self.bert(input_ids, token_type_ids, attention_mask=attention_mask)
        logits = self.classifier(self.dropout(pooled))
        if labels is not None:
            loss = nn.functional.cross_entropy(logits, labels)
            return loss, logits
        return logits


class BertForMaskedLM(nn.Layer):
    def __init__(self, config: BertConfig):
        super().__init__()
        self.bert = BertModel(config)
        self.transform = nn.Linear(config.hidden_size, config.hidden_size)
        self.layer_norm = nn.LayerNorm(config.hidden_size, epsilon=config.layer_norm_eps)
        self.decoder = nn.Linear(config.hidden_size, config.vocab_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None, labels=None):
        h, _ = self.bert(input_ids, token_type_ids, attention_mask=attention_mask)
        h = self.layer_norm(nn.functional.gelu(self.transform(h)))
        logits = self.decoder(h)
        if labels is not None:
            loss = nn.functional.cross_entropy(
                logits.reshape([-1, logits.shape[-1]]).astype("float32"),
                labels.reshape([-1]),
                ignore_index=-100,
            )
            return loss, logits
        return logits


ErnieModel = BertModel
ErnieForSequenceClassification = BertForSequenceClassification


def bert_tiny(**kw) -> BertConfig:
    cfg = dict(
        vocab_size=1024,
        hidden_size=128,
        num_hidden_layers=2,
        num_attention_heads=4,
        intermediate_size=256,
        max_position_embeddings=128,
    )
    cfg.update(kw)
    return BertConfig(**cfg)
