"""Constructor `bert_sequence_classification`: the program's BERT encoder
with its pooled classification head, at the sizes of a configuration
file."""


def build(cfg):
    from paddle_tpu.models.bert import (BertConfig,
                                        BertForSequenceClassification)

    return BertForSequenceClassification(BertConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        hidden_dropout=0.0, attention_dropout=0.0,
        layer_norm_epsilon=cfg["layer_norm_epsilon"],
        initializer_range=cfg["initializer_range"],
        num_labels=cfg["num_labels"]))
