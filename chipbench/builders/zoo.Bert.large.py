"""Builder ``zoo.Bert.large``: the program's BERT-large-shaped causal
decoder with the benchmark's weights put in."""


def build(cfg: dict):
    from deeplearning4j_tpu.zoo.bert import Bert

    return Bert(vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
                n_layers=cfg["num_hidden_layers"],
                n_heads=cfg["num_attention_heads"],
                ffn_size=cfg["intermediate_size"],
                max_length=cfg["max_position_embeddings"],
                type_vocab_size=cfg["type_vocab_size"],
                hidden_dropout=0.0, causal=True, task="mlm").init()


def load(net, weights: dict) -> None:
    """The serving programs donate only the KV pools, so the arrays are
    shared with the reference, not copied."""
    net.params[0] = dict(weights["emb"])
    for i, lyr in enumerate(weights["layers"]):
        net.params[i + 1] = dict(lyr)
    net.params[-1] = dict(weights["head"])
