"""Operations and bytes the algorithms need, from shapes alone. A
multiply-add counts as 2 operations. Recomputation, padding rows and bucket
padding never count: these are the operations of the model, not of the
program."""

from __future__ import annotations

from chipbench.manifest import module_from


def train_flops_per_example(cfg: dict) -> int:
    """Forward + backward operations of one training example: every
    trainable configuration's plain reference (``chipbench/reference/``)
    counts its own model from the same list of layers it is built from."""
    return module_from("reference",
                       cfg["reference"]).train_flops_per_example(cfg)


def decoder_matmul_params(cfg: dict) -> int:
    """Weights that every token is multiplied through: the four attention
    projections and the two FFN matrices of each layer, and the output head.
    Embedding tables are looked up, not multiplied."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return (cfg["num_hidden_layers"] * (4 * h * h + 2 * h * f)
            + h * cfg["vocab_size"])


def decoder_params(cfg: dict) -> int:
    """All parameters: matrices, biases, LayerNorms, embeddings, head."""
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    per_layer = 4 * h * h + 2 * h * f + 4 * h + f + h + 4 * h
    emb = (v + cfg["max_position_embeddings"] + cfg["type_vocab_size"]) * h \
        + 2 * h
    return cfg["num_hidden_layers"] * per_layer + emb + h * v + v


def decoder_request_flops(cfg: dict, prompt: int, new: int) -> int:
    """A request of ``prompt`` tokens that is served ``new`` tokens: the
    prompt and the first new - 1 served tokens each pass through the layers;
    the head runs once per served token; token i attends i + 1 keys
    (2 x head_dim x heads multiply-adds for scores, the same for values)."""
    h, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    n = prompt + new - 1
    per_tok = 2 * layers * (4 * h * h + 2 * h * cfg["intermediate_size"])
    attn = layers * 4 * h * (n * (n + 1) // 2)
    return n * per_tok + attn + new * 2 * h * cfg["vocab_size"]


def kv_bytes_per_token(cfg: dict) -> int:
    """K and V of one token over all layers, at the served KV type."""
    size = {"float32": 4, "bfloat16": 2}[cfg["kv_dtype"]]
    return 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * size


def decode_step_bytes(cfg: dict, live_tokens: int) -> int:
    """The least a decode step must move through HBM: every weight that is
    multiplied, once, and the K and V of the tokens the streams hold."""
    size = {"float32": 4, "bfloat16": 2}[cfg["param_dtype"]]
    return (decoder_matmul_params(cfg) * size
            + live_tokens * kv_bytes_per_token(cfg))
