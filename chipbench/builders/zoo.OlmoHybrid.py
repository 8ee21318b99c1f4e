"""Builder ``zoo.OlmoHybrid``: the program's Olmo Hybrid with the
benchmark's weights put in. The program's module is imported here, at the
top: a checkout without it fails at this import, before any weight is
made."""

from deeplearning4j_tpu.zoo.olmo_hybrid import OlmoHybrid


def build(cfg: dict):
    """The net without parameters: ``init()`` would draw billions of them
    (and an optimiser state) next to the benchmark's. The program's Gated
    DeltaNet allows negative eigenvalues, as this configuration states."""
    if not cfg["linear_allow_neg_eigval"] or \
            cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]:
        raise ValueError("the Gated DeltaNet here has beta in (0, 2) and "
                         "as many value heads as key heads")
    layers = cfg["num_hidden_layers"]
    return OlmoHybrid(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=tuple(cfg["layer_types"][:layers]),
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        linear_heads=cfg["linear_num_key_heads"],
        key_head_dim=cfg["linear_key_head_dim"],
        value_head_dim=cfg["linear_value_head_dim"],
        conv_size=cfg["linear_conv_kernel_dim"],
        ffn_size=cfg["intermediate_size"], eps=cfg["rms_norm_eps"],
        max_length=cfg["max_position_embeddings"],
        param_dtype=cfg["param_dtype"]).network()


def load(net, weights: dict) -> None:
    """The leaves are shared with the reference, not copied: the serving
    programs donate only the pools."""
    net.params = ([dict(weights["emb"])]
                  + [dict(lyr) for lyr in weights["layers"]]
                  + [dict(weights["head"])])
