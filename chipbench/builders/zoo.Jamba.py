"""Builder ``zoo.Jamba``: the program's Jamba with the benchmark's weights
put in. The program's module is imported here, at the top: a checkout
without it fails at this import, before any weight is made."""

from deeplearning4j_tpu.zoo.jamba import Jamba


def build(cfg: dict):
    """The net without parameters: ``init()`` would draw 3 billion of them
    (and an optimiser state) next to the benchmark's."""
    return Jamba(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        attn_layer_period=cfg["attn_layer_period"],
        attn_layer_offset=cfg["attn_layer_offset"],
        ffn_size=cfg["intermediate_size"], d_state=cfg["mamba_d_state"],
        d_conv=cfg["mamba_d_conv"], dt_rank=cfg["mamba_dt_rank"],
        expand=cfg["mamba_expand"], conv_bias=cfg["mamba_conv_bias"],
        eps=cfg["rms_norm_eps"], max_length=cfg["max_position_embeddings"],
        param_dtype=cfg["param_dtype"]).network()


def load(net, weights: dict) -> None:
    """The leaves are shared with the reference, not copied (the serving
    programs donate only the pools), and the head is handed the embedding's
    own array: one matrix on the device."""
    net.params = ([dict(weights["emb"])]
                  + [dict(lyr) for lyr in weights["layers"]]
                  + [dict(weights["head"])])
    Jamba.tie(net)
