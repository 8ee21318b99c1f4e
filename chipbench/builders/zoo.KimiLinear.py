"""Builder ``zoo.KimiLinear``: the program's Kimi Linear share with the
benchmark's weights put in. The program's module is imported here, at the
top: a checkout without it fails at this import, before any weight is
made."""

from deeplearning4j_tpu.zoo.kimi_linear import KimiLinear


def build(cfg: dict):
    """The net without parameters: ``init()`` would draw 4.3 billion of them
    (and an optimiser state) next to the benchmark's."""
    lin = cfg["linear_attn_config"]
    return KimiLinear(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        full_attn_layers=tuple(lin["full_attn_layers"]),
        first_dense_layers=cfg["first_k_dense_replace"],
        ffn_size=cfg["intermediate_size"],
        expert_size=cfg["moe_intermediate_size"],
        n_experts=cfg.get("published_num_experts", cfg["num_experts"]),
        n_local_experts=cfg["num_experts"],
        expert_offset=cfg.get("expert_offset", 0),
        top_k=cfg["num_experts_per_token"],
        routed_scale=cfg["routed_scaling_factor"],
        n_shared_experts=cfg["num_shared_experts"],
        kda_head_dim=lin["head_dim"],
        conv_size=lin["short_conv_kernel_size"],
        gate_rank=cfg.get("gate_low_rank", lin["head_dim"]),
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        eps=cfg["rms_norm_eps"], max_length=cfg["max_position_embeddings"],
        param_dtype=cfg["param_dtype"]).network()


def load(net, weights: dict) -> None:
    """The leaves are shared with the reference, not copied: the serving
    programs donate only the pools."""
    net.params = ([dict(weights["emb"])]
                  + [dict(lyr) for lyr in weights["layers"]]
                  + [dict(weights["head"])])
