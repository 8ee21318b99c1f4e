"""Builder ``zoo.Glm4MoeLite``: the program's GLM-4.7-Flash cut with the
benchmark's weights put in. The program's module is imported here, at the
top: a checkout without it fails at this import, before any weight is
made."""

from deeplearning4j_tpu.zoo.glm4_moe_lite import Glm4MoeLite


def model(cfg: dict) -> Glm4MoeLite:
    return Glm4MoeLite(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        first_dense_layers=cfg["first_k_dense_replace"],
        ffn_size=cfg["intermediate_size"],
        expert_size=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"],
        n_local_experts=cfg.get("num_experts", cfg["n_routed_experts"]),
        expert_offset=cfg.get("expert_offset", 0),
        top_k=cfg["num_experts_per_tok"],
        routed_scale=cfg["routed_scaling_factor"],
        n_shared_experts=cfg["n_shared_experts"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), eps=cfg["rms_norm_eps"],
        max_length=cfg["max_position_embeddings"],
        param_dtype=cfg["param_dtype"])


def build(cfg: dict):
    """The net without parameters: ``init()`` would draw 4.5 billion of them
    (and an optimiser state) next to the benchmark's."""
    return model(cfg).network()


def load(net, weights: dict) -> None:
    """The leaves are shared with the reference, not copied: the serving
    programs donate only the pools."""
    net.params = ([dict(weights["emb"])]
                  + [dict(lyr) for lyr in weights["layers"]]
                  + [dict(weights["head"])])


def self_draft(cfg: dict, weights: dict):
    """The MTP module over the reference's ``mtp`` leaves, for a caller that
    asks for the draft (no cell does: PERF.md section 4)."""
    return model(cfg).mtp(weights["mtp"])
