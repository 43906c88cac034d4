"""The program's ModelConfig for a DeepSeek-V3-style configuration file
(multi-head latent attention, leading dense layers, sigmoid-routed experts
with a correction bias, the chip's expert-parallel share).

Everything else of the system under test (the trainer, its state, the
checkpoint manager) is built by :mod:`chipbench.program`, as for every cell.
"""

from __future__ import annotations

from typing import Any, Dict


def model_config(c: Dict[str, Any]):
    """The program's ModelConfig with the file's sizes; refuses what the
    program does not compute (query compression, expert groups, biases)."""
    from repro.configs import get_config
    from repro.configs.base import LayerSpec, MLASpec, MoESpec

    if c["q_lora_rank"] is not None or c["attention_bias"]:
        raise ValueError("the program's MLA has no query compression and no attention bias")
    if c["n_group"] != 1 or c["topk_group"] != 1 or c["topk_method"] != "noaux_tc":
        raise ValueError("the program routes over one group of experts (noaux_tc)")
    if c["moe_layer_freq"] != 1 or not c["norm_topk_prob"] or c["num_nextn_predict_layers"]:
        raise ValueError("the program makes every layer after the leading ones an MoE layer, "
                         "renormalises the top-k weights and predicts one token")
    base = get_config(c["program_arch"])
    mla = MLASpec(
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"],
    )
    moe = MoESpec(
        n_experts=c["n_routed_experts"] * c["ep_size"],
        top_k=c["num_experts_per_tok"],
        d_ff=c["moe_intermediate_size"],
        n_shared=c["n_shared_experts"],
        capacity_factor=c["moe_capacity_factor"],
        scoring=c["scoring_func"],
        routed_scale=c["routed_scaling_factor"],
        balance="seq" if c["seq_aux"] else "switch",
        load_balance_coef=c["aux_loss_alpha"],
        bias_rate=c["bias_update_speed"],
        n_held=c["n_routed_experts"],
        first_held=c["first_held_expert"],
    )
    return base.replace(
        d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        lead=(LayerSpec(mixer="mla", ffn="dense"),) * c["first_k_dense_replace"],
        pattern=(LayerSpec(mixer="mla", ffn="moe"),),
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=mla.qk_head_dim,
        d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        norm="rmsnorm",
        tie_embeddings=bool(c["tie_word_embeddings"]),
        mla=mla,
        moe=moe,
        dtype=c["compute_dtype"],
        param_dtype=c["param_dtype"],
    )
