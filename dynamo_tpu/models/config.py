"""Model architecture configuration.

Read from a HuggingFace ``config.json`` (the model-card plane hands the
engine a local model directory, mirroring the reference's
ModelDeploymentCard/ModelInfoType flow — lib/llm/src/model_card/model.rs:37-63)
or constructed directly for tests/benchmarks.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Optional


# model_type values served by the dense decoder (models/llama.py) and by
# the latent-attention + routed-expert block (models/mla_moe.py); the
# state-space + attention hybrid with routed experts is models/ssm_moe.py
_DENSE_TYPES = frozenset({"llama", "mistral", "qwen2", "ouro"})
# the dense decoder run ``total_ut_steps`` times over the SAME weights, a
# K/V plane a (step, layer), four norms a layer, the final norm and an
# exit gate after every step (_ouro_loop reads what the dense reader does
# not; models/llama.py builds it as static branches of the dense decoder)
_LOOPED_TYPES = frozenset({"ouro"})
_MLA_MOE_TYPES = frozenset({"deepseek_v3", "joyai_llm_flash", "xing4_0"})
_SSM_MOE_TYPES = frozenset({"granitemoehybrid"})
# linear-attention layers with a matrix state beside block-sparse NoPE GQA
# layers, one dense MLP a layer: the same hybrid stack (models/ssm_moe.py)
# with other mixers
_LINEAR_SPARSE_TYPES = frozenset({"minicpm_sala"})
# delta-rule linear attention (KDA) layers beside latent (MLA) layers, a
# leading run of dense layers, then sigmoid-routed experts picked within
# groups: the same hybrid stack again (_from_hf_kda_latent). Tested BEFORE
# the latent block's ``"kv_lora_rank" in d`` arm: such a file has the key
_KDA_LATENT_TYPES = frozenset({"ling3_flash", "bailing_hybrid"})
# Mamba-1 (selective scan) layers beside NoPE attention layers, one dense
# SwiGLU a layer, the head tied: the same hybrid stack (_from_hf_jamba)
_JAMBA_TYPES = frozenset({"jamba"})
_JAMBA_KEYS = ("attn_layer_offset", "attn_layer_period", "mamba_d_conv",
               "mamba_d_state", "mamba_dt_rank", "mamba_expand",
               "mamba_conv_bias", "mamba_proj_bias", "num_experts",
               "intermediate_size", "num_attention_heads",
               "num_key_value_heads", "num_hidden_layers")
# Mamba-1 layers, window / full / cross DIFFERENTIAL attention layers and
# gated memory units, LayerNorm, one dense SwiGLU a layer, the head tied:
# the same hybrid stack (_from_hf_phi4flash)
_PHI4FLASH_TYPES = frozenset({"phi4flash"})
_PHI4FLASH_KEYS = ("mb_per_layer", "sliding_window", "layer_norm_eps",
                   "intermediate_size", "num_attention_heads",
                   "num_key_value_heads", "num_hidden_layers")
# window and full rotary GQA layers whose query-head counts differ by layer
# over one K/V geometry, a per-head sigmoid gate on the attention output,
# a rotary rule a layer kind, one leading dense SwiGLU and then sigmoid-
# routed experts with a shared one, the head untied: the same hybrid stack
# (_from_hf_laguna)
_LAGUNA_TYPES = frozenset({"laguna"})
_LAGUNA_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
                "num_attention_heads", "num_key_value_heads", "head_dim",
                "vocab_size", "rms_norm_eps", "num_experts",
                "num_experts_per_tok", "moe_intermediate_size",
                "shared_expert_intermediate_size", "norm_topk_prob",
                "mlp_only_layers", "gating", "sliding_window",
                "rope_parameters", "layer_types", "mlp_layer_types",
                "gating_types", "moe_routed_scaling_factor",
                "num_attention_heads_per_layer")
# the published names of its layer kinds -> the kinds models/ssm_moe.py builds
_LAGUNA_KINDS = {"full_attention": "attention",
                 "sliding_attention": "window_attention"}
_YARN_RULE_KEYS = ("factor", "original_max_position_embeddings",
                   "beta_fast", "beta_slow", "attention_factor")
# keys that mean "not a dense Llama": a config carrying one is refused
# rather than read with its extra structure dropped
_FOREIGN_KEYS = ("kv_lora_rank", "q_lora_rank", "n_routed_experts",
                 "num_experts", "num_local_experts", "layer_types",
                 "sliding_window", "first_k_dense_replace")
_MLA_KEYS = ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim")
_ROUTED_KEYS = ("n_routed_experts", "num_experts_per_tok",
                "moe_intermediate_size", "n_shared_experts",
                "first_k_dense_replace", "routed_scaling_factor",
                "norm_topk_prob")
# manifold-constrained hyper-connections (arXiv:2512.24880): all five or
# none. With them the residual state is hc_mult streams (ops/
# hyper_connection.py)
_HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
            "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
# the one rope_scaling this block implements: YaRN as DeepSeek-V3's
# config.json parameterises it (ops/rope.py: yarn_inv_freq, yarn_mscale)
_YARN_KEYS = frozenset({"type", "factor", "original_max_position_embeddings",
                        "beta_fast", "beta_slow", "mscale",
                        "mscale_all_dim"})
# the hybrid block as its config.json parameterises it: every key must
# be there (models/ssm_moe.py reads them from ``ModelConfig.hybrid``)
_SSM_KEYS = ("mamba_n_heads", "mamba_d_head", "mamba_d_state",
             "mamba_d_conv", "mamba_expand", "mamba_n_groups",
             "mamba_chunk_size", "mamba_conv_bias", "mamba_proj_bias")
_HYBRID_KEYS = _SSM_KEYS + (
    "layer_types", "attention_multiplier", "embedding_multiplier",
    "residual_multiplier", "logits_scaling", "shared_intermediate_size",
    "num_local_experts", "num_experts_per_tok", "intermediate_size")
_LAYER_KINDS = frozenset({"mamba", "attention"})
# the linear-attention + sparse-attention stack as its config.json
# parameterises it: every key must be there
_LINEAR_SPARSE_KEYS = (
    "mixer_types", "lightning_nh", "lightning_nkv", "lightning_head_dim",
    "lightning_scale", "lightning_use_rope", "attn_use_rope", "qk_norm",
    "use_output_gate", "use_output_norm", "attn_use_output_gate",
    "scale_emb", "scale_depth", "dim_model_base", "sparse_config")
# the published names of its mixers -> the kinds models/ssm_moe.py builds
_MIXER_KINDS = {"lightning-attn": "linear_attention",
                "minicpm4": "sparse_attention"}
# the selection's geometry (InfLLM-V2's ``sparse_config``): compressed keys of ``kernel_size`` positions every
# ``kernel_stride``, blocks of ``block_size`` positions, ``topk`` blocks
# a query in all, of which ``init_blocks`` leading ones and those over the
# last ``window_size`` positions are forced; queries below ``dense_len``
# attend everything
_SPARSE_KEYS = ("kernel_size", "kernel_stride", "block_size", "topk",
                "init_blocks", "window_size", "dense_len")
# the KDA + latent stack as its config.json parameterises it: every key
# the equations read, each with the one value this program builds (None:
# any value, checked by the reader)
_KDA_LATENT_KEYS = {
    "hidden_size": None, "intermediate_size": None,
    "num_hidden_layers": None, "num_attention_heads": None,
    "head_dim": None, "vocab_size": None, "first_k_dense_replace": None,
    "moe_intermediate_size": None,
    "moe_shared_expert_intermediate_size": None, "num_experts": None,
    "num_experts_per_tok": None, "n_group": None, "topk_group": None,
    "routed_scaling_factor": None, "kv_lora_rank": None,
    "q_lora_rank": None, "qk_nope_head_dim": None,
    "qk_rope_head_dim": None, "v_head_dim": None, "rope_theta": None,
    "rms_norm_eps": None, "layer_group_size": None,
    "short_conv_kernel_size": None, "kda_lower_bound": None,
    "rotary_dim": None, "partial_rotary_factor": None,
    "num_kv_heads_for_linear_attn": None,
    "expert_swiglu_limit_list": None,
    "share_expert_swiglu_limit_list": None,
    "score_function": "sigmoid", "norm_topk_prob": True,
    "moe_router_enable_expert_bias": True, "kda_safe_gate": True,
    "linear_silu": True, "use_qk_norm": True, "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "no_kda_lora": True, "use_kda_lora": False, "use_nGPT": False,
    "value_norm": False, "up_proj_norm": False,
    "scale_router_input": False, "mtp_use_kda": False,
    "use_mla_nope": False,
}
_TINY_KDA_LATENT = {
    "model_type": "ling3_flash", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 7,
    "first_k_dense_replace": 1, "layer_group_size": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "num_kv_heads_for_linear_attn": 0, "short_conv_kernel_size": 4,
    "kda_safe_gate": True, "kda_lower_bound": -5, "linear_silu": True,
    "use_qk_norm": True, "group_norm_size": 1,
    "gated_attention_proj_granularity_type": "head_wise",
    "no_kda_lora": True, "use_kda_lora": False, "mtp_use_kda": False,
    "use_nGPT": False, "value_norm": False, "up_proj_norm": False,
    "scale_router_input": False, "use_mla_nope": False,
    "q_lora_rank": None, "kv_lora_rank": 24, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rotary_dim": 8,
    "partial_rotary_factor": 0.5, "rope_theta": 10000.0,
    "num_experts": 16, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
    "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 32,
    "num_local_experts": 4,
    "expert_share": {"published_experts": 16, "of": 4, "index": 0},
    "expert_swiglu_limit_list": [0] * 7,
    "share_expert_swiglu_limit_list": [0] * 7,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
}
_TINY_JAMBA = {
    "model_type": "jamba", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 6,
    "attn_layer_offset": 1, "attn_layer_period": 3,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "num_experts": 1, "num_experts_per_tok": 1,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "mamba_d_conv": 4, "mamba_d_state": 16, "mamba_dt_rank": 8,
    "mamba_expand": 2, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "hidden_act": "silu", "rms_norm_eps": 1e-6, "sliding_window": None,
    "max_position_embeddings": 512, "tie_word_embeddings": True,
}
# layers of ONE part each (``hybrid_override_pattern``: a Mamba-2 mixer with
# B/C groups, a NoPE GQA mixer, an expert layer of UNGATED relu^2 experts,
# or a dense relu^2 MLP; never two), sigmoid-routed experts in one group
# with a shared one, the head untied: the same hybrid stack
# (_from_hf_nemotron_h)
_NEMOTRON_H_TYPES = frozenset({"nemotron_h"})
_NEMOTRON_H_KEYS = (
    "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
    "vocab_size", "layer_norm_epsilon", "tie_word_embeddings",
    "num_attention_heads", "num_key_value_heads", "head_dim",
    "mamba_num_heads", "mamba_head_dim", "n_groups", "ssm_state_size",
    "conv_kernel", "chunk_size", "mamba_hidden_act", "use_conv_bias",
    "mlp_hidden_act", "intermediate_size", "n_routed_experts",
    "num_experts_per_tok", "moe_intermediate_size", "n_shared_experts",
    "moe_shared_expert_intermediate_size", "n_group", "topk_group",
    "norm_topk_prob", "routed_scaling_factor")
# the pattern's letters -> the kinds models/ssm_moe.py builds
_NEMOTRON_H_KINDS = {"M": "mamba", "*": "attention", "E": "experts",
                     "-": "mlp"}
_TINY_NEMOTRON_H = {
    "model_type": "nemotron_h", "vocab_size": 256, "hidden_size": 64,
    "num_hidden_layers": 13, "hybrid_override_pattern": "MEMEM*EMEMEM*",
    "intermediate_size": 40, "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "mlp_bias": False, "use_bias": False,
    "mamba_num_heads": 8, "mamba_head_dim": 12, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "mamba_hidden_act": "silu", "mamba_proj_bias": False,
    "use_conv_bias": True, "mlp_hidden_act": "relu2",
    "n_routed_experts": 4, "num_experts_per_tok": 6,
    "expert_share": {"published_experts": 32, "of": 8, "index": 0},
    "moe_intermediate_size": 40, "moe_shared_expert_intermediate_size": 80,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "sliding_window": None, "residual_in_fp32": False,
    "rope_theta": 10000, "partial_rotary_factor": 1,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
}
_TINY_PHI4FLASH = {
    "model_type": "phi4flash", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 8, "mb_per_layer": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "sliding_window": 8, "layer_norm_eps": 1e-5, "hidden_act": "silu",
    "mamba_dt_rank": 8, "mlp_bias": False, "lm_head_bias": False,
    "max_position_embeddings": 512, "tie_word_embeddings": True,
}
_TINY_LAGUNA = {
    "model_type": "laguna", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 6,
    "num_attention_heads": 12, "num_key_value_heads": 2, "head_dim": 16,
    "attention_bias": False, "rms_norm_eps": 1e-6,
    "num_experts": 4, "num_experts_per_tok": 4,
    "expert_share": {"published_experts": 16, "of": 4, "index": 0},
    "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
    "norm_topk_prob": True, "decoder_sparse_step": 1,
    "mlp_only_layers": [0], "gating": "per-head", "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 4,
            "original_max_position_embeddings": 32, "beta_slow": 1,
            "beta_fast": 32, "attention_factor": 1.1386294361119891,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "layer_types": ["full_attention"] + ["sliding_attention"] * 3
                   + ["full_attention", "sliding_attention"],
    "mlp_layer_types": ["dense"] + ["sparse"] * 5,
    "gating_types": ["per_head"] * 6,
    "num_attention_heads_per_layer": [12, 18, 18, 18, 12, 18],
    "moe_apply_router_weight_on_input": False,
    "moe_routed_scaling_factor": 2.5, "moe_router_logit_softcapping": 0,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
}
_TINY_LINEAR_SPARSE = {
    "model_type": "minicpm_sala", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 6,
    "mixer_types": ["lightning-attn", "minicpm4", "minicpm4",
                    "lightning-attn", "lightning-attn", "minicpm4"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "attn_use_rope": False, "qk_norm": True, "use_output_gate": True,
    "use_output_norm": True, "attn_use_output_gate": True,
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
    "depth_scale_layers": 8,
    "sparse_config": {"kernel_size": 4, "kernel_stride": 2, "block_size": 8,
                      "topk": 6, "init_blocks": 1, "window_size": 16,
                      "dense_len": 64},
    "attention_bias": False, "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "rope_theta": 10000, "max_position_embeddings": 512,
    "tie_word_embeddings": False,
}
_TINY_SSM_MOE = {
    "model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 32, "shared_intermediate_size": 48,
    "num_hidden_layers": 6,
    "layer_types": ["mamba", "mamba", "attention"] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_chunk_size": 8, "mamba_conv_bias": True,
    "mamba_proj_bias": False,
    "num_local_experts": 4, "num_experts_per_tok": 2,
    "expert_share": {"published_experts": 8, "of": 2, "index": 0},
    "attention_multiplier": 0.0625, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "logits_scaling": 16,
    "position_embedding_type": "nope", "rope_scaling": None,
    "attention_bias": False, "hidden_act": "silu",
    "normalization_function": "rmsnorm", "rms_norm_eps": 1e-5,
    "max_position_embeddings": 512, "tie_word_embeddings": True,
}
_TINY_MLA_MOE = {
    "model_type": "deepseek_v3", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 24,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "first_k_dense_replace": 1, "routed_scaling_factor": 2.5,
    "norm_topk_prob": True, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
    "rope_theta": 10000.0, "rope_interleave": True, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
    "hidden_act": "silu", "tie_word_embeddings": False,
}
_TINY_MHC = {
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "rope_scaling": {
        "type": "yarn", "factor": 4, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
}


@dataclass(frozen=True)
class ModelConfig:
    """Llama-family architecture hyperparameters."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    # Hashable (the config is a jit static arg): tuple of sorted (key, value)
    # pairs, e.g. (("factor", 8.0), ("rope_type", "llama3"), ...). Use
    # `rope_scaling_dict` to read.
    rope_scaling: Optional[tuple[tuple[str, Any], ...]] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 8192
    tie_word_embeddings: bool = False
    model_type: str = "llama"
    dtype: str = "bfloat16"
    # Mixture-of-Experts FFN (hashable, like rope_scaling): tuple of sorted
    # (key, value) pairs with keys num_experts / top_k / capacity_factor.
    # None = dense MLP. Experts shard over the `ep` mesh axis, expert
    # hidden dim over `tp` (the sglang wide-EP shape, SURVEY §2.5).
    moe: Optional[tuple[tuple[str, Any], ...]] = None
    # Weight quantization: None (dense, `dtype`) or "int8" (w8a16:
    # per-output-channel symmetric int8 weights dequantized inside the
    # matmul — llama.py _mm). Halves weight bytes, which both halves the
    # decode weight-pass floor and is what fits an 8B on a 16 GB v5e
    # (the reference's FP8 recipes, examples/llm/benchmarks/README.md:28).
    quant: Optional[str] = None
    # Latent (MLA) attention + the DeepSeek-V3 expert block (hashable like
    # rope_scaling; keys in _MLA_KEYS / _ROUTED_KEYS below). With `mla`
    # set the model is served by models/mla_moe.py: ONE cached row of
    # kv_lora_rank + qk_rope_head_dim values a token a layer, a leading
    # run of dense layers, then expert layers. `num_kv_heads`/`head_dim`
    # then describe that cached row (1 head of the row's width).
    mla: Optional[tuple[tuple[str, Any], ...]] = None
    routed: Optional[tuple[tuple[str, Any], ...]] = None
    # the four-stream residual of that block (keys in _HC_KEYS); None =
    # one stream, the plain residual
    hc: Optional[tuple[tuple[str, Any], ...]] = None
    # Layers of two kinds, Mamba-2 state-space mixers and NoPE attention,
    # each followed by softmax-routed experts of which this chip may hold
    # a share (hashable; keys in _HYBRID_KEYS plus the share). With
    # `hybrid` set the model is served by models/ssm_moe.py: K/V rows for
    # the attention layers and, beside them, a recurrent state a lane for
    # each state-space layer. `num_layers` counts both kinds;
    # `num_heads`/`num_kv_heads`/`head_dim` are the attention layers'.
    # The same stack with other mixers (_from_hf_linear_sparse): linear
    # attention with a matrix state, block-sparse attention with
    # compressed-key rows, one dense MLP a layer; and
    # (_from_hf_kda_latent, which sets `mla` beside `hybrid`) delta-rule
    # linear attention with a matrix state and three convolution windows,
    # latent attention with the `kv` row of the latent block, a leading
    # run of dense layers, grouped sigmoid routing. What a layer does
    # follows from `layer_types`: mamba | attention | linear_attention |
    # sparse_attention | kda | latent_attention | mamba1 (_from_hf_jamba:
    # the selective scan with a decay per channel and state column, one
    # dense MLP a layer, the head tied) | window_attention |
    # cross_attention | gmu (_from_hf_phi4flash: differential attention
    # behind a window, over another layer's rows, and a gated memory unit
    # over another layer's scan; LayerNorm); and (_from_hf_laguna) the
    # attention and window_attention kinds in the ROTARY GQA form: a
    # rotary rule a kind (`rope`), every layer's own number of query heads
    # (`heads_by_layer`; `num_heads` is then the published default only), a
    # sigmoid gate a head, the one-group sigmoid router with a share; and
    # (_from_hf_nemotron_h) layers of ONE part (`one_part`: a mixer kind,
    # or `experts` | `mlp`, a feed-forward kind of its own), Mamba-2 with
    # B/C groups (`mamba_n_groups`), experts without a gate matrix
    # (`expert_act` relu2).
    hybrid: Optional[tuple[tuple[str, Any], ...]] = None
    # The looped dense decoder (_ouro_loop): the stack of ``num_layers``
    # WEIGHT layers runs ``loop_steps`` times over the same weights, the
    # final norm after every step (its output feeds the next step and,
    # after the last, the head). Attention at step t, layer l reads and
    # writes CACHE PLANE t * num_layers + l (``cache_planes`` of them):
    # whatever sizes a cache asks ``cache_planes``, whatever indexes a
    # weight asks ``num_layers``. ``sandwich_norms``: a second norm on
    # each half's output, ahead of the residual add (gains ln1b, ln2b).
    # ``exit_gate``: a Linear(hidden, 1) + sigmoid on every step's
    # output; the exit CDF is evaluated and counted, and at the served
    # threshold of 1 only the last step reaches it.
    loop_steps: int = 1
    sandwich_norms: bool = False
    exit_gate: bool = False

    @property
    def looped(self) -> bool:
        """Whether the dense decoder takes any of its loop branches; a
        config that takes none lowers to the plain dense programs."""
        return self.loop_steps > 1 or self.sandwich_norms or self.exit_gate

    @property
    def cache_planes(self) -> int:
        """Planes of K/V a cache (region, ring, pool page, transfer
        frame) holds a token: one a weight layer a loop step."""
        return self.num_layers * self.loop_steps

    @property
    def hybrid_dict(self) -> Optional[dict[str, Any]]:
        return dict(self.hybrid) if self.hybrid else None

    @property
    def hc_dict(self) -> Optional[dict[str, Any]]:
        return dict(self.hc) if self.hc else None

    @property
    def mla_dict(self) -> Optional[dict[str, Any]]:
        return dict(self.mla) if self.mla else None

    @property
    def routed_dict(self) -> Optional[dict[str, Any]]:
        return dict(self.routed) if self.routed else None

    @property
    def rope_scaling_dict(self) -> Optional[dict[str, Any]]:
        return dict(self.rope_scaling) if self.rope_scaling else None

    @property
    def moe_dict(self) -> Optional[dict[str, Any]]:
        return dict(self.moe) if self.moe else None

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @classmethod
    def from_hf_dict(cls, d: dict[str, Any]) -> "ModelConfig":
        model_type = d.get("model_type", "llama")
        if model_type in _KDA_LATENT_TYPES:
            return cls._from_hf_kda_latent(d)
        if model_type in _MLA_MOE_TYPES or "kv_lora_rank" in d:
            return cls._from_hf_mla_moe(d)
        if model_type in _SSM_MOE_TYPES:
            return cls._from_hf_ssm_moe(d)
        if model_type in _JAMBA_TYPES:
            return cls._from_hf_jamba(d)
        if model_type in _PHI4FLASH_TYPES:
            return cls._from_hf_phi4flash(d)
        if model_type in _LINEAR_SPARSE_TYPES:
            return cls._from_hf_linear_sparse(d)
        if model_type in _LAGUNA_TYPES:
            return cls._from_hf_laguna(d)
        if model_type in _NEMOTRON_H_TYPES:
            return cls._from_hf_nemotron_h(d)
        if model_type not in _DENSE_TYPES:
            raise ValueError(
                f"model_type {model_type!r} is no block this program "
                f"builds (dense: {sorted(_DENSE_TYPES)}; latent attention "
                f"+ routed experts: {sorted(_MLA_MOE_TYPES)}; state-space "
                f"+ attention layers with routed experts: "
                f"{sorted(_SSM_MOE_TYPES)}; linear-attention + "
                f"block-sparse attention layers: "
                f"{sorted(_LINEAR_SPARSE_TYPES)}; delta-rule linear "
                f"attention + latent attention layers with grouped "
                f"sigmoid experts (_from_hf_kda_latent): "
                f"{sorted(_KDA_LATENT_TYPES)}; Mamba-1 + differential "
                f"window / full / cross attention + gated memory units "
                f"(_from_hf_phi4flash): {sorted(_PHI4FLASH_TYPES)}; window "
                f"+ full rotary GQA layers with head counts by layer, a "
                f"head gate and sigmoid experts (_from_hf_laguna): "
                f"{sorted(_LAGUNA_TYPES)})")
        loop = cls._ouro_loop(d) if model_type in _LOOPED_TYPES else {}
        unknown = sorted(k for k in _FOREIGN_KEYS
                         if d.get(k) and not (loop and k == "layer_types"))
        if unknown:
            raise ValueError(
                f"config keys {unknown} belong to a block the dense "
                f"{model_type!r} decoder does not have; refusing to read "
                "it as a Llama")
        num_heads = d["num_attention_heads"]
        head_dim = d.get("head_dim") or d["hidden_size"] // num_heads
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=num_heads,
            num_kv_heads=d.get("num_key_value_heads", num_heads),
            head_dim=head_dim,
            rope_theta=d.get("rope_theta", 10000.0),
            rope_scaling=(
                tuple(sorted(d["rope_scaling"].items()))
                if d.get("rope_scaling")
                else None
            ),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=d.get("tie_word_embeddings", False),
            model_type=d.get("model_type", "llama"),
            **loop,
        )

    @staticmethod
    def _ouro_loop(d: dict[str, Any]) -> dict[str, Any]:
        """The loop's fields of an ``ouro`` file (LoopLM, arXiv:2510.25741):
        ``total_ut_steps`` passes of the stack, sandwich norms and the
        exit gate always. The Qwen2-style window keys the family's files
        carry dead are read as what they say, no window
        (``max_window_layers`` then means nothing); a window, a layer
        that is not full attention, or a threshold under 1 is refused."""
        steps = int(d.get("total_ut_steps", 1))
        if steps < 1:
            raise ValueError(f"total_ut_steps={steps}: at least one pass")
        if d.get("use_sliding_window") or d.get("sliding_window"):
            raise ValueError(
                "an ouro file with a sliding window (use_sliding_window="
                f"{d.get('use_sliding_window')!r}, sliding_window="
                f"{d.get('sliding_window')!r}): the looped dense decoder "
                "has full attention only")
        kinds = set(d.get("layer_types") or ()) - {"full_attention"}
        if kinds:
            raise ValueError(
                f"ouro layer_types {sorted(kinds)}: the looped dense "
                "decoder has full_attention layers only")
        threshold = float(d.get("early_exit_threshold", 1.0))
        if threshold < 1.0:
            raise ValueError(
                f"early_exit_threshold={threshold:g} < 1 (per-token early "
                "exit: a step that no longer runs for every lane, K/V "
                "planes of skipped steps to fill) is not served yet; the "
                "published threshold of 1 runs every step for every token")
        return dict(loop_steps=steps, sandwich_norms=True, exit_gate=True)

    @classmethod
    def _from_hf_mla_moe(cls, d: dict[str, Any]) -> "ModelConfig":
        """The DeepSeek-V3 block as a config.json parameterises it. Every
        key the equations need must be there with a value this program
        implements; anything else is an error, never a default."""
        missing = sorted(k for k in _MLA_KEYS + _ROUTED_KEYS if k not in d)
        if missing:
            raise ValueError(f"latent-attention block: keys {missing} "
                             "are missing from the config")
        scaling = d.get("rope_scaling")
        if scaling is not None and (
                not isinstance(scaling, dict)
                or scaling.get("type") != "yarn"
                or set(scaling) != _YARN_KEYS):
            raise ValueError(
                f"latent-attention block: rope_scaling {scaling!r} is one "
                "this program does not implement: only type 'yarn' with "
                f"exactly the keys {sorted(_YARN_KEYS)}")
        hc_given = sorted(k for k in _HC_KEYS if k in d)
        if hc_given and len(hc_given) != len(_HC_KEYS):
            raise ValueError(
                "latent-attention block: hyper-connection keys "
                f"{sorted(set(_HC_KEYS) - set(hc_given))} are missing "
                f"from the config (it has {hc_given})")
        refused = {
            "scoring_func": d["scoring_func"] != "sigmoid",
            "topk_method": d.get("topk_method", "noaux_tc") != "noaux_tc",
            "n_group/topk_group": (d.get("n_group", 1), d.get(
                "topk_group", 1)) != (1, 1),
            "hidden_act": d.get("hidden_act", "silu") != "silu",
            "attention_bias": bool(d.get("attention_bias")),
            "moe_layer_freq": d.get("moe_layer_freq", 1) != 1,
            "rope_interleave": not d.get("rope_interleave", True),
            "num_nextn_predict_layers (the draft head is not built)":
                d.get("num_nextn_predict_layers", 0) != 0,
            "tie_word_embeddings": bool(d.get("tie_word_embeddings")),
            "hc_mult < 2": bool(hc_given) and int(d["hc_mult"]) < 2,
            "hc_sinkhorn_iters < 1":
                bool(hc_given) and int(d["hc_sinkhorn_iters"]) < 1,
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(
                f"latent-attention block: {bad} have values this program "
                "does not implement")
        mla = {k: d[k] for k in _MLA_KEYS}
        routed = {k: d[k] for k in _ROUTED_KEYS}
        row = d["kv_lora_rank"] + d["qk_rope_head_dim"]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=1,
            head_dim=row,
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            model_type=d.get("model_type", "deepseek_v3"),
            rope_scaling=(tuple(sorted(scaling.items()))
                          if scaling else None),
            mla=tuple(sorted(mla.items())),
            routed=tuple(sorted(routed.items())),
            hc=(tuple((k, d[k]) for k in _HC_KEYS) if hc_given else None),
        )

    @classmethod
    def _from_hf_ssm_moe(cls, d: dict[str, Any]) -> "ModelConfig":
        """The state-space + attention hybrid with routed experts, as a
        ``granitemoehybrid`` config.json parameterises it. Every key the
        equations need must be there with a value this program builds;
        anything else is refused by name, never defaulted.

        The experts held HERE are ``num_local_experts`` (the published
        key; a cut configuration lists it under ``reduced``). A file that
        holds a share states the deployment under a key of its own,
        ``expert_share``: ``published_experts`` (the router's width),
        ``of`` (the chips that share a layer's experts) and ``index``
        (which of them this is: it holds experts ``index * held`` up to
        ``(index + 1) * held``). Without the key every expert is held."""
        missing = sorted(k for k in _HYBRID_KEYS if k not in d)
        if missing:
            raise ValueError(f"state-space hybrid block: keys {missing} "
                             "are missing from the config")
        kinds = tuple(d["layer_types"])
        held = int(d["num_local_experts"])
        share = d.get("expert_share") or {
            "published_experts": held, "of": 1, "index": 0}
        if set(share) != {"published_experts", "of", "index"}:
            raise ValueError(
                "state-space hybrid block: expert_share needs exactly "
                f"published_experts, of and index (it has {sorted(share)})")
        E, of, index = (int(share[k]) for k in
                        ("published_experts", "of", "index"))
        nh, hd = int(d["mamba_n_heads"]), int(d["mamba_d_head"])
        refused = {
            f"position_embedding_type {d.get('position_embedding_type')!r}"
            " (only 'nope': no rotary is built for this block)":
                d.get("position_embedding_type") != "nope",
            "rope_scaling (there is no rotary to scale)":
                d.get("rope_scaling") is not None,
            f"mamba_n_groups {d['mamba_n_groups']} (no whole number of "
            f"the {nh} heads reads one group's B and C)":
                d["mamba_n_groups"] < 1 or nh % d["mamba_n_groups"] != 0,
            "attention_bias": bool(d.get("attention_bias")),
            "mamba_proj_bias": bool(d["mamba_proj_bias"]),
            "mamba_conv_bias false": not d["mamba_conv_bias"],
            f"hidden_act {d.get('hidden_act')!r}":
                d.get("hidden_act", "silu") != "silu",
            f"normalization_function {d.get('normalization_function')!r}":
                d.get("normalization_function", "rmsnorm") != "rmsnorm",
            "tie_word_embeddings false (the head is the embedding)":
                not d.get("tie_word_embeddings"),
            f"layer_types other than {sorted(_LAYER_KINDS)}":
                not set(kinds) <= _LAYER_KINDS,
            "layer_types whose length is not num_hidden_layers":
                len(kinds) != d["num_hidden_layers"],
            "mamba_d_conv < 2": d["mamba_d_conv"] < 2,
            f"expert_share: {held} held x {of} chips is not the published "
            f"{E} experts (a share is a whole-number split)":
                of < 1 or held * of != E,
            f"expert_share index {index} outside 0..{of - 1}":
                not 0 <= index < max(of, 1),
            "num_experts_per_tok above the published experts":
                d["num_experts_per_tok"] > E,
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(
                f"state-space hybrid block: {bad} are values this program "
                "does not build")
        hybrid = {k: d[k] for k in _HYBRID_KEYS if k != "layer_types"}
        hybrid.update(layer_types=kinds, published_experts=E,
                      share_of=of, share_index=index)
        num_heads = d["num_attention_heads"]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=num_heads,
            num_kv_heads=d.get("num_key_value_heads", num_heads),
            head_dim=d.get("head_dim") or d["hidden_size"] // num_heads,
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=True,
            model_type=d["model_type"],
            hybrid=tuple(sorted(hybrid.items())),
        )

    @classmethod
    def _from_hf_linear_sparse(cls, d: dict[str, Any]) -> "ModelConfig":
        """Linear-attention layers with a decayed matrix state beside
        block-sparse NoPE GQA layers, one dense SwiGLU a layer, muP
        multipliers on the embedding, the residual and the logits. Every key the equations need must be there with
        a value this program builds; anything else is refused by name.

        ``sparse_config`` (the selection's geometry, ``_SPARSE_KEYS``) is
        the family's convention and a key of its own in a configuration
        file, as is ``depth_scale_layers``: the depth that
        ``scale_depth / sqrt(depth)`` is taken at, the PUBLISHED one where
        a file holds a cut of the layers (default: num_hidden_layers)."""
        missing = sorted(k for k in _LINEAR_SPARSE_KEYS if k not in d)
        if missing:
            raise ValueError("linear + sparse attention block: keys "
                             f"{missing} are missing from the config")
        published = tuple(d["mixer_types"])
        kinds = tuple(_MIXER_KINDS.get(k, k) for k in published)
        sparse = d["sparse_config"]
        if not isinstance(sparse, dict) or set(sparse) != set(_SPARSE_KEYS):
            raise ValueError(
                "linear + sparse attention block: sparse_config needs "
                f"exactly {sorted(_SPARSE_KEYS)} (it has "
                f"{sorted(sparse) if isinstance(sparse, dict) else sparse})")
        sp = {k: int(sparse[k]) for k in _SPARSE_KEYS}
        heads = d["num_attention_heads"]
        hd = d.get("head_dim") or d["hidden_size"] // heads
        forced = sp["init_blocks"] + -(-sp["window_size"]
                                       // max(sp["block_size"], 1)) + 1
        refused = {
            f"mixer_types other than {sorted(_MIXER_KINDS)}":
                not set(published) <= set(_MIXER_KINDS),
            "mixer_types whose length is not num_hidden_layers":
                len(kinds) != d["num_hidden_layers"],
            "qk_norm false": not d["qk_norm"],
            "use_output_gate false": not d["use_output_gate"],
            "use_output_norm false": not d["use_output_norm"],
            "attn_use_output_gate false": not d["attn_use_output_gate"],
            "attn_use_rope true (the sparse layers are built without "
            "rotary)": bool(d["attn_use_rope"]),
            "lightning_use_rope false": not d["lightning_use_rope"],
            f"lightning_scale {d['lightning_scale']!r} (only '1/sqrt(d)')":
                d["lightning_scale"] != "1/sqrt(d)",
            "lightning_nkv != lightning_nh (no grouped linear attention)":
                d["lightning_nkv"] != d["lightning_nh"],
            "rope_scaling": d.get("rope_scaling") is not None,
            "attention_bias": bool(d.get("attention_bias")),
            f"hidden_act {d.get('hidden_act')!r}":
                d.get("hidden_act", "silu") != "silu",
            "tie_word_embeddings true (the head is a matrix of its own)":
                bool(d.get("tie_word_embeddings")),
            "sparse_config: kernel_size is not 2 x kernel_stride, or "
            "block_size no multiple of kernel_stride":
                sp["kernel_size"] != 2 * sp["kernel_stride"]
                or sp["kernel_stride"] < 1
                or sp["block_size"] % max(sp["kernel_stride"], 1) != 0,
            f"sparse_config: topk {sp['topk']} below the {forced} blocks "
            "that are forced (init_blocks + those over window_size)":
                sp["topk"] < forced,
            "sparse_config: dense_len below topk x block_size (a query "
            "past it must have topk blocks to choose from)":
                sp["dense_len"] < sp["topk"] * sp["block_size"],
            "num_attention_heads no multiple of num_key_value_heads":
                heads % d.get("num_key_value_heads", heads) != 0,
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(
                f"linear + sparse attention block: {bad} are values this "
                "program does not build")
        depth = int(d.get("depth_scale_layers", d["num_hidden_layers"]))
        hybrid = dict(
            layer_types=kinds,
            lightning_heads=int(d["lightning_nh"]),
            lightning_head_dim=int(d["lightning_head_dim"]),
            sparse=tuple(sorted(sp.items())),
            embedding_multiplier=d["scale_emb"],
            residual_multiplier=d["scale_depth"] / depth ** 0.5,
            logits_scaling=d["hidden_size"] / d["dim_model_base"])
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=d.get("num_key_value_heads", heads),
            head_dim=hd,
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=False,
            model_type=d["model_type"],
            hybrid=tuple(sorted(hybrid.items())),
        )

    @classmethod
    def _from_hf_jamba(cls, d: dict[str, Any]) -> "ModelConfig":
        """Mamba-1 mixers (a decay per channel and state column, RMSNorms
        on dt / B / C between ``x_proj`` and ``dt_proj``) with a NoPE
        attention layer where ``i % attn_layer_period ==
        attn_layer_offset``, one dense SwiGLU a layer, the head tied, no
        multipliers: ``model_type: jamba`` with ``num_experts`` 1. Every
        key the equations read must be there with a value this program
        builds; anything else is refused by name. ``head_dim`` is not a
        key of the family: hidden_size / num_attention_heads."""
        missing = sorted(k for k in _JAMBA_KEYS if k not in d)
        if missing:
            raise ValueError("Mamba-1 + attention block: keys "
                             f"{missing} are missing from the config")
        heads, kvh = d["num_attention_heads"], d["num_key_value_heads"]
        refused = {
            f"num_experts {d['num_experts']} (routed experts on this "
            "stack are not built: only the dense feed-forward part, "
            "num_experts 1)": d["num_experts"] != 1,
            "mamba_proj_bias": bool(d["mamba_proj_bias"]),
            "mamba_conv_bias false": not d["mamba_conv_bias"],
            "sliding_window": d.get("sliding_window") is not None,
            f"hidden_act {d.get('hidden_act')!r}":
                d.get("hidden_act", "silu") != "silu",
            "tie_word_embeddings false (the head is the embedding)":
                not d.get("tie_word_embeddings", True),
            "hidden_size no multiple of num_attention_heads":
                d["hidden_size"] % heads != 0,
            "num_attention_heads no multiple of num_key_value_heads":
                heads % kvh != 0,
            "attn_layer_offset outside its period":
                not 0 <= d["attn_layer_offset"] < d["attn_layer_period"],
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(f"Mamba-1 + attention block: {bad} are values "
                             "this program does not build")
        hd = d["hidden_size"] // heads
        kinds = tuple(
            "attention" if i % d["attn_layer_period"]
            == d["attn_layer_offset"] else "mamba1"
            for i in range(d["num_hidden_layers"]))
        hybrid = dict(
            layer_types=kinds,
            m1_inner=int(d["mamba_expand"] * d["hidden_size"]),
            m1_state=int(d["mamba_d_state"]),
            m1_dt_rank=int(d["mamba_dt_rank"]),
            m1_conv=int(d["mamba_d_conv"]),
            # the family has none of the four multipliers
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=hd ** -0.5, logits_scaling=1.0)
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=kvh,
            head_dim=hd,
            rms_norm_eps=d.get("rms_norm_eps", 1e-6),
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=True,
            model_type=d["model_type"],
            hybrid=tuple(sorted(hybrid.items())),
        )

    @classmethod
    def _from_hf_phi4flash(cls, d: dict[str, Any]) -> "ModelConfig":
        """The decoder-hybrid-decoder stack of ``model_type: phi4flash``:
        with ``mb_per_layer`` 2 every even layer is a Mamba-side mixer and
        every odd one an attention-side mixer, and the second half of the
        stack is the cross-decoder. Of L layers (a multiple of 4): even l
        <= L/2 Mamba-1 (no inner norms), odd l < L/2 DIFFERENTIAL attention
        behind a window of ``sliding_window``, l = L/2 + 1 full differential
        attention (the only full K/V rows), and above it even l gated
        memory units over layer L/2's scan output and odd l cross
        differential attention over layer L/2 + 1's rows. LayerNorm (gain
        and bias), projection biases on the attention, one dense SwiGLU a
        layer, the head tied, no rotary, no multipliers. Adjacent heads
        pair, so a K/V row is held pair-wide (2 x head_dim). The Mamba
        sizes are the family's where the file has no key for them (state
        16, conv 4, expand 2, dt rank ceil(hidden / 16)). Anything this
        program does not build is refused by name."""
        missing = sorted(k for k in _PHI4FLASH_KEYS if k not in d)
        if missing:
            raise ValueError("differential-attention hybrid block: keys "
                             f"{missing} are missing from the config")
        L, H = d["num_hidden_layers"], d["hidden_size"]
        heads, kvh = d["num_attention_heads"], d["num_key_value_heads"]
        half = L // 2
        window = d["sliding_window"]
        if isinstance(window, (list, tuple)):
            # one entry a layer: the window on the window layers, none on
            # the others
            at = [w for l, w in enumerate(window) if l % 2 and l < half]
            roles_ok = (len(window) == L and len(set(at)) == 1
                        and all(w is None for l, w in enumerate(window)
                                if not (l % 2 and l < half)))
            window = at[0] if roles_ok and at else None
        rank = d.get("mamba_dt_rank", "auto")
        refused = {
            f"mb_per_layer {d['mb_per_layer']} (only 2: Mamba-side and "
            "attention-side mixers alternate)": d["mb_per_layer"] != 2,
            f"num_hidden_layers {L} (no multiple of 4: the two decoders "
            "are halves of whole (Mamba, attention) pairs)": L % 4 != 0,
            "sliding_window (no one window on the odd layers of the first "
            "half and none elsewhere)":
                not isinstance(window, int) or isinstance(window, bool)
                or window < 2,
            f"num_key_value_heads {kvh} (odd: adjacent K/V heads pair)":
                kvh % 2 != 0,
            "num_attention_heads no multiple of num_key_value_heads":
                heads % kvh != 0,
            "hidden_size no multiple of num_attention_heads":
                H % heads != 0,
            "tie_word_embeddings false (the head is the embedding)":
                not d.get("tie_word_embeddings", True),
            "mlp_bias": bool(d.get("mlp_bias", False)),
            "lm_head_bias": bool(d.get("lm_head_bias", False)),
            f"hidden_act {d.get('hidden_act')!r}":
                d.get("hidden_act", "silu") != "silu",
            "mamba_proj_bias": bool(d.get("mamba_proj_bias", False)),
            "mamba_conv_bias false": not d.get("mamba_conv_bias", True),
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError("differential-attention hybrid block: "
                             f"{bad} are values this program does not build")
        kinds = tuple(
            ("mamba1" if l % 2 == 0 else "window_attention") if l <= half
            else "attention" if l == half + 1
            else "gmu" if l % 2 == 0 else "cross_attention"
            for l in range(L))
        hybrid = dict(
            layer_types=kinds,
            m1_inner=int(d.get("mamba_expand", 2) * H),
            m1_state=int(d.get("mamba_d_state", 16)),
            m1_dt_rank=-(-H // 16) if rank == "auto" else int(rank),
            m1_conv=int(d.get("mamba_d_conv", 4)),
            # the differential form on every attention layer; its window
            # and the rows a window layer's lane buffer holds (a power of
            # two, position p in slot p mod it)
            differential=True, layer_norm=True, m1_inner_norms=False,
            window=int(window),
            window_rows=1 << (int(window) - 1).bit_length(),
            # the layer whose rows the cross layers read, and the layer
            # whose scan output the gated memory units read
            rows_from=half + 1, scan_from=half,
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=(H // heads) ** -0.5, logits_scaling=1.0)
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=H,
            intermediate_size=d["intermediate_size"],
            num_layers=L,
            num_heads=heads,
            num_kv_heads=kvh,
            head_dim=H // heads,
            rms_norm_eps=d["layer_norm_eps"],
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=True,
            model_type=d["model_type"],
            hybrid=tuple(sorted(hybrid.items())),
        )

    @classmethod
    def _from_hf_laguna(cls, d: dict[str, Any]) -> "ModelConfig":
        """Window and full GQA layers of ``model_type: laguna``: every
        layer is softmax attention over ``num_key_value_heads`` K/V heads
        of ``head_dim``, ``full_attention`` over the whole context and
        ``sliding_attention`` over the last ``sliding_window`` positions
        (the query's own among them), with ITS OWN number of query heads
        (``num_attention_heads_per_layer``), a sigmoid gate a head on the
        attention output before W_o (``gating`` ``per-head``) and a rotary
        rule a kind (``rope_parameters``: theta, the share of the head
        that rotates, rotate-half, and YaRN with its factor on cos and
        sin). The layers of ``mlp_only_layers`` (a leading run) carry one
        dense SwiGLU of ``intermediate_size``, the others ``num_experts``
        routed experts of ``moe_intermediate_size`` (top
        ``num_experts_per_tok`` of sigmoid scores with a selection-only
        bias, weights normalised x ``moe_routed_scaling_factor``: the
        grouped sigmoid router with ONE group) plus a shared expert,
        ungated; the head is untied. Anything this program does not build
        is refused by name.

        The experts held HERE are ``num_experts``; a file that holds a
        share states the deployment under ``expert_share``
        (``published_experts``, ``of``, ``index``) as the other expert
        stacks do, and the router keeps the published width."""
        missing = sorted(k for k in _LAGUNA_KEYS if k not in d)
        if missing:
            raise ValueError("window + full GQA block: keys "
                             f"{missing} are missing from the config")
        L, hd = int(d["num_hidden_layers"]), int(d["head_dim"])
        kvh = int(d["num_key_value_heads"])
        held = int(d["num_experts"])
        share = d.get("expert_share") or {
            "published_experts": held, "of": 1, "index": 0}
        if set(share) != {"published_experts", "of", "index"}:
            raise ValueError(
                "window + full GQA block: expert_share needs exactly "
                f"published_experts, of and index (it has {sorted(share)})")
        E, of, index = (int(share[k]) for k in
                        ("published_experts", "of", "index"))
        lists = {k: list(d[k]) for k in (
            "layer_types", "mlp_layer_types", "gating_types",
            "num_attention_heads_per_layer")}
        short = sorted(k for k, v in lists.items() if len(v) != L)
        if short:
            raise ValueError(
                f"window + full GQA block: {short} hold no entry a layer "
                f"(num_hidden_layers {L})")
        kinds = tuple(_LAGUNA_KINDS.get(t, t) for t in lists["layer_types"])
        heads = tuple(int(n) for n in lists["num_attention_heads_per_layer"])
        dense = sorted(int(l) for l in d["mlp_only_layers"])
        window = d["sliding_window"]
        rules = d["rope_parameters"]
        rope, bad_rules = {}, []
        for name, kind in _LAGUNA_KINDS.items():
            r = rules.get(name) if isinstance(rules, dict) else None
            if kind not in kinds:
                continue
            rot = (hd * r.get("partial_rotary_factor", 1)
                   if isinstance(r, dict) else 0)
            yarn = isinstance(r, dict) and r.get("rope_type") == "yarn"
            if (not isinstance(r, dict) or "rope_theta" not in r
                    or r.get("rope_type") not in ("default", "yarn")
                    or rot != int(rot) or int(rot) % 2 or not 0 < rot <= hd
                    or (yarn and any(k not in r for k in _YARN_RULE_KEYS))):
                bad_rules.append(name)
                continue
            rule = {"type": r["rope_type"], "theta": float(r["rope_theta"]),
                    "rot": int(rot)}
            if yarn:
                rule.update({k: float(r[k]) for k in _YARN_RULE_KEYS})
            rope[kind] = tuple(sorted(rule.items()))
        refused = {
            f"gating {d['gating']!r} (only 'per-head': one sigmoid gate a "
            "query head)": d["gating"] != "per-head",
            "gating_types other than per_head":
                set(lists["gating_types"]) != {"per_head"},
            f"layer_types other than {sorted(_LAGUNA_KINDS)}":
                not set(lists["layer_types"]) <= set(_LAGUNA_KINDS),
            "a stack without a full_attention layer (its region would "
            "hold no rows of the context's length)":
                "attention" not in kinds,
            f"rope_parameters of {bad_rules} (a rule a layer kind: "
            "rope_theta, rope_type default | yarn with its five keys, an "
            "even partial_rotary_factor x head_dim)": bool(bad_rules),
            "moe_router_logit_softcapping other than 0":
                d.get("moe_router_logit_softcapping", 0) != 0,
            "moe_apply_router_weight_on_input":
                bool(d.get("moe_apply_router_weight_on_input", False)),
            f"decoder_sparse_step {d.get('decoder_sparse_step', 1)} (only "
            "1)": d.get("decoder_sparse_step", 1) != 1,
            "norm_topk_prob false": not d["norm_topk_prob"],
            "attention_bias": bool(d.get("attention_bias", False)),
            "tie_word_embeddings": bool(d.get("tie_word_embeddings", False)),
            f"hidden_act {d.get('hidden_act')!r}":
                d.get("hidden_act", "silu") != "silu",
            "mlp_only_layers that are no leading run, or mlp_layer_types "
            "that disagree with them":
                dense != list(range(len(dense)))
                or lists["mlp_layer_types"] != (
                    ["dense"] * len(dense) + ["sparse"] * (L - len(dense))),
            "sliding_window (no one window of 2 positions or more)":
                not isinstance(window, int) or isinstance(window, bool)
                or window < 2,
            "num_attention_heads_per_layer: a count that is no multiple "
            "of num_key_value_heads": any(n < kvh or n % kvh for n in heads),
            f"expert_share: {held} held x {of} chips is not the published "
            f"{E} experts (a share is a whole-number split)":
                of < 1 or held * of != E,
            f"expert_share index {index} outside 0..{of - 1}":
                not 0 <= index < max(of, 1),
            "num_experts_per_tok above the published experts, or fewer "
            "than 2 of them": d["num_experts_per_tok"] > E or E < 2,
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(f"window + full GQA block: {bad} are values "
                             "this program does not build")
        hybrid = dict(
            layer_types=kinds, heads_by_layer=heads, gate="head",
            rope=tuple(sorted(rope.items())),
            window=int(window),
            window_rows=1 << (int(window) - 1).bit_length(),
            n_dense=len(dense),
            # the grouped sigmoid router with ONE group: every expert
            # stays in the running
            router="sigmoid_groups", n_group=1, topk_group=1,
            routed_scaling_factor=float(d["moe_routed_scaling_factor"]),
            num_local_experts=held,
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            intermediate_size=int(d["moe_intermediate_size"]),
            shared_intermediate_size=int(
                d["shared_expert_intermediate_size"]),
            published_experts=E, share_of=of, share_index=index,
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=hd ** -0.5, logits_scaling=1.0)
        if "window_attention" not in kinds:
            del hybrid["window"], hybrid["window_rows"]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=L,
            num_heads=int(d["num_attention_heads"]),
            num_kv_heads=kvh,
            head_dim=hd,
            rms_norm_eps=d["rms_norm_eps"],
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=False,
            model_type=d["model_type"],
            hybrid=tuple(sorted(hybrid.items())),
        )

    @classmethod
    def _from_hf_nemotron_h(cls, d: dict[str, Any]) -> "ModelConfig":
        """Layers of ONE part each, as a ``model_type: nemotron_h``
        config.json parameterises them: ``x = x + part(RMSNorm(x))`` with
        the part named by the layer's letter of ``hybrid_override_pattern``:
        ``M`` a Mamba-2 mixer (``mamba_num_heads`` heads of
        ``mamba_head_dim``, inner = their product whatever ``expand`` says,
        B and C in ``n_groups`` groups of ``ssm_state_size``, a gated
        RMSNorm over each group's channels, a convolution of
        ``conv_kernel`` with a bias), ``*`` a GQA mixer with no rotary
        (the family's attention is position-free: ``rope_theta`` and
        ``partial_rotary_factor`` are in the file and unread), ``E``
        ``n_routed_experts`` UNGATED experts ``W_d relu(x W_u)^2`` of
        ``moe_intermediate_size`` (top ``num_experts_per_tok`` of sigmoid
        scores with a selection-only bias, weights normalised x
        ``routed_scaling_factor``: the grouped sigmoid router with ONE
        group) plus one shared expert of
        ``moe_shared_expert_intermediate_size``, ``-`` one dense relu^2
        MLP of ``intermediate_size``. No multipliers; the head is tied or
        not by what the file says. Anything this program does not build is
        refused by name.

        The experts held HERE are ``n_routed_experts``; a file that holds
        a share states the deployment under ``expert_share``
        (``published_experts``, ``of``, ``index``) as the other expert
        stacks do, and the router keeps the published width."""
        name = "one-part hybrid block"
        missing = sorted(k for k in _NEMOTRON_H_KEYS if k not in d)
        if missing:
            raise ValueError(f"{name}: keys {missing} are missing from the "
                             "config")
        L, pattern = int(d["num_hidden_layers"]), str(
            d["hybrid_override_pattern"])
        kinds = tuple(_NEMOTRON_H_KINDS.get(t, t) for t in pattern)
        held = int(d["n_routed_experts"])
        share = d.get("expert_share") or {
            "published_experts": held, "of": 1, "index": 0}
        if set(share) != {"published_experts", "of", "index"}:
            raise ValueError(
                f"{name}: expert_share needs exactly published_experts, of "
                f"and index (it has {sorted(share)})")
        E, of, index = (int(share[k]) for k in
                        ("published_experts", "of", "index"))
        nh, groups = int(d["mamba_num_heads"]), int(d["n_groups"])
        heads, kvh = (int(d[k]) for k in ("num_attention_heads",
                                          "num_key_value_heads"))
        hd = int(d["head_dim"])
        refused = {
            f"hybrid_override_pattern letters other than "
            f"{sorted(_NEMOTRON_H_KINDS)}":
                not set(pattern) <= set(_NEMOTRON_H_KINDS),
            "hybrid_override_pattern whose length is not "
            "num_hidden_layers": len(pattern) != L,
            "a pattern without an attention layer (its region would hold "
            "no rows of the context's length)": "*" not in pattern,
            f"mlp_hidden_act {d['mlp_hidden_act']!r} (only 'relu2')":
                d["mlp_hidden_act"] != "relu2",
            f"mamba_hidden_act {d['mamba_hidden_act']!r} (only 'silu')":
                d["mamba_hidden_act"] != "silu",
            **{f"{flag} true": bool(d.get(flag, False)) for flag in (
                "attention_bias", "mlp_bias", "use_bias", "mamba_proj_bias")},
            "use_conv_bias false": not d["use_conv_bias"],
            "residual_in_fp32": bool(d.get("residual_in_fp32", False)),
            f"n_group {d['n_group']} / topk_group {d['topk_group']} (only "
            "one group of experts, kept)":
                d["n_group"] != 1 or d["topk_group"] != 1,
            "norm_topk_prob false": not d["norm_topk_prob"],
            f"n_shared_experts {d['n_shared_experts']} (only 1)":
                d["n_shared_experts"] != 1,
            f"n_groups {groups} (no whole number of the {nh} Mamba heads "
            "reads one group's B and C)": groups < 1 or nh % groups != 0,
            "conv_kernel < 2": d["conv_kernel"] < 2,
            "num_attention_heads that is no multiple of "
            "num_key_value_heads": kvh < 1 or heads % kvh != 0,
            f"sliding_window {d.get('sliding_window')!r} (no window is "
            "built on this block's attention)":
                d.get("sliding_window") is not None,
            f"norm_eps {d.get('norm_eps')!r} beside layer_norm_epsilon "
            f"{d['layer_norm_epsilon']!r} (one epsilon)":
                d.get("norm_eps", d["layer_norm_epsilon"])
                != d["layer_norm_epsilon"],
            f"expert_share: {held} held x {of} chips is not the published "
            f"{E} experts (a share is a whole-number split)":
                of < 1 or held * of != E,
            f"expert_share index {index} outside 0..{of - 1}":
                not 0 <= index < max(of, 1),
            "num_experts_per_tok above the published experts, or fewer "
            "than 2 of them": d["num_experts_per_tok"] > E or E < 2,
        }
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(f"{name}: {bad} are values this program does "
                             "not build")
        hybrid = dict(
            layer_types=kinds, one_part=True,
            mamba_n_heads=nh, mamba_d_head=int(d["mamba_head_dim"]),
            mamba_n_groups=groups, mamba_d_state=int(d["ssm_state_size"]),
            mamba_d_conv=int(d["conv_kernel"]),
            mamba_chunk_size=int(d["chunk_size"]),
            # the grouped sigmoid router with ONE group: every expert
            # stays in the running
            router="sigmoid_groups", n_group=1, topk_group=1,
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            expert_act="relu2",
            num_local_experts=held,
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            intermediate_size=int(d["moe_intermediate_size"]),
            shared_intermediate_size=int(
                d["moe_shared_expert_intermediate_size"]),
            published_experts=E, share_of=of, share_index=index,
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=hd ** -0.5, logits_scaling=1.0)
        if "E" not in pattern:   # nothing routes
            for key in ("router", "n_group", "topk_group",
                        "routed_scaling_factor", "num_local_experts",
                        "num_experts_per_tok", "shared_intermediate_size",
                        "published_experts", "share_of", "share_index"):
                del hybrid[key]
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=L,
            num_heads=heads,
            num_kv_heads=kvh,
            head_dim=hd,
            rms_norm_eps=d["layer_norm_epsilon"],
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=bool(d["tie_word_embeddings"]),
            model_type=d["model_type"],
            hybrid=tuple(sorted(hybrid.items())),
        )

    @classmethod
    def _from_hf_kda_latent(cls, d: dict[str, Any]) -> "ModelConfig":
        """Delta-rule linear attention (KDA) layers with a per-channel
        gate and short convolutions, one latent (MLA) layer closing every
        ``layer_group_size``, ``first_k_dense_replace`` dense layers and
        then sigmoid-routed experts picked within groups (top
        ``topk_group`` of ``n_group`` groups by the sum of their two best
        scores + bias) with one shared expert. Every key the equations
        read must be there with a value this program builds; anything
        else is refused by name.

        The experts held HERE are ``num_local_experts`` (default: all
        ``num_experts``); a file that holds a share states the deployment
        under ``expert_share`` as the state-space hybrid does
        (``published_experts``, ``of``, ``index``), and ``of`` has to
        divide ``n_group`` or be a multiple of it, so that a share is
        whole groups or a whole part of one."""
        missing = sorted(k for k in _KDA_LATENT_KEYS if k not in d)
        if missing:
            raise ValueError("delta-rule + latent attention block: keys "
                             f"{missing} are missing from the config")
        E = int(d["num_experts"])
        held = int(d.get("num_local_experts", E))
        share = d.get("expert_share") or {
            "published_experts": held, "of": 1, "index": 0}
        if set(share) != {"published_experts", "of", "index"}:
            raise ValueError(
                "delta-rule + latent attention block: expert_share needs "
                f"exactly published_experts, of and index (it has "
                f"{sorted(share)})")
        of, index = int(share["of"]), int(share["index"])
        L, period = int(d["num_hidden_layers"]), int(d["layer_group_size"])
        heads, hd = int(d["num_attention_heads"]), int(d["head_dim"])
        n_group, rope = int(d["n_group"]), int(d["qk_rope_head_dim"])
        clamps = [d["expert_swiglu_limit_list"],
                  d["share_expert_swiglu_limit_list"]]
        refused = {
            f"{k} {d[k]!r} (only {want!r})": d[k] != want
            for k, want in _KDA_LATENT_KEYS.items() if want is not None}
        refused.update({
            "q_lora_rank (the latent layers' query has no low-rank "
            "factor here)": d["q_lora_rank"] is not None,
            "num_kv_heads_for_linear_attn other than 0 or "
            "num_attention_heads": d["num_kv_heads_for_linear_attn"]
                not in (0, heads),
            "a non-zero entry of expert_swiglu_limit_list / "
            "share_expert_swiglu_limit_list (the clamp's form is not "
            "published: none is built)":
                any(x != 0 for lst in clamps for x in lst),
            "a clamp list whose length is not num_hidden_layers":
                any(len(lst) != L for lst in clamps),
            "layer_group_size < 2, or above num_hidden_layers (a stack "
            "without a latent layer keeps no rows)": not 2 <= period <= L,
            "short_conv_kernel_size < 2": d["short_conv_kernel_size"] < 2,
            "kda_lower_bound not below 0": not d["kda_lower_bound"] < 0,
            "rotary_dim != qk_rope_head_dim (the latent layers rotate "
            "their rope part, the KDA layers nothing)":
                d["rotary_dim"] != rope
                or d["partial_rotary_factor"] * hd != d["rotary_dim"],
            "qk_nope_head_dim / v_head_dim != head_dim":
                (d["qk_nope_head_dim"], d["v_head_dim"]) != (hd, hd),
            "rope_scaling": d.get("rope_scaling") is not None,
            "rope_interleave false": not d.get("rope_interleave", True),
            "tie_word_embeddings": bool(d.get("tie_word_embeddings")),
            f"hidden_act {d.get('hidden_act')!r}":
                d.get("hidden_act", "silu") != "silu",
            "use_bias / use_qkv_bias":
                bool(d.get("use_bias")) or bool(d.get("use_qkv_bias")),
            "num_shared_experts other than 1":
                d.get("num_shared_experts", 1) != 1,
            f"topk_method {d.get('topk_method')!r}":
                d.get("topk_method", "noaux_tc") != "noaux_tc",
            "scoring_func other than score_function":
                d.get("scoring_func", "sigmoid") != "sigmoid",
            f"n_group {n_group} does not divide num_experts {E}, or "
            "topk_group outside 1..n_group":
                n_group < 1 or E % max(n_group, 1) != 0
                or not 1 <= d["topk_group"] <= n_group,
            "num_experts_per_tok above what topk_group groups hold":
                d["num_experts_per_tok"]
                > d["topk_group"] * (E // max(n_group, 1)),
            "a group of fewer than 2 experts (its score is the sum of "
            "its two best)": E // max(n_group, 1) < 2,
            f"expert_share: {held} held x {of} chips is not the "
            f"published {E} experts, or published_experts is not "
            "num_experts": of < 1 or held * of != E
                or int(share["published_experts"]) != E,
            f"expert_share: of {of} neither divides n_group {n_group} "
            "nor is a multiple of it":
                of >= 1 and n_group % of != 0 and of % n_group != 0,
            f"expert_share index {index} outside 0..{of - 1}":
                not 0 <= index < max(of, 1),
            "first_k_dense_replace above num_hidden_layers":
                not 0 <= d["first_k_dense_replace"] <= L,
        })
        bad = sorted(k for k, v in refused.items() if v)
        if bad:
            raise ValueError(
                f"delta-rule + latent attention block: {bad} are values "
                "this program does not build")
        kinds = tuple("latent_attention" if (l + 1) % period == 0 else "kda"
                      for l in range(L))
        hybrid = dict(
            layer_types=kinds, kda_heads=heads, kda_head_dim=hd,
            kda_conv=int(d["short_conv_kernel_size"]),
            kda_lower_bound=float(d["kda_lower_bound"]),
            n_dense=int(d["first_k_dense_replace"]),
            router="sigmoid_groups", n_group=n_group,
            topk_group=int(d["topk_group"]),
            routed_scaling_factor=float(d["routed_scaling_factor"]),
            num_local_experts=held,
            num_experts_per_tok=int(d["num_experts_per_tok"]),
            intermediate_size=int(d["moe_intermediate_size"]),
            shared_intermediate_size=int(
                d["moe_shared_expert_intermediate_size"]),
            published_experts=E, share_of=of, share_index=index,
            embedding_multiplier=1.0, residual_multiplier=1.0,
            logits_scaling=1.0)
        mla = {k: d[k] for k in _MLA_KEYS}
        return cls(
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=L,
            num_heads=heads,
            # the latent layers' cached row, as the latent block states it
            num_kv_heads=1,
            head_dim=d["kv_lora_rank"] + rope,
            rope_theta=float(d["rope_theta"]),
            rms_norm_eps=d["rms_norm_eps"],
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            tie_word_embeddings=False,
            model_type=d.get("model_type", "ling3_flash"),
            mla=tuple(sorted(mla.items())),
            hybrid=tuple(sorted(hybrid.items())),
        )

    @classmethod
    def tiny_kda_latent(cls, **kw) -> "ModelConfig":
        """Toy delta-rule + latent stack for CPU tests: one dense layer,
        two periods of (kda, kda, latent), 16 experts in 4 groups of which
        2 are kept, top 4, share 0 of 4 holds one group."""
        d = dict(_TINY_KDA_LATENT)
        d.update(kw)
        return cls.from_hf_dict(d)

    @classmethod
    def tiny_jamba(cls, **kw) -> "ModelConfig":
        """Toy Mamba-1 + attention stack for CPU tests: two periods of
        (mamba1, attention, mamba1), four query heads on ONE K/V head, an
        inner width of 128 with 16 state columns and a dt rank of 8."""
        d = dict(_TINY_JAMBA)
        d.update(kw)
        return cls.from_hf_dict(d)

    @classmethod
    def tiny_phi4flash(cls, **kw) -> "ModelConfig":
        """Toy decoder-hybrid-decoder stack for CPU tests: eight layers
        (Mamba-1, window, Mamba-1, window, Mamba-1, full, gated memory
        unit, cross), four query heads on two K/V heads of 16 (one K/V
        pair of 32), a window of 8."""
        d = dict(_TINY_PHI4FLASH)
        d.update(kw)
        return cls.from_hf_dict(d)

    @classmethod
    def tiny_laguna(cls, **kw) -> "ModelConfig":
        """Toy window + full GQA stack for CPU tests: six layers (full,
        window x 3, full, window), 18 / 12 query heads (groups of 9 and 6)
        over two K/V heads of 16, a window of 8, YaRN over half the head
        on the full layers, one dense layer and then 16 experts top 4 of
        which share 0 of 4 holds 4."""
        d = dict(_TINY_LAGUNA)
        d.update(kw)
        return cls.from_hf_dict(d)

    @classmethod
    def tiny_nemotron_h(cls, **kw) -> "ModelConfig":
        """Toy one-part hybrid stack for CPU tests: thirteen layers
        ``MEMEM*EMEMEM*`` (six Mamba-2 mixers of 8 heads x 12 in 2 B/C
        groups, inner 96 against hidden 64; five expert layers of 32
        ungated relu^2 experts top 6 of which share 0 of 8 holds 4, width
        40, plus a shared one of 80; two NoPE GQA layers of 8 heads over
        2), a scan chunk of 8, the head untied."""
        d = dict(_TINY_NEMOTRON_H)
        d.update(kw)
        return cls.from_hf_dict(d)

    @classmethod
    def tiny_linear_sparse(cls, **kw) -> "ModelConfig":
        """Toy linear + sparse attention stack for CPU tests: six layers
        of both kinds with two sparse ones adjacent, blocks of 8, top 6,
        a switch to the selection at position 64."""
        d = dict(_TINY_LINEAR_SPARSE)
        d.update(kw)
        return cls.from_hf_dict(d)

    @classmethod
    def tiny_ssm_moe(cls, **kw) -> "ModelConfig":
        """Toy state-space hybrid for CPU tests: two periods of (mamba,
        mamba, attention), 8 experts top 2 of which share 0 of 2 holds 4,
        a scan chunk of 8."""
        d = dict(_TINY_SSM_MOE)
        d.update(kw)
        return cls.from_hf_dict(d)

    @classmethod
    def tiny_mla_moe(cls, **kw) -> "ModelConfig":
        """Toy latent-attention + routed-expert model for CPU tests:
        1 dense + 2 expert layers, 8 experts top 2, one shared."""
        d = dict(_TINY_MLA_MOE)
        d.update(kw)
        return cls.from_hf_dict(d)

    @classmethod
    def tiny_mla_moe_mhc(cls, **kw) -> "ModelConfig":
        """The toy block with the four-stream residual and YaRN (factor 4
        over 64 positions, so that a short test prompt crosses it)."""
        d = dict(_TINY_MHC)
        d.update(kw)
        return cls.tiny_mla_moe(**d)

    @classmethod
    def from_pretrained(cls, model_dir: str) -> "ModelConfig":
        with open(os.path.join(model_dir, "config.json")) as f:
            return cls.from_hf_dict(json.load(f))

    # ---- canned configs for tests / benchmarks (shapes only; weights are
    # random unless load_hf_params is used) ----

    @classmethod
    def tiny(cls, **kw) -> "ModelConfig":
        """4-layer toy model for CPU tests."""
        base = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=4,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            max_position_embeddings=512,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def tiny_looped(cls, **kw) -> "ModelConfig":
        """The toy model as a looped stack (the ``ouro`` block's shape):
        3 weight layers run 4 times, 12 cache planes, sandwich norms, the
        exit gate."""
        base = dict(num_layers=3, loop_steps=4, sandwich_norms=True,
                    exit_gate=True, model_type="ouro")
        base.update(kw)
        return cls.tiny(**base)

    @classmethod
    def tiny_wide(cls, **kw) -> "ModelConfig":
        """Toy model with 4 kv heads — shardable to tp=4 (multi-host CPU
        tests / the cross-host CLI path)."""
        base = dict(num_kv_heads=4, num_heads=8)
        base.update(kw)
        return cls.tiny(**base)

    @classmethod
    def tiny_moe(cls, **kw) -> "ModelConfig":
        """Toy MoE model (8 experts, top-2, dropless) for CPU tests / the
        dryrun — the served stand-in for the reference's wide-EP DeepSeek
        shape. capacity_factor 0 = dropless (see moe.MoEConfig.capacity:
        capacity drops break prefix-cache reproducibility)."""
        base = dict(
            moe=(("capacity_factor", 0.0), ("num_experts", 8),
                 ("top_k", 2)),
        )
        base.update(kw)
        return cls.tiny(**base)

    @classmethod
    def llama3_1b(cls, **kw) -> "ModelConfig":
        """Llama-3.2-1B shapes (fits one v5e chip in bf16 with room for KV)."""
        base = dict(
            vocab_size=128256,
            hidden_size=2048,
            intermediate_size=8192,
            num_layers=16,
            num_heads=32,
            num_kv_heads=8,
            head_dim=64,
            rope_theta=500000.0,
            max_position_embeddings=131072,
            tie_word_embeddings=True,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama3_8b(cls, **kw) -> "ModelConfig":
        """Llama-3.1-8B / DeepSeek-R1-Distill-Llama-8B shapes (the
        reference's benchmark model)."""
        base = dict(
            vocab_size=128256,
            hidden_size=4096,
            intermediate_size=14336,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=500000.0,
            max_position_embeddings=131072,
        )
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama3_8b_int8(cls) -> "ModelConfig":
        """The 8B model on one 16 GB v5e: w8a16 int8 weights (~8 GB) —
        bf16 cannot fit."""
        return cls.llama3_8b(quant="int8")

    @classmethod
    def llama3_1b_int8(cls) -> "ModelConfig":
        return cls.llama3_1b(quant="int8")

    @classmethod
    def llama3_70b(cls) -> "ModelConfig":
        return cls(
            vocab_size=128256,
            hidden_size=8192,
            intermediate_size=28672,
            num_layers=80,
            num_heads=64,
            num_kv_heads=8,
            head_dim=128,
            rope_theta=500000.0,
            max_position_embeddings=131072,
        )

    def num_params(self) -> int:
        """Approximate parameter count (for memory planning). A looped
        stack's weights are counted ONCE: they are held once."""
        h, i, v = self.hidden_size, self.intermediate_size, self.vocab_size
        per_layer = (
            h * self.q_dim + 2 * h * self.kv_dim + self.q_dim * h  # attn
            + 3 * h * i  # mlp
            + (4 if self.sandwich_norms else 2) * h  # norms
        )
        embed = v * h * (1 if self.tie_word_embeddings else 2)
        gate = h + 1 if self.exit_gate else 0
        return self.num_layers * per_layer + embed + h + gate
