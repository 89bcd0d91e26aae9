"""Model config files that ``fetch-models --synthesize-lm`` installs.

``deepseek_v2_ep8`` is DeepSeek-V2's published config
(https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json)
with every width as published, cut to what ONE chip of an 8-way
expert-parallel deployment holds of six layers: the router keeps its 160
outputs, 8 groups, top-3 groups and top-6 experts; the chip holds routing
group ``held_group`` (20 experts), an eighth of the vocabulary, the
leading dense layer and five expert layers. ``deepseek_v2_tiny`` has the
same structure at a size a CPU test runs.

``jamba2_3b`` is AI21-Jamba2-3B's published config
(https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json),
whole: all 28 layers (26 Mamba, attention at 7 and 21) and the whole
vocabulary fit one chip, so nothing is cut but the weights (seeded).
``jamba_tiny`` has the same structure (Mamba runs before, between and
after two attention layers) at a size a CPU test runs.

``kimi_linear_ep4`` is Kimi-Linear-48B-A3B-Instruct's published config
(https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json)
with every width as published, cut to what ONE chip of four that share
each layer holds of the first eight layers (two whole periods of three KDA
layers and one MLA layer, the first with the dense feed-forward): the
router keeps its 256 outputs and 8 a token; the chip holds experts
``[held_lo, held_lo + experts_held)`` (64) and a quarter of the
vocabulary. ``kda_gate_rank`` is the one size the config does not carry
(the family's head_dim). ``kimi_linear_tiny`` has the same structure (KDA,
KDA, MLA, KDA, the first dense; 16 experts, 4 held, 4 a token) at a size
a CPU test runs.

``lfm2_moe_ep2`` is LFM2-8B-A1B's published config
(https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json) with
every width as published, cut to what ONE chip of two that share each
layer holds of ALL 24 layers (18 gated short convolutions, grouped-query
attention at 2, 6, 10, 14, 18 and 21, the first two layers dense): the
router keeps its 32 outputs and 4 a token; the chip holds experts
``[held_lo, held_lo + experts_held)`` (16) and half the vocabulary.
``qk_norm_gain`` is the mean of the query and key head norms' seeded gains
(at 1 a seeded softmax over 2.4 k rows is flat and nothing sees which rows
it weighs). ``lfm2_moe_tiny`` has the same structure (both dense layers,
an expert layer behind a convolution and behind an attention layer,
attention at 2, 6 and 8: no regular period; 8 experts, 4 held, 2 a token,
2 key-value heads under 4 query heads) at a size a CPU test runs.

``laguna_xs2_pp8`` is Laguna-XS.2's published config
(https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json) with
every width as published, cut to what the FIRST of eight pipeline stages
holds, every layer of it whole: layers 0-4 (full attention with 48 query
heads and the dense feed-forward; three window layers of 64 query heads
over the experts; full attention over the experts), the embedding, and the
final norm and head so that the stage yields logits. All 256 experts of a
layer and the whole vocabulary are here (``experts_held`` 256). Both
rotations are the published tables (``rope_parameters``). ``qk_norm_gain``
is the mean of the head norms' seeded gains, as LFM2's. ``laguna_tiny`` has
the same structure (dense + full, three window layers, full + experts, one
more window layer; 6 and 8 query heads over 2 key-value heads of 16; a
window of 8; half of a head rotated under a YaRN table whose ramp lies
inside it on the full layers; 8 experts, all held, 2 a token, one shared)
at a size a CPU test runs.

``nemotron3_super_ep8`` is NVIDIA-Nemotron-3-Super-120B-A12B's published
config
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json)
with every width as published, cut to what ONE chip of the eight that share
each block holds of the FIRST of eight pipeline stages: blocks 0-10
(``MEMEMEM*EME``: five Mamba-2 mixers, five latent expert layers, one
attention block, the published 5 : 5 : 1), experts ``[held_lo, held_lo +
experts_held)`` (64 of 512) of every expert layer, an eighth of the
vocabulary, and the final norm and head so that the stage yields logits; the
router keeps its 512 outputs and 22 a token. The multi-token-prediction head
(``num_nextn_predict_layers``) is not held. ``attn_qk_init_scale`` widens
the seeded ``q`` and ``k`` of the one attention block (as
``mla_qk_init_scale``); ``mamba_a_init_max`` and ``mamba_dt_init_max`` are
the upper ends of the seeded decays' and steps' draws (absent: Mamba-2's
own, 16 and ``time_step_max``). ``nemotron_h_tiny`` has the same structure
(``MEM*EME``; 4 Mamba-2 heads of 8 over a state of 16 in 2 groups; 4 query
heads over 2 key-value heads; 16 experts, 2 held, 6 a token, a latent of 32)
at a size a CPU test runs.

``brumby_14b_pp8`` is Brumby-14B-Base's published config
(https://huggingface.co/manifestai/Brumby-14B-Base/blob/main/config.json)
with every width as published, cut to what the FIRST of eight pipeline
stages holds, every layer of it whole: layers 0-4 (all power retention: the
model has one kind of layer), the embedding, and the final norm and head so
that the stage yields logits; the whole vocabulary is here. The config
carries neither the degree (2), the gate nor the normaliser: they are the
family's (models/lm/brumby.py). ``gate_memory_min`` / ``gate_memory_max``
are the ends of the seeded decays' draw, in tokens remembered (a state that
forgets in tens of tokens tells a bfloat16 state from a float32 one no
more: Nemotron's lesson). ``brumby_tiny`` has the same structure (3 layers,
4 query heads over 2 key-value heads of 16: a state of 136 x 16 a head) at
a size a CPU test runs.

Which module serves a preset is its ``model_type`` (models/lm
``FAMILIES``); the latent attention (models/lm/mla.py: ``deepseek_v2``,
``kimi_linear``), the plain attention (models/lm/attention.py: ``jamba``,
``lfm2_moe``, ``laguna``, ``nemotron_h``) and the expert layer
(models/lm/experts.py: ``deepseek_v2``, ``kimi_linear``, ``lfm2_moe``,
``laguna``, ``nemotron_h``) are each shared and read what differs from
these keys.
"""

from __future__ import annotations

DEEPSEEK_V2_PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 5120, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v2", "moe_intermediate_size": 1536,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 160,
    "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 128, "num_experts_per_tok": 6,
    "num_hidden_layers": 60, "num_key_value_heads": 128,
    "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 16,
    "scoring_func": "softmax", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 3,
    "topk_method": "group_limited_greedy", "v_head_dim": 128,
    "vocab_size": 102400,
}

JAMBA2_3B_PUBLISHED = {
    "attn_layer_offset": 7, "attn_layer_period": 14,
    "expert_layer_offset": 1, "expert_layer_period": 2,
    "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
    "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
    "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "model_type": "jamba",
    "num_attention_heads": 20, "num_experts": 1, "num_experts_per_tok": 1,
    "num_hidden_layers": 28, "num_key_value_heads": 1,
    "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
    "tie_word_embeddings": True, "use_mamba_kernels": True,
    "vocab_size": 65536,
}

KIMI_LINEAR_PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                       21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840,
}

LFM2_8B_A1B_PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 7168,
    "layer_types": [
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "conv", "full_attention", "conv",
        "conv", "conv", "full_attention", "conv", "conv", "conv",
        "full_attention", "conv", "conv", "full_attention", "conv", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1792, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 24, "num_key_value_heads": 8,
    "rope_theta": 1000000, "routed_scaling_factor": 1,
    "use_expert_bias": True, "vocab_size": 65536,
}

_LAGUNA_PERIOD = ["full_attention"] + 3 * ["sliding_attention"]

LAGUNA_XS2_PUBLISHED = {
    "attention_bias": False, "gating": True, "head_dim": 128,
    "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": 10 * _LAGUNA_PERIOD,
    "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + 39 * ["sparse"],
    "model_type": "laguna", "moe_apply_router_weight_on_input": False,
    "moe_intermediate_size": 512, "moe_routed_scaling_factor": 2.5,
    "num_attention_heads": 48,
    "num_attention_heads_per_layer": 10 * [48, 64, 64, 64],
    "num_experts": 256, "num_experts_per_tok": 8, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "partial_rotary_factor": 0.5,
    "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "shared_expert_intermediate_size": 512, "sliding_window": 512,
    "tie_word_embeddings": False, "vocab_size": 100352,
}

NEMOTRON3_SUPER_PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
    "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": (
        "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
        "EMEMEMEMEM*EMEMEMEM*EMEMEMEME"),
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False,
    "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
    "moe_shared_expert_overlap": False,
    "mtp_hybrid_override_pattern": "*E", "n_group": 1, "n_groups": 8,
    "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rescale_prenorm_residual": True, "residual_in_fp32": False,
    "rope_theta": 10000, "routed_scaling_factor": 5,
    "sliding_window": None, "ssm_state_size": 128,
    "tie_word_embeddings": False, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072,
}

BRUMBY_14B_PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 5120, "intermediate_size": 17408,
    "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}

PRESETS = {
    "brumby_14b_pp8": {
        **BRUMBY_14B_PUBLISHED,
        # the cut: the first pipeline stage's five layers, each whole
        "num_hidden_layers": 5,
        "vocab_held": 151936,
        "weights_seed": 20261005,
        "initializer_range": 0.02,
        # assumed: the gates' biases drawn so that a head's decay remembers
        # 600-1800 tokens, a quarter to three quarters of the product's
        # context, as a model trained for long contexts does: at 150-450
        # a state kept in bfloat16 reads only twice what bfloat16
        # activations do (PERF.md section 6, PR 57)
        "gate_memory_min": 600,
        "gate_memory_max": 1800,
    },
    "brumby_tiny": {
        **BRUMBY_14B_PUBLISHED,
        "hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 3, "max_window_layers": 3, "vocab_size": 512,
        "rope_theta": 100,
        "vocab_held": 512,
        "weights_seed": 29,
        # as deepseek_v2_tiny: 0.02 at width 64 leaves every score flat
        "initializer_range": 0.15,
        "gate_memory_min": 8,
        "gate_memory_max": 40,
    },
    "nemotron3_super_ep8": {
        **NEMOTRON3_SUPER_PUBLISHED,
        # the cut: the first stage's blocks, the chip's share of the
        # experts and vocabulary
        "num_hidden_layers": 11,
        "experts_held": 64,
        "held_lo": 0,
        "vocab_held": 16384,
        "weights_seed": 20260311,
        "initializer_range": 0.02,
        # assumed: the attention block's q and k drawn this much wider, for
        # a softmax as peaked as a trained one
        "attn_qk_init_scale": 1.5,
        # assumed: the heads' decays and steps drawn from the LOW end of
        # Mamba-2's initialisation (A in [1, 16], steps in [time_step_min,
        # time_step_max]), for a state that remembers across a prompt as a
        # trained one's: at the whole ranges it forgets in ~25 tokens and a
        # state kept in bfloat16 reads as the float32 one
        "mamba_a_init_max": 1.5,
        "mamba_dt_init_max": 0.002,
    },
    "nemotron_h_tiny": {
        **NEMOTRON3_SUPER_PUBLISHED,
        "hidden_size": 64, "hybrid_override_pattern": "MEM*EME",
        "num_hidden_layers": 7,
        "mamba_num_heads": 4, "mamba_head_dim": 8, "ssm_state_size": 16,
        "n_groups": 2, "head_dim": 16, "num_attention_heads": 4,
        "num_key_value_heads": 2,
        "moe_intermediate_size": 48, "intermediate_size": 48,
        "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
        "n_routed_experts": 16, "num_experts_per_tok": 6,
        "vocab_size": 512,
        "experts_held": 2,
        "held_lo": 0,
        "vocab_held": 128,
        "weights_seed": 23,
        # as deepseek_v2_tiny: 0.02 at width 64 leaves every score flat
        "initializer_range": 0.15,
        "attn_qk_init_scale": 1.0,
    },
    "laguna_xs2_pp8": {
        **LAGUNA_XS2_PUBLISHED,
        # the cut: the first pipeline stage's five layers, each whole
        "num_hidden_layers": 5,
        "experts_held": 256,
        "held_lo": 0,
        "vocab_held": 100352,
        "weights_seed": 20260501,
        "initializer_range": 0.02,
        # assumed: the mean of the q_norm / k_norm gains, for a softmax as
        # peaked as a trained one (a score's deviation is the product of
        # the two gains): at 1 neither a missing window nor a missing
        # rotation shows in the logits (PERF.md section 6, PR 42)
        "qk_norm_gain": 1.75,
    },
    "laguna_tiny": {
        **LAGUNA_XS2_PUBLISHED,
        # dense + full | window x 3 | full + experts | window
        "hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "num_attention_heads": 6, "num_key_value_heads": 2,
        "num_attention_heads_per_layer": [6, 8, 8, 8, 6, 8],
        "layer_types": _LAGUNA_PERIOD + _LAGUNA_PERIOD[:2],
        "mlp_layer_types": ["dense"] + 5 * ["sparse"],
        "num_hidden_layers": 6, "num_experts": 8, "num_experts_per_tok": 2,
        "sliding_window": 8, "vocab_size": 512,
        "rope_parameters": {
            # of 8 rotated values' 4 frequencies: lo 1, hi 3
            "full_attention": {
                "rope_theta": 100, "rope_type": "yarn", "factor": 8,
                "original_max_position_embeddings": 32, "beta_slow": 0.3,
                "beta_fast": 0.9, "attention_factor": 1.2,
                "partial_rotary_factor": 0.5},
            "sliding_attention": {
                "rope_type": "default", "rope_theta": 100,
                "partial_rotary_factor": 1},
            "original_max_position_embeddings": 32},
        "experts_held": 8,
        "held_lo": 0,
        "vocab_held": 512,
        "weights_seed": 19,
        # as deepseek_v2_tiny: 0.02 at width 64 leaves every score flat
        "initializer_range": 0.15,
        "qk_norm_gain": 1.75,
    },
    "lfm2_moe_ep2": {
        **LFM2_8B_A1B_PUBLISHED,
        # the cut: the chip's share of the experts and of the vocabulary;
        # every one of the 24 layers is here
        "experts_held": 16,
        "held_lo": 0,
        "vocab_held": 32768,
        "weights_seed": 20251007,
        "initializer_range": 0.02,
        # assumed: the mean of the q_layernorm / k_layernorm gains, for a
        # softmax as peaked as a trained one (a score's deviation is the
        # product of the two gains)
        "qk_norm_gain": 1.75,
    },
    "lfm2_moe_tiny": {
        **LFM2_8B_A1B_PUBLISHED,
        "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "num_attention_heads": 4,
        "num_key_value_heads": 2, "num_experts": 8,
        "num_experts_per_tok": 2, "num_hidden_layers": 10,
        "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                        "conv", "full_attention", "conv", "full_attention",
                        "conv"],
        "vocab_size": 512,
        "experts_held": 4,
        "held_lo": 0,
        "vocab_held": 128,
        "weights_seed": 17,
        # as deepseek_v2_tiny: 0.02 at width 64 leaves every score flat
        "initializer_range": 0.15,
        "qk_norm_gain": 1.75,
    },
    "kimi_linear_ep4": {
        **KIMI_LINEAR_PUBLISHED,
        # the cut: depth, the chip's share of the experts and vocabulary
        "num_hidden_layers": 8,
        "experts_held": 64,
        "held_lo": 0,
        "vocab_held": 40960,
        # assumed: the rank of the two gates' low-rank projections
        "kda_gate_rank": 128,
        "weights_seed": 20251030,
        "initializer_range": 0.02,
        # assumed: the MLA layers' q and kv_a drawn this much wider, for a
        # softmax as peaked as a trained one (at 0.02 alone it is flat
        # over 2.4 k rows and nothing downstream sees what it weighs)
        "mla_qk_init_scale": 2.5,
    },
    "kimi_linear_tiny": {
        **KIMI_LINEAR_PUBLISHED,
        # KDA, KDA, MLA, KDA; the first with the dense feed-forward
        "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "kv_lora_rank": 32,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "linear_attn_config": {
            "full_attn_layers": [3], "head_dim": 16,
            "kda_layers": [1, 2, 4], "num_heads": 2,
            "short_conv_kernel_size": 4},
        "num_experts": 16, "num_experts_per_token": 4,
        "num_hidden_layers": 4, "vocab_size": 512,
        "experts_held": 4,
        "held_lo": 0,
        "vocab_held": 128,
        "kda_gate_rank": 8,
        "weights_seed": 13,
        # as deepseek_v2_tiny: 0.02 at width 64 leaves every score flat
        "initializer_range": 0.15,
        "mla_qk_init_scale": 1.0,
    },
    "jamba2_3b": {
        **JAMBA2_3B_PUBLISHED,
        "vocab_held": 65536,
        "weights_seed": 20251008,
        "initializer_range": 0.02,
    },
    "jamba_tiny": {
        **JAMBA2_3B_PUBLISHED,
        # Mamba layers 0 | 2, 3 | 5 around attention layers 1 and 4
        "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_hidden_layers": 6,
        "attn_layer_period": 3, "attn_layer_offset": 1,
        "mamba_dt_rank": 8, "vocab_size": 512,
        "vocab_held": 128,
        "weights_seed": 11,
        # as deepseek_v2_tiny: 0.02 at width 64 leaves every score flat
        "initializer_range": 0.15,
    },
    "deepseek_v2_ep8": {
        **DEEPSEEK_V2_PUBLISHED,
        # the cut: depth, the chip's share of the experts and vocabulary
        "num_hidden_layers": 6,
        "held_group": 0,
        "vocab_held": 12800,
        "weights_seed": 20240507,
        "initializer_range": 0.02,
    },
    "deepseek_v2_tiny": {
        **DEEPSEEK_V2_PUBLISHED,
        "hidden_size": 64, "intermediate_size": 96,
        "moe_intermediate_size": 32, "kv_lora_rank": 32,
        "q_lora_rank": 48, "num_attention_heads": 4,
        "num_key_value_heads": 4, "qk_nope_head_dim": 16,
        "qk_rope_head_dim": 8, "v_head_dim": 16,
        "n_group": 4, "topk_group": 2, "n_routed_experts": 16,
        "num_experts_per_tok": 3, "num_hidden_layers": 3,
        "vocab_size": 512,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 16,
                         "type": "yarn"},
        "held_group": 0,
        "vocab_held": 128,
        "weights_seed": 7,
        # 0.02 at width 64 leaves every score flat; the tests need terms
        # that matter
        "initializer_range": 0.15,
    },
}
