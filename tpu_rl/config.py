"""Typed configuration.

Replaces the reference's import-time module-global ``SimpleNamespace`` config
(``/root/reference/utils/utils.py:24-44`` loading ``utils/parameters.json`` and
``utils/machines.json``) with explicit dataclasses, loadable from the same JSON
shapes, plus validation. Runtime-derived fields (obs/action spaces) live here too
instead of being mutated onto the global namespace (``/root/reference/main.py:66-95``).
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any


# Single source of truth for storage semantics per algorithm (reference
# switcher ``main.py:310-321``). The algo registry asserts consistency with
# its specs; kept here so the host-only data plane never imports jax.
OFF_POLICY_ALGOS = frozenset({"SAC", "SAC-Continuous"})

# Config fields that shape the train-state pytree or the meaning of its
# numbers — the resume compatibility surface hashed by
# ``tpu_rl.checkpoint.resume_fingerprint``. Runtime knobs (ports,
# supervision, telemetry, chaos, throttles) are deliberately excluded:
# changing them must never strand a checkpoint. Lives here (not in
# checkpoint.py, which imports jax) so ``Config.validate`` can enforce the
# population plane's searchable-field rule — a pop-spec may only mutate
# fields OUTSIDE this set, because an exploit step copies checkpoints
# across members and a fingerprint-changing mutation would strand them.
FINGERPRINT_FIELDS = (
    "env",
    "algo",
    "model",
    "hidden_size",
    "n_heads",
    "n_layers",
    "seq_len",
    "attention_impl",
    "arch",
    "obs_shape",
    "action_space",
    "is_continuous",
    "compute_dtype",
    "need_conv",
    "height",
    "width",
    "is_gray",
)


def is_off_policy(algo: str) -> bool:
    return algo in OFF_POLICY_ALGOS


# The keys of a GraniteMoeHybrid ``config.json`` that shape the policy core:
# what ``models/granite_hybrid.py`` reads from ``Config.arch`` and the check
# below holds together (the token embedding's and the experts' keys are
# carried along unread).
GRANITE_ARCH_KEYS = (
    "hidden_size", "layer_types", "rms_norm_eps", "intermediate_size",
    "residual_multiplier", "embedding_multiplier", "logits_scaling",
    "mamba_n_heads", "mamba_d_head", "mamba_d_state", "mamba_n_groups",
    "mamba_d_conv", "mamba_expand", "mamba_chunk_size", "mamba_conv_bias",
    "mamba_proj_bias", "num_attention_heads", "num_key_value_heads",
    "attention_multiplier", "attention_bias",
)


def _check_granite_arch(arch: dict | None) -> None:
    """What ``model="granite_hybrid"`` can build: the dense (no experts)
    Mamba-2 + grouped-query attention arrangement without positions."""
    assert isinstance(arch, dict), "model='granite_hybrid' needs arch (config.json keys)"
    missing = [k for k in GRANITE_ARCH_KEYS if k not in arch]
    assert not missing, f"arch lacks {missing}"
    kinds = arch["layer_types"]
    assert kinds and set(kinds) <= {"mamba", "attention"}, kinds
    assert not arch.get("num_local_experts"), "expert layers are not built"
    assert arch.get("position_embedding_type", "nope") == "nope", (
        "only the published 'nope' (no positions) attention is built"
    )
    assert arch.get("hidden_act", "silu") == "silu", arch.get("hidden_act")
    assert arch.get("normalization_function", "rmsnorm") == "rmsnorm"
    inner = arch["mamba_n_heads"] * arch["mamba_d_head"]
    assert inner == arch["mamba_expand"] * arch["hidden_size"], (
        f"mamba_n_heads x mamba_d_head = {inner} is not mamba_expand x hidden_size"
    )
    assert arch["mamba_n_heads"] % arch["mamba_n_groups"] == 0
    assert arch["hidden_size"] % arch["num_attention_heads"] == 0
    assert arch["num_attention_heads"] % arch["num_key_value_heads"] == 0
    assert arch["mamba_d_conv"] >= 2 and arch["mamba_chunk_size"] >= 1


# The keys of a NemotronH ``config.json`` that shape the policy core
# (``models/nemotron_h.py``); the optional group ``expert_parallel``
# (``published_n_routed_experts``, ``chips``, ``rank``) states the deployment
# whose one rank ``n_routed_experts`` counts.
NEMOTRON_ARCH_KEYS = (
    "hidden_size", "hybrid_override_pattern", "layer_norm_epsilon",
    "mamba_num_heads", "mamba_head_dim", "ssm_state_size", "n_groups",
    "conv_kernel", "chunk_size", "use_conv_bias", "mamba_proj_bias",
    "num_attention_heads", "num_key_value_heads", "head_dim", "attention_bias",
    "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
    "moe_shared_expert_intermediate_size", "n_shared_experts", "norm_topk_prob",
    "routed_scaling_factor",
)


def _check_nemotron_arch(arch: dict | None) -> None:
    """What ``model="nemotron_h"`` can build: Mamba-2 (``M``), attention
    without positions (``*``) and sparse-expert (``E``) layers, one mixer a
    layer; sigmoid routing in one group, the chosen scores normalised; ``relu2``
    experts without bias."""
    assert isinstance(arch, dict), "model='nemotron_h' needs arch (config.json keys)"
    missing = [k for k in NEMOTRON_ARCH_KEYS if k not in arch]
    assert not missing, f"arch lacks {missing}"
    pattern = arch["hybrid_override_pattern"]
    assert pattern and set(pattern) <= set("ME*"), (
        f"layers {pattern!r}: only M (Mamba-2), E (experts) and * (attention) are built"
    )
    assert arch.get("mlp_hidden_act", "relu2") == "relu2", (
        f"mlp_hidden_act {arch.get('mlp_hidden_act')!r}: only relu2 experts are built"
    )
    assert arch.get("mamba_hidden_act", "silu") == "silu", arch.get("mamba_hidden_act")
    assert not arch.get("mlp_bias", False), "expert projections have no bias"
    assert arch["n_shared_experts"] == 1, "one shared expert is built"
    assert arch["norm_topk_prob"], "the chosen scores are always normalised"
    assert arch.get("n_group", 1) == 1 and arch.get("topk_group", 1) == 1, (
        "the router's group stage is not built"
    )
    assert arch["mamba_num_heads"] % arch["n_groups"] == 0
    assert arch["num_attention_heads"] % arch["num_key_value_heads"] == 0
    assert arch["conv_kernel"] >= 2 and arch["chunk_size"] >= 1
    _check_expert_share(arch, arch["n_routed_experts"], arch["num_experts_per_tok"])


# The keys of a SmallThinker ``config.json`` that shape the policy core
# (``models/smallthinker.py``); ``expert_parallel`` as above, with
# ``moe_num_primary_experts`` the count one rank holds.
SMALLTHINKER_ARCH_KEYS = (
    "hidden_size", "num_hidden_layers", "rms_norm_eps", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "rope_layout",
    "sliding_window_layout", "sliding_window_size", "moe_ffn_hidden_size",
    "moe_num_primary_experts", "moe_num_active_primary_experts",
    "moe_primary_router_apply_softmax", "norm_topk_prob",
)


def _check_expert_share(arch: dict, held: int, top_k: int) -> None:
    """``arch["expert_parallel"]`` against the experts one rank holds."""
    share = arch.get("expert_parallel")
    if share:
        total, chips, rank = (
            share[k] for k in ("published_n_routed_experts", "chips", "rank")
        )
        assert held * chips == total, (
            f"{chips} chips x {held} routed experts held is not the published {total}"
        )
        assert 0 <= rank < chips, f"rank {rank} of {chips}"
    else:
        total = held
    assert 1 <= top_k <= total, top_k


def _check_smallthinker_arch(arch: dict | None) -> None:
    """What ``model="smallthinker"`` can build: every layer grouped-query
    attention (global without positions, or a sliding window with rotary
    ones, as the two layouts say) and gated ``relu`` experts under a softmax
    router over the chosen logits; no shared expert, no bias anywhere."""
    assert isinstance(arch, dict), "model='smallthinker' needs arch (config.json keys)"
    missing = [k for k in SMALLTHINKER_ARCH_KEYS if k not in arch]
    assert not missing, f"arch lacks {missing}"
    depth = arch["num_hidden_layers"]
    for key in ("rope_layout", "sliding_window_layout"):
        layout = arch[key]
        assert len(layout) == depth >= 1 and set(layout) <= {0, 1}, (
            f"{key} {layout!r}: one 0 or 1 for each of the {depth} layers"
        )
    assert arch["sliding_window_size"] >= 1, arch["sliding_window_size"]
    assert arch.get("rope_scaling") is None, "rotary scaling is not built"
    assert arch["head_dim"] % 2 == 0, "rotate-half pairs the head's two halves"
    assert arch["moe_primary_router_apply_softmax"] and arch["norm_topk_prob"], (
        "the router is the softmax over the chosen logits"
    )
    assert arch["num_attention_heads"] % arch["num_key_value_heads"] == 0
    _check_expert_share(
        arch, arch["moe_num_primary_experts"], arch["moe_num_active_primary_experts"]
    )


# The keys of a Qwen3-Next ``config.json`` that shape the policy core
# (``models/qwen3_next.py``); ``expert_parallel`` as above, with
# ``num_experts`` the count one rank holds.
QWEN3_NEXT_ARCH_KEYS = (
    "hidden_size", "num_hidden_layers", "full_attention_interval", "rms_norm_eps",
    "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
    "linear_value_head_dim", "linear_conv_kernel_dim", "num_attention_heads",
    "num_key_value_heads", "head_dim", "rope_theta", "partial_rotary_factor",
    "moe_intermediate_size", "shared_expert_intermediate_size", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "decoder_sparse_step", "mlp_only_layers",
)


def _check_qwen3_next_arch(arch: dict | None) -> None:
    """What ``model="qwen3_next"`` can build: Gated-DeltaNet linear-attention
    layers with a gated full-attention layer (q/k norm, partial rotary
    positions) every ``full_attention_interval``-th, every layer followed by
    ``swiglu`` experts under a softmax router over the chosen logits and a
    gated shared expert; no bias anywhere, no dense MLP layer, no window."""
    assert isinstance(arch, dict), "model='qwen3_next' needs arch (config.json keys)"
    missing = [k for k in QWEN3_NEXT_ARCH_KEYS if k not in arch]
    assert not missing, f"arch lacks {missing}"
    assert arch["num_hidden_layers"] >= 1 and arch["full_attention_interval"] >= 1
    assert not arch["mlp_only_layers"], "dense MLP layers are not built"
    assert arch["decoder_sparse_step"] == 1, "every layer has an expert block"
    assert not arch.get("use_sliding_window", False), "the full layers have no window"
    assert arch.get("rope_scaling") is None, "rotary scaling is not built"
    assert arch.get("hidden_act", "silu") == "silu", arch.get("hidden_act")
    assert not arch.get("attention_bias", False), "attention projections have no bias"
    assert arch["norm_topk_prob"], "the router is the softmax over the chosen logits"
    rotary = arch["head_dim"] * arch["partial_rotary_factor"]
    assert rotary == int(rotary) and int(rotary) % 2 == 0 and 0 < rotary <= arch["head_dim"], (
        f"partial_rotary_factor {arch['partial_rotary_factor']}: rotate-half pairs the halves "
        f"of the first {rotary} features"
    )
    assert arch["linear_num_value_heads"] % arch["linear_num_key_heads"] == 0
    assert arch["num_attention_heads"] % arch["num_key_value_heads"] == 0
    assert arch["linear_conv_kernel_dim"] >= 2, arch["linear_conv_kernel_dim"]
    assert arch["shared_expert_intermediate_size"] >= 1, "one gated shared expert is built"
    _check_expert_share(arch, arch["num_experts"], arch["num_experts_per_tok"])


# The keys of a GLM-4.7-Flash (``glm4_moe_lite``) ``config.json`` that shape
# the policy core (``models/glm4_moe_lite.py``); ``expert_parallel`` as above,
# with ``n_routed_experts`` the count one rank holds.
GLM4_MOE_LITE_ARCH_KEYS = (
    "hidden_size", "num_hidden_layers", "first_k_dense_replace", "rms_norm_eps",
    "num_attention_heads", "num_key_value_heads", "q_lora_rank", "kv_lora_rank",
    "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
    "partial_rotary_factor", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
    "routed_scaling_factor", "topk_method", "n_group", "topk_group",
)


def _check_glm4_moe_lite_arch(arch: dict | None) -> None:
    """What ``model="glm4_moe_lite"`` can build: every layer multi-head latent
    attention (queries and keys/values through normed low-rank latents, one
    rotated key shared by all heads) at equal query/key and value head sizes;
    the first ``first_k_dense_replace`` layers with a dense SwiGLU MLP, the
    others with ``swiglu`` experts under the sigmoid router with its
    correction bias (``noaux_tc`` in one group) and ungated shared experts; no
    bias anywhere, no rotary scaling, no multi-token prediction."""
    assert isinstance(arch, dict), "model='glm4_moe_lite' needs arch (config.json keys)"
    missing = [k for k in GLM4_MOE_LITE_ARCH_KEYS if k not in arch]
    assert not missing, f"arch lacks {missing}"
    depth, dense = arch["num_hidden_layers"], arch["first_k_dense_replace"]
    assert 0 <= dense < depth, (
        f"first_k_dense_replace {dense} of {depth} layers: an expert layer has to follow"
    )
    qk, v = arch["qk_nope_head_dim"] + arch["qk_rope_head_dim"], arch["v_head_dim"]
    assert qk == v, (
        f"qk_nope_head_dim + qk_rope_head_dim = {qk} is not v_head_dim = {v}: a latent path "
        "with unequal query/value head sizes is not built"
    )
    assert arch["num_key_value_heads"] == arch["num_attention_heads"], (
        "latent attention is multi-head: every head has its own keys and values"
    )
    assert arch["q_lora_rank"], "queries without a latent (q_lora_rank null) are not built"
    assert arch["qk_rope_head_dim"] % 2 == 0, "rotate-half pairs the rotated part's two halves"
    assert arch["partial_rotary_factor"] == 1, "the whole rotated part is rotated"
    assert arch.get("rope_scaling") is None, "rotary scaling is not built"
    assert arch.get("hidden_act", "silu") == "silu", arch.get("hidden_act")
    assert not arch.get("attention_bias", False), "attention projections have no bias"
    assert not arch.get("num_nextn_predict_layers", 0), "multi-token prediction is not built"
    assert arch["topk_method"] == "noaux_tc", f"topk_method {arch['topk_method']!r}"
    assert arch["n_group"] == 1 and arch["topk_group"] == 1, (
        "the router's group stage is not built"
    )
    assert arch["norm_topk_prob"], "the chosen scores are always normalised"
    assert arch["n_shared_experts"] >= 1, "an expert layer has its shared expert"
    _check_expert_share(arch, arch["n_routed_experts"], arch["num_experts_per_tok"])


# The keys of an LFM2-MoE (``lfm2_moe``) ``config.json`` that shape the policy
# core (``models/lfm2_moe.py``); ``expert_parallel`` as above, with
# ``num_experts`` the count one rank holds.
LFM2_MOE_ARCH_KEYS = (
    "hidden_size", "num_hidden_layers", "layer_types", "num_dense_layers", "norm_eps",
    "conv_L_cache", "conv_bias", "num_attention_heads", "num_key_value_heads",
    "rope_parameters", "intermediate_size", "moe_intermediate_size", "num_experts",
    "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "use_expert_bias",
)
LFM2_MOE_LAYER_TYPES = ("conv", "full_attention")


def _check_lfm2_moe_arch(arch: dict | None) -> None:
    """What ``model="lfm2_moe"`` can build: each layer a gated short
    convolution (``conv``) or grouped-query attention with plain per-head q/k
    norms and rotary positions over the whole head (``full_attention``), as
    ``layer_types`` says; the first ``num_dense_layers`` layers with a dense
    SwiGLU MLP, the others with ``swiglu`` experts under the sigmoid router
    with its expert bias and no shared expert; no bias anywhere, no rotary
    scaling."""
    assert isinstance(arch, dict), "model='lfm2_moe' needs arch (config.json keys)"
    missing = [k for k in LFM2_MOE_ARCH_KEYS if k not in arch]
    assert not missing, f"arch lacks {missing}"
    depth, dense, kinds = arch["num_hidden_layers"], arch["num_dense_layers"], arch["layer_types"]
    assert len(kinds) == depth >= 1, f"layer_types names {len(kinds)} layers of {depth}"
    unknown = sorted(set(kinds) - set(LFM2_MOE_LAYER_TYPES))
    assert not unknown, f"layer_types {unknown}: a layer is one of {LFM2_MOE_LAYER_TYPES}"
    assert 0 <= dense < depth, (
        f"num_dense_layers {dense} of {depth} layers: an expert layer has to follow"
    )
    assert not arch["conv_bias"], "the convolution and its projections have no bias"
    assert arch["conv_L_cache"] >= 2, f"conv_L_cache {arch['conv_L_cache']}: a tail of none"
    heads, kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    assert heads % kv == 0 and arch["hidden_size"] % heads == 0, (heads, kv)
    assert arch.get("head_dim", arch["hidden_size"] // heads) == arch["hidden_size"] // heads, (
        "a head is hidden_size / num_attention_heads wide"
    )
    assert arch["hidden_size"] // heads % 2 == 0, "rotate-half pairs a head's two halves"
    rotary = arch["rope_parameters"]
    assert "rope_theta" in rotary and rotary.get("rope_type", "default") == "default", (
        f"rope_parameters {rotary!r}: rotary scaling is not built"
    )
    assert arch["norm_topk_prob"], "the chosen scores are always normalised"
    assert arch["use_expert_bias"], "the router is the sigmoid one with its expert bias"
    _check_expert_share(arch, arch["num_experts"], arch["num_experts_per_tok"])


# The keys of a Ling-3.0-flash (``ling_flash``) ``config.json`` that shape the
# policy core (``models/ling_flash.py``); ``expert_parallel`` as above, with
# ``num_experts`` the count one rank holds, and the optional ``layer_offset``:
# the published index of the first layer built (a cut that starts inside the
# published stack keeps each layer's published mixer).
LING_FLASH_ARCH_KEYS = (
    "hidden_size", "num_hidden_layers", "layer_group_size", "first_k_dense_replace",
    "rms_norm_eps", "num_attention_heads", "num_key_value_heads", "head_dim", "q_lora_rank",
    "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "rope_theta",
    "short_conv_kernel_size", "kda_safe_gate", "kda_lower_bound", "intermediate_size",
    "moe_intermediate_size", "moe_shared_expert_intermediate_size", "num_experts",
    "num_experts_per_tok", "n_group", "topk_group", "score_function",
    "moe_router_enable_expert_bias", "routed_scaling_factor", "norm_topk_prob",
)
# ``ops/kda.SUB``: the steps whose pair decays are factored against one
# reference step, which a gate at its bound raises to e^(|bound| (SUB - 1)).
KDA_SUB_BLOCK = 16
# What the family's published switches must say where the arch holds them.
_LING_FLASH_FIXED = {
    "linear_silu": True, "use_qk_norm": True, "num_kv_heads_for_linear_attn": 0,
    "group_norm_size": 1, "gated_attention_proj_granularity_type": "head_wise",
    "no_kda_lora": True, "use_kda_lora": False, "use_mla_nope": False, "use_nGPT": False,
    "scale_router_input": False, "value_norm": False, "up_proj_norm": False,
    "rope_scaling": None, "hidden_act": "silu",
}


def _check_ling_flash_arch(arch: dict | None) -> None:
    """What ``model="ling_flash"`` can build: layer ``i`` (published index,
    ``layer_offset`` + its place in the stack) with latent attention where
    ``(i + 1) % layer_group_size == 0`` — queries without a latent, unequal
    query/key and value head sizes, a head-wise output gate — and Kimi Delta
    Attention elsewhere (full-rank per-channel gate bounded by
    ``kda_lower_bound``, as many key heads as value heads, a per-head norm and a
    head-wise gate); the first ``first_k_dense_replace`` layers of the stack
    with a dense SwiGLU MLP, the others with ``swiglu`` experts under the
    group-limited sigmoid router with its expert bias and one ungated shared
    expert; no bias anywhere, no rotary scaling, no clamped experts, no
    multi-token prediction, no tower."""
    assert isinstance(arch, dict), "model='ling_flash' needs arch (config.json keys)"
    missing = [k for k in LING_FLASH_ARCH_KEYS if k not in arch]
    assert not missing, f"arch lacks {missing}"
    for key, want in _LING_FLASH_FIXED.items():
        assert arch.get(key, want) == want, f"{key} {arch[key]!r}: only {want!r} is built"
    depth, dense, group = (
        arch[k] for k in ("num_hidden_layers", "first_k_dense_replace", "layer_group_size"))
    assert 0 <= dense < depth, (
        f"first_k_dense_replace {dense} of {depth} layers: an expert layer has to follow"
    )
    assert group >= 2, f"layer_group_size {group}: a group is linear layers and one latent layer"
    assert arch.get("layer_offset", 0) >= 0, arch.get("layer_offset")
    heads = arch["num_attention_heads"]
    assert arch["num_key_value_heads"] == heads, (
        "both mixers are multi-head: every head has its own keys and values"
    )
    assert arch["q_lora_rank"] is None, (
        f"q_lora_rank {arch['q_lora_rank']}: this family's queries have no latent"
    )
    assert arch["qk_rope_head_dim"] % 2 == 0, "rotate-half pairs the rotated part's two halves"
    assert arch.get("rotary_dim", arch["qk_rope_head_dim"]) == arch["qk_rope_head_dim"], (
        "the whole rotated part is rotated"
    )
    assert arch["short_conv_kernel_size"] >= 2, (
        f"short_conv_kernel_size {arch['short_conv_kernel_size']}: a tail of none"
    )
    bound = arch["kda_lower_bound"]
    assert arch["kda_safe_gate"] and bound < 0, (
        f"kda_safe_gate {arch['kda_safe_gate']!r} with kda_lower_bound {bound}: the gate is the "
        "bounded one, kda_lower_bound * sigmoid(.) with a bound below 0"
    )
    assert abs(bound) * KDA_SUB_BLOCK <= 85, (
        f"kda_lower_bound {bound}: a sub-block of {KDA_SUB_BLOCK} steps at that bound raises an "
        "operand of the chunked rule past float32 (e^88)"
    )
    limits = [
        x for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list")
        for x in arch.get(key, [])[arch.get("layer_offset", 0):][:depth]
    ]
    assert not any(limits), "clamped SwiGLU experts are not built"
    assert not arch.get("num_nextn_predict_layers", 0), "multi-token prediction is not built"
    assert arch["score_function"] == "sigmoid" and arch["moe_router_enable_expert_bias"], (
        "the router is the sigmoid one with its expert bias"
    )
    assert arch["norm_topk_prob"], "the chosen scores are always normalised"
    assert arch["moe_shared_expert_intermediate_size"] >= 1, "an expert layer has its shared expert"
    held, top_k = arch["num_experts"], arch["num_experts_per_tok"]
    _check_expert_share(arch, held, top_k)
    share = arch.get("expert_parallel")
    total = share["published_n_routed_experts"] if share else held
    n_group, kept = arch["n_group"], arch["topk_group"]
    assert n_group >= 1 and total % n_group == 0, (
        f"{total} experts are no whole number of n_group {n_group} groups"
    )
    assert 1 <= kept <= n_group, f"topk_group {kept} of n_group {n_group}"
    size = total // n_group
    assert top_k <= kept * size, (
        f"num_experts_per_tok {top_k} from {kept} kept groups of {size} experts"
    )
    first = share["rank"] * held if share else 0
    assert held % size == 0 or first % size + held <= size, (
        f"experts {first}-{first + held - 1} held in groups of {size}: a rank holds whole groups "
        "or lies inside one"
    )


# The keys of an EvaByte (``evabyte``) ``config.json`` that shape the policy
# core (``models/evabyte.py``).
EVABYTE_ARCH_KEYS = (
    "hidden_size", "num_hidden_layers", "num_attention_heads", "num_key_value_heads",
    "window_size", "chunk_size", "intermediate_size", "rms_norm_eps", "rope_theta",
    "norm_add_unit_offset", "init_std", "attention_class",
)


def _check_evabyte_arch(arch: dict | None) -> None:
    """What ``model="evabyte"`` can build: every layer EVA attention — the
    exact keys of the query's own ``window_size``-step block and one pooled
    summary for every ``chunk_size``-step chunk of the blocks before it, one
    softmax over both — at as many key/value heads as query heads, rotary
    positions over the whole head, and a dense SwiGLU MLP, each behind a
    ``1 + w`` RMSNorm; no bias anywhere, no rotary scaling."""
    assert isinstance(arch, dict), "model='evabyte' needs arch (config.json keys)"
    missing = [k for k in EVABYTE_ARCH_KEYS if k not in arch]
    assert not missing, f"arch lacks {missing}"
    assert arch["attention_class"] == "eva", f"attention_class {arch['attention_class']!r}"
    assert arch["num_hidden_layers"] >= 1, arch["num_hidden_layers"]
    heads, hidden = arch["num_attention_heads"], arch["hidden_size"]
    assert arch["num_key_value_heads"] == heads, (
        "EVA attention is multi-head: every head pools its own keys and values"
    )
    assert hidden % heads == 0 and arch.get("head_dim") in (None, hidden // heads), (
        f"hidden_size {hidden} is not num_attention_heads {heads} x the head size"
    )
    assert hidden // heads % 2 == 0, "rotate-half pairs a head's two halves"
    block, chunk = arch["window_size"], arch["chunk_size"]
    assert chunk >= 1 and block >= chunk and block % chunk == 0, (
        f"window_size {block} is no whole number of chunk_size {chunk} chunks"
    )
    assert arch["norm_add_unit_offset"], "the norms scale by 1 + w"
    assert arch.get("rope_scaling") is None, "rotary scaling is not built"
    assert arch.get("hidden_act", "silu") == "silu", arch.get("hidden_act")
    assert not arch.get("attention_bias", False), "attention projections have no bias"


# The families built from a published config.json in ``Config.arch``.
ARCH_CHECKS = {
    "granite_hybrid": _check_granite_arch,
    "nemotron_h": _check_nemotron_arch,
    "smallthinker": _check_smallthinker_arch,
    "qwen3_next": _check_qwen3_next_arch,
    "glm4_moe_lite": _check_glm4_moe_lite_arch,
    "lfm2_moe": _check_lfm2_moe_arch,
    "evabyte": _check_evabyte_arch,
    "ling_flash": _check_ling_flash_arch,
}


@dataclass
class Config:
    """Hyperparameters. Field names/defaults match the reference's
    ``utils/parameters.json:1-32`` so existing config files load unchanged."""

    # experiment
    env: str = "CartPole-v1"
    algo: str = "PPO"
    result_dir: str | None = None
    model_dir: str | None = None

    # observation preprocessing (conv path; parity with the reference's unused flags)
    need_conv: bool = False
    height: int = 84
    width: int = 84
    is_gray: bool = False

    # model
    hidden_size: int = 64
    # Policy backbone: "lstm" (reference parity), "transformer" (new
    # TPU-native long-context capability; on-policy algos only) or
    # "granite_hybrid" (Mamba-2 + GQA attention layers of a published
    # GraniteMoeHybrid config.json, given whole in ``arch``) or "nemotron_h"
    # (Mamba-2, attention and sparse-expert layers of a NemotronH config.json)
    # or "smallthinker" (global and sliding-window attention, each layer with
    # gated sparse experts, of a SmallThinker config.json) or "qwen3_next"
    # (Gated-DeltaNet linear attention and gated full attention, each layer
    # with sparse experts and a gated shared one, of a Qwen3-Next config.json)
    # or "glm4_moe_lite" (multi-head latent attention, a leading dense layer,
    # then sparse experts with a shared one, of a GLM-4.7-Flash config.json)
    # or "lfm2_moe" (gated short convolutions around grouped-query attention,
    # a leading dense layer, then sparse experts, of an LFM2-MoE config.json)
    # or "evabyte" (EVA attention — a block's exact keys and pooled summaries of
    # the chunks before it under one softmax — and a dense SwiGLU MLP in every
    # layer, of an EvaByte config.json) or "ling_flash" (Kimi Delta Attention —
    # the delta rule with a decay per key channel — five layers in six around
    # latent attention with unequal query and value heads, a leading dense
    # layer, then experts under a group-limited router, of a Ling-3.0-flash
    # config.json).
    model: str = "lstm"
    n_heads: int = 4
    n_layers: int = 2
    # Attention impl for the transformer: "full" | "blockwise" (single-chip
    # memory-efficient, no (T,T) scores) | "ring" | "ulysses" (seq-sharded).
    attention_impl: str = "full"
    # Worker-side attention context (sliding window) for transformer acting;
    # 0 = use seq_len.
    act_ctx: int = 0
    # A published architecture's own config.json, under its published key
    # names (model="granite_hybrid": GRANITE_ARCH_KEYS above; "nemotron_h":
    # NEMOTRON_ARCH_KEYS; "smallthinker": SMALLTHINKER_ARCH_KEYS;
    # "qwen3_next": QWEN3_NEXT_ARCH_KEYS; "glm4_moe_lite":
    # GLM4_MOE_LITE_ARCH_KEYS; "lfm2_moe": LFM2_MOE_ARCH_KEYS; "evabyte":
    # EVABYTE_ARCH_KEYS; "ling_flash": LING_FLASH_ARCH_KEYS). One
    # mapping instead of a Config field per width: the widths of a catalog
    # model are its source's to name, not this file's.
    arch: dict | None = None

    # rollout
    time_horizon: int = 500
    reward_scale: float = 0.1
    seq_len: int = 5
    batch_size: int = 128

    # returns / losses
    gamma: float = 0.99
    lmbda: float = 0.95
    eps_clip: float = 0.1
    policy_loss_coef: float = 1.0
    value_loss_coef: float = 0.5
    entropy_coef: float = 0.00005

    # SAC
    alpha: float = 0.2
    tau: float = 0.005
    # Temperature floor (0 = reference parity, no floor). The auto-tuned
    # alpha shrinks until the policy's entropy matches the target, which on
    # sparse-goal envs can extinguish exploration before the critic has
    # consolidated the goal basin — measured on MountainCarContinuous seed
    # 2: alpha decayed 0.117 -> 0.008 while the 50-game mean fell 64.5 ->
    # -33 in lockstep (the rise-then-collapse of BASELINE_RESULTS row 11).
    # A floor keeps exploration pressure alive, the off-policy analogue of
    # std_floor for PPO-Continuous.
    alpha_min: float = 0.0
    # Temperature-controller learning rate; None = cfg.lr (reference parity:
    # one Adam lr for all three optimizers, agents/learner.py:360-367).
    # Slowing ONLY the alpha controller stretches the exploration-decay
    # clock without moving its equilibrium — on sparse-goal envs the decay
    # otherwise outruns critic/policy consolidation (the measured
    # MountainCarContinuous seed-2 race; see alpha_min).
    alpha_lr: float | None = None
    # SAC temperature target entropy; None = standard auto rule
    # (-dim(A) continuous, 0.98*log|A| discrete — see algos/sac.py for the
    # documented divergence from the reference's +action_space).
    target_entropy: float | None = None
    # Strict-parity mode for the SAC temperature controller: reproduce the
    # reference's alpha update EXACTLY — target_entropy = +action_space and
    # loss_alpha = +mean(alpha * (E[log pi] + target))
    # (/root/reference/agents/learner_module/sac/learning.py:66-74,
    # agents/learner.py:363-365). That feedback runs backwards (alpha decays
    # toward 0 unconditionally, since E[log pi] + |A| > 0 always), which is
    # why the default here is the corrected controller; the flag exists so
    # reference temperature behavior is reproducible for audit, same
    # pattern as zero_window_carry/std_floor (parity by default elsewhere,
    # gated divergence here because the fix is load-bearing for learning).
    sac_reference_alpha: bool = False

    # V-trace clipping (reference hard-codes rho in [0.1, 0.8], c_bar = 1.0,
    # /root/reference/agents/learner_module/compute_loss.py:29-43)
    rho_bar: float = 0.8
    rho_min: float = 0.1
    c_bar: float = 1.0
    # Bounded-return value clamp [v_min, v_max] for the V-trace recursion
    # (ops/returns.py): None = reference parity. For envs whose scaled
    # discounted return is bounded by construction (CartPole at
    # reward_scale 0.1 / gamma 0.99: [0, ~9.93]) this stops the async-lag
    # value-hallucination spiral measured in CLUSTER_LEARNING.md — the
    # rho-damped corrections cannot pull a drifting critic back, but the
    # clamp caps the drift at the source.
    value_target_clip: tuple[float, float] | None = None

    # V-MPO
    v_mpo_lagrange_multiplier_init: float = 5.0
    coef_eta: float = 0.01
    coef_alpha_upper: float = 0.01
    coef_alpha_below: float = 0.005

    # replay
    buffer_size: int = 10240

    # optimization
    K_epoch: int = 1
    lr: float = 0.0001
    max_grad_norm: float = 40.0
    # Two-phase entropy/lr anneal, applied by both the inline harness and the
    # distributed learner (LearnerService): after a switch point the run
    # continues with {"coef": final_entropy_coef, "lr": final_lr (optional)}.
    # The switch point is {"at": n} — an ABSOLUTE update index, so a
    # checkpoint-resumed learner already past it re-enters the cold phase
    # immediately — or {"frac": f} as a fraction of the run's update budget
    # (inline: the updates arg; cluster: max_updates). High early
    # exploration, then a near-deterministic low-variance tail —
    # capped-return targets (CartPole 500) need it (measured: a fixed
    # entropy bonus that keeps entropy ~0.58 caps the 50-game mean near 50;
    # see BASELINE_RESULTS.md / CLUSTER_LEARNING.md).
    entropy_anneal: dict | None = None
    # Distributed learner early stop: when the fleet 50-game mean reward
    # (stat mailbox, window full) reaches this value the learner exits
    # cleanly (exit code 0) before max_updates. None = run the full budget.
    stop_at_reward: float | None = None

    # logging / checkpoints
    loss_log_interval: int = 50
    model_save_interval: int = 100
    # Committed checkpoints retained on disk (newest-index wins; GC removes
    # older COMMITTED dirs only — see tpu_rl/checkpoint.py).
    ckpt_keep: int = 5
    # Move the checkpoint D2H + disk write onto a background thread
    # (device-side snapshot, latest-wins queue). False = blocking save on
    # the update loop (the A/B baseline; both paths are commit-atomic).
    ckpt_async: bool = True
    # Resume from a checkpoint whose stored config fingerprint (the
    # structure-defining subset — model/env/dtype shape) disagrees with the
    # current config. Default False: mismatch refuses to resume.
    resume_force: bool = False
    # XLA profiler trace export (the reference has timers but no trace
    # export, SURVEY.md §5.1): when set, the learner captures a device
    # profile of ~profile_steps updates once profile_start updates have
    # completed in this run (resume-safe; the trace is closed on exit even
    # if the run ends early). View with tensorboard or xprof.
    profile_dir: str | None = None
    profile_start: int = 10
    profile_steps: int = 5

    # ---- TPU-native knobs (new capability; no reference equivalent) ----
    # Reset the LSTM carry at in-sequence episode seams (the reference does not:
    # /root/reference/networks/models.py:71-75 carries state straight through
    # spliced trajectories). Default True = the fix; set False for bit-parity.
    reset_carry_on_first: bool = True
    # Data-parallel mesh size for the learner (1 = single chip).
    mesh_data: int = 1
    # Updates per dispatched learner program (make_parallel_train_step's
    # chain): the learner accumulates K consumed batches and dispatches ONE
    # compiled program running K sequential optimizer updates (lax.scan).
    # Amortizes fixed per-dispatch host overhead, which at the reference
    # quantum (sub-ms updates) otherwise dominates learner throughput.
    # 1 = dispatch per batch (reference semantics).
    # Two dispatch-granularity caveats: (a) the update counter advances K per
    # dispatch, so between-dispatch checks — notably the entropy/lr anneal
    # switch — can fire up to K-1 updates late; (b) a max_updates budget
    # smaller than K clamps the chain down to the budget at learner start
    # (a small budget performs real updates instead of silently zero).
    learner_chain: int = 1
    # Learner host-data-plane pipelining: depth of the prefetch queue. The
    # feed (shm sample/consume -> carry zeroing -> Batch assembly -> H2D
    # placement with the step's sharding) runs on a background thread and
    # the learner pops device-resident batches, so the NEXT dispatch's host
    # work overlaps the CURRENT train_step (tpu_rl/data/prefetch.py). Costs
    # depth x batch bytes of device memory and at most `depth` dispatches of
    # extra on-policy staleness. 0 = synchronous feed (the A/B switch and
    # the pre-pipeline serial semantics).
    learner_prefetch: int = 2
    # Off-policy update:data ratio cap: maximum learner updates per received
    # environment transition (transitions = stored windows x seq_len). The
    # replay learner WAITS (idles, heartbeating) while one more update would
    # exceed the cap, instead of free-running against the ring (~50:1
    # measured on a shared core, CLUSTER_R5_SAC.md — the round-5 blocker:
    # re-fitting early random experience). E.g. 0.2 allows one update per 5
    # transitions. None = no gate (reference parity: sample as fast as the
    # ring answers). Ignored by on-policy algos (their store consumes).
    max_update_data_ratio: float | None = None
    # Sequence-parallel mesh size (long-context training; needs
    # model="transformer" and attention_impl "ring"/"ulysses").
    mesh_seq: int = 1
    # Multi-host learner: {"coordinator": "ip:port", "num_processes": N,
    # "process_id": i}. After jax.distributed init, meshes span all hosts'
    # chips and the same GSPMD train steps scale unchanged (parallel.multihost).
    multihost: dict | None = None
    # Compute dtype for the train step ("float32" or "bfloat16").
    compute_dtype: str = "float32"
    # Learner device: "auto" = the accelerator (reference learner
    # semantics, main.py:66-68) — an accelerator-owning role that finds only
    # the CPU backend raises instead of carrying on — or "cpu" (run the
    # learner child on the CPU on purpose; used by CI and by deployments
    # where another process owns the chip).
    learner_device: str = "auto"
    # Worker step throttle, seconds (reference hard-codes 0.05:
    # /root/reference/agents/worker.py:131). 0 disables. With
    # worker_num_envs > 1 the throttle applies per batched tick.
    worker_step_sleep: float = 0.05
    # R2D2-style zero-init of the recurrent carry at training-window starts
    # (learner side). The reference trains from the actor-stored stale carry
    # (ppo/learning.py:37-40); under async fleet lag those off-manifold
    # hidden states measurably drive bootstrapped value hallucination
    # (mean V above the discounted cap). False = reference parity.
    zero_window_carry: bool = False
    # Hold each policy action for k underlying env steps (frame-skip),
    # summing rewards; 1 = reference parity (no repeat). Shrinks the
    # decision horizon k-fold and makes exploration noise piecewise-
    # constant (see EnvAdapter.step).
    action_repeat: int = 1
    # Sampling-std lower bound for the Gaussian (PPO-Continuous) policy:
    # 0 = reference parity (std = softplus(head) alone, models.py:114-118);
    # > 0 keeps exploration alive on sparse-goal envs (MountainCarContinuous)
    # where the entropy bonus alone lets the std collapse into the do-nothing
    # local optimum before the goal is ever found. Sampling and training use
    # the same floored distribution, so the policy stays exactly on-policy.
    std_floor: float = 0.0
    # Number of gymnasium envs one worker process steps with a SINGLE batched
    # act() call per tick (TPU-native vectorized acting; the reference is
    # strictly one env per process, /root/reference/agents/worker.py:87-142,
    # capping each process at ~20 env-steps/s). Batching the policy forward
    # amortizes dispatch overhead, so one process sustains ~N x the reference
    # per-process throughput. Works for every backbone: the transformer
    # acting carry packs per-env KV caches with per-row step counters.
    worker_num_envs: int = 1
    # ---- colocated (Anakin) mode (tpu_rl.runtime.colocated) ----
    # "distributed": the reference topology — gymnasium envs on host worker
    # processes, rollouts over ZMQ into shm, learner consumes (everything
    # above). "colocated": Podracer-Anakin — pure-JAX vectorized envs
    # (tpu_rl.envs) stepped INSIDE the jitted training loop on the learner
    # mesh; no workers, no manager, no storage, no host hop. One process,
    # one program: act -> env step -> window assembly -> train_step fused
    # under a single jit, the env batch sharded over the data mesh.
    env_mode: str = "distributed"
    # Env-batch size for colocated mode; each fused iteration rolls this
    # many envs seq_len steps and trains on the resulting windows, so it
    # overrides batch_size there (the env batch IS the train batch).
    # 0 = use batch_size unchanged. Thousands of instances is the intended
    # operating point on chip; tests/CI run tens.
    colocated_envs: int = 0
    # Sebulba split (Podracer, tpu_rl.runtime.sebulba): number of THIS
    # host's devices dedicated to the jitted act->env.step rollout program;
    # the REMAINING local devices run train_step, fed through a bounded
    # on-device queue so acting overlaps training instead of serializing
    # inside one fused dispatch. 0 = off (pure Anakin: one fused program
    # over one mesh). Requires env_mode="colocated"; the split must
    # partition jax.local_device_count() into two non-empty groups —
    # checked at loop construction (config never imports jax).
    sebulba_split: int = 0
    # Bounded device-resident Batch slots between the device groups (2 =
    # double buffering, 3 = triple). Bounds learner-group staging memory
    # AND policy staleness (a queued batch is at most depth+1 updates
    # stale); a full queue backpressures the actor into the goodput
    # ledger's queue-wait bucket.
    sebulba_queue: int = 2
    # RolloutAssembler idle-trajectory drop window, seconds
    # (reference hard-codes 0.5: /root/reference/buffers/rollout_assembler.py:52-56).
    rollout_lag_sec: float = 0.5
    # Rollout fan-in relay path (manager + storage ingest). "raw": the
    # manager routes Rollout/RolloutBatch frames on the proto byte alone
    # (protocol.peek — header/size validation only, no CRC/LZ4/unpack) and
    # forwards the received wire bytes verbatim, O(1) per frame; storage —
    # the only payload consumer — runs the single full CRC+decode and
    # ingests each tick columnar-wise (RolloutAssembler.push_tick).
    # "decode": the pre-zero-copy A/B baseline — the manager fully decodes
    # and re-encodes every frame and storage shreds ticks into per-step
    # dicts (split_rollout_batch + per-step push). Same assembled windows
    # bit-for-bit either way (tests/test_push_tick_equivalence.py).
    relay_mode: str = "raw"
    # Data-hop fabric for the rollout/stat/telemetry fan-in (manager ->
    # storage, learner/supervisor -> storage). "tcp": ZMQ PUB/SUB loopback
    # or DCN everywhere (the default — remote-safe, zero shared state).
    # "shm": producers write frames into named shared-memory SPSC rings and
    # the consumer fans them in (transport.ShmPub/FanInSub) — same-host
    # hops never touch a socket; the consumer's TCP SUB stays bound so
    # remote producers in a mixed fleet still land. "auto": shm exactly
    # when the hop's peer address is loopback (MachinesConfig), TCP
    # otherwise. The model broadcast (fan-OUT to remote workers) always
    # stays TCP.
    transport: str = "tcp"
    # Acting placement (SEED RL / Podracer-Sebulba): "local" — each worker
    # runs its own jitted policy forward on CPU (reference semantics);
    # "remote" — workers ship observations to the centralized inference
    # service colocated with the learner (runtime/inference_service.py),
    # which batches requests across the fleet and runs ONE jitted act on
    # the learner's device with zero-staleness params (swapped in-process
    # after every update, no broadcast lag).
    act_mode: str = "local"
    # Dynamic-batch flush knobs for the inference service: a batch is
    # dispatched when `inference_batch` observation rows are pending OR the
    # oldest pending request is `inference_flush_us` microseconds old,
    # whichever comes first. Bigger batch = better device utilization;
    # shorter deadline = lower per-tick acting latency.
    inference_batch: int = 64
    inference_flush_us: int = 1000
    # Remote-acting fault path: a worker whose inference request sees no
    # reply within `inference_timeout_ms` resends up to `inference_retries`
    # times, then falls back to LOCAL acting with its last-known params
    # (logged once) — a dead inference server degrades throughput, it never
    # wedges the fleet.
    inference_timeout_ms: int = 2000
    inference_retries: int = 2
    # Fallback recovery: a fallen-back worker probes the inference service
    # every `inference_reprobe_s` seconds (single zero-retry request using
    # the live observation; a reply restores remote acting, a timeout costs
    # one `inference_timeout_ms` and doubles the interval up to
    # `inference_reprobe_max_s`). 0 = the old one-way degradation: fall
    # back once, local forever.
    inference_reprobe_s: float = 5.0
    inference_reprobe_max_s: float = 60.0
    # ---- inference fleet (tpu_rl.fleet) ----
    # Number of inference service replicas serving the acting plane
    # (act_mode="remote"). 1 = the single learner-colocated service (PR 2
    # semantics). N > 1: replica 0 stays in-process in the learner
    # (zero-staleness params) and replicas 1..N-1 run as supervised
    # standalone processes fed by the model broadcast, each a continuous-
    # batching GSPMD-sharded InferenceReplica; workers act through the
    # FleetClient (power-of-two selection + hedged retries + failover).
    inference_replicas: int = 1
    # First port of the replica port range [base, base + replicas). 0 = the
    # legacy convention learner_port + 2 (MachinesConfig.inference_ports
    # still collision-checks the derived range either way).
    inference_base_port: int = 0
    # Hedged retries (FleetClient): when a reply hasn't arrived after this
    # many milliseconds, the SAME request (same seq) is resent to a second
    # replica and the first reply wins; the duplicate is deduped exactly
    # once. 0 = hedge only at the full timeout boundary (plain failover).
    inference_hedge_ms: int = 0
    # Data-mesh size per inference replica: obs/carry batches are sharded
    # over `inference_mesh_data` devices (NamedSharding over the "data"
    # axis, params replicated) and the padded act program runs under GSPMD.
    # 1 = single-device (no sharding constraints applied).
    inference_mesh_data: int = 1
    # ---- serving fast path (quantized params + bucketed batching) ----
    # Serving precision for the actor params held by InferenceService /
    # InferenceReplica: params are cast ONCE at set_params time
    # (tpu_rl.models.quant) and dequantized inside the jitted act step.
    # "f32" = bit-for-bit baseline; "bf16" halves the param bytes each
    # flush moves; "int8" quarters the matmul-weight bytes (per-tensor
    # symmetric scales, biases stay f32). Training precision is untouched.
    inference_dtype: str = "f32"
    # Padded-batch bucket ladder: 0 = single fixed pad_rows =
    # max(inference_batch, worker_num_envs) (legacy behavior, the A/B
    # baseline). > 0 = power-of-two buckets from this floor up to pad_rows
    # (e.g. 8 -> [8, 16, 32, ..., pad_rows]); each flush dispatches the
    # smallest covering bucket's pre-warmed program, so small flushes stop
    # paying the full padded step. All buckets compile before the socket
    # binds: the recompile ratchet (inference-xla-recompiles) stays 0.
    inference_buckets: int = 0
    # Act-step kernel for the serving/local act path: "xla" = the generic
    # family.act; "pallas" = the fused torso->LSTM->head kernel
    # (tpu_rl.ops.pallas_act) where supported (discrete LSTM actor-critic,
    # f32 compute, single-device), transparent fallback elsewhere.
    act_kernel: str = "xla"
    # ---- supervision (tpu_rl.runtime.runner.Supervisor) ----
    # A child silent (no heartbeat) for `heartbeat_timeout_s` is killed and
    # respawned; `startup_grace_s` extends the allowance after (re)spawn so
    # jit warmup/env construction don't read as hangs. The supervisor polls
    # children every `supervise_poll_s` seconds.
    heartbeat_timeout_s: float = 60.0
    startup_grace_s: float = 180.0
    supervise_poll_s: float = 2.0
    # Sliding-window restart budget: a child gets at most `max_restarts`
    # respawns per trailing `restart_window_s` seconds; exceeding it marks
    # the child exhausted and shuts the fleet down cleanly (a crash-loop is
    # a bug to surface, not to hide). Within a crash streak, respawn N waits
    # `restart_backoff_s * 2**(N-2)` seconds (first respawn is immediate),
    # capped at `restart_backoff_max_s`; a child healthy for a full window
    # resets its streak.
    max_restarts: int = 3
    restart_window_s: float = 300.0
    restart_backoff_s: float = 1.0
    restart_backoff_max_s: float = 30.0
    # ---- chaos plane (tpu_rl.chaos) ----
    # Deterministic fault plan, e.g.
    # "kill:worker-0-1@t+3s,corrupt:rollout@p=0.01,delay:manager@50ms".
    # Grammar and semantics: tpu_rl/chaos/plan.py. None (default) = no
    # injectors constructed anywhere; every hot-path hook reduces to one
    # `is None` check.
    chaos_spec: str | None = None
    # Base seed for all injectors; each socket/service derives its own
    # stream via crc32(site/instance), so a run replays from config alone.
    chaos_seed: int = 0
    # Learner liveness rebroadcast: when the learner has been idle (no
    # batch) and nothing was published for `rebroadcast_idle_s` seconds, it
    # re-publishes current weights + ver. Late-joining and *restarted*
    # workers (PUB/SUB slow-joiner drops the one-shot initial broadcast)
    # converge onto the live policy instead of acting stale forever.
    # 0 = publish only on the update cadence.
    rebroadcast_idle_s: float = 2.0
    # Live-membership lease at storage: a worker is a member while any of
    # its frames (rollout or telemetry) arrived within this window; silence
    # past it evicts the wid (storage-members-evicted counter). A NEW wid
    # joining raises the mailbox join flag so the learner pushes current
    # weights+ver immediately instead of waiting out rebroadcast_idle_s.
    membership_lease_s: float = 15.0
    # ---- self-healing plane (tpu_rl.heal) ----
    # In-jit non-finite update guards: every algo's train_step selects,
    # leaf by leaf, between its optimizer apply and the incoming state on
    # isfinite(loss) & isfinite(grad global-norm) — a bad update leaves
    # params/opt state untouched and counts into the per-step
    # "nonfinite-updates" metric. Guard off = literally the unguarded code
    # (bit-identity pinned in tests).
    update_guard: bool = True
    # Host-side divergence watchdog at the learner: EWMA/z-score over loss,
    # grad-norm and fleet mean return at the loss-log cadence, plus a
    # cumulative non-finite-update channel. A sustained anomaly rolls the
    # learner back to the PREVIOUS committed checkpoint, bumps the run
    # epoch (fencing in-flight pre-rollback rollouts exactly like
    # post-crash frames) and rebroadcasts weights. Off = no detector, no
    # per-update accumulator.
    watchdog_enabled: bool = False
    # EWMA window (samples) for the per-signal mean/variance estimates;
    # also the per-signal warmup before z-scores are trusted.
    watchdog_window: int = 32
    # |z| above this marks one check anomalous.
    watchdog_z: float = 6.0
    # Consecutive anomalous checks before a rollback triggers.
    watchdog_sustain: int = 3
    # Cumulative guard-skipped updates (since the last rollback) that
    # trigger a rollback immediately — the contained-NaN-stream channel.
    watchdog_nonfinite: int = 3
    # Feed the learning-dynamics diagnostics (tpu_rl.obs.learn) into the
    # watchdog as extra z-score channels: sustained approx-KL spikes and
    # importance-weight ESS collapse become rollback trip signals alongside
    # loss/grad-norm. Requires learn_diag (the signals don't exist without
    # it) and watchdog_enabled. Default off: diagnostics observe, the
    # watchdog acts — coupling them is an explicit operator choice.
    watchdog_diag: bool = False
    # Sliding-window rollback budget (the supervisor restart-budget shape):
    # at most `max_rollbacks` rollbacks per trailing `rollback_window_s`
    # seconds; an exhausted budget exits the learner cleanly — a run that
    # keeps diverging is a bug to surface, not to hide in a restore loop.
    max_rollbacks: int = 3
    rollback_window_s: float = 600.0
    # Ingress validation at the storage edge: vectorized finite/range
    # checks over each RolloutBatch's obs/rew columns before epoch
    # admission. Poisoned frames are dropped + counted
    # (storage-poisoned-frames) and strike their wid's quarantine counter.
    # Off = one `is None` check on the ingest path.
    ingress_validate: bool = False
    # Absolute-value bound for the ingress range check (observations and
    # rewards beyond it are treated as poisoned even when finite).
    ingress_abs_max: float = 1e6
    # Poisoned frames from one wid before it is quarantined (frames
    # dropped under storage-quarantined-frames, lease flagged).
    quarantine_strikes: int = 3
    # Quarantine cooldown: after this many seconds without a new poisoned
    # frame, the wid's next CLEAN frame clears the quarantine and resets
    # its strikes (un-quarantine on clean re-probe).
    quarantine_clear_s: float = 2.0
    # ---- telemetry plane (tpu_rl.obs) ----
    # Learning-dynamics diagnostics (tpu_rl.obs.learn): every train_step
    # additionally returns an in-jit `diag` pytree (entropy, approx-KL,
    # clip/rho/c rates, importance-weight ESS, advantage moments, value
    # explained-variance, per-module grad norms, update/param norm) which
    # the learner accumulates ON DEVICE — bucketed by the batch's policy
    # staleness — and publishes as `learner-diag-*` gauges plus a
    # result_dir/learn.jsonl timeline at the loss-log cadence. Guard-style
    # bit-identity contract: diag on/off never changes a bit of params or
    # opt state (pinned per algo in tests). Off = the algos return exactly
    # the pre-diag metrics dict and no accumulator exists.
    learn_diag: bool = True
    # HTTP port for the storage-side exporter serving Prometheus text at
    # /metrics and staleness-aware liveness at /healthz. 0 = no server, no
    # socket. The plane as a whole (registries, Telemetry frames, the
    # aggregator) activates iff `telemetry_enabled` — see the property.
    telemetry_port: int = 0
    # Wall-clock period between a role's Telemetry snapshots. Emission is on
    # the clock, not on episode completion, so idle/stuck workers stay
    # visible to /healthz.
    telemetry_interval_s: float = 5.0
    # TelemetryAggregator staleness window: a source silent longer than this
    # is reported dead by /healthz. Should comfortably exceed
    # telemetry_interval_s — the stat channel is best-effort PUB/SUB and one
    # lost frame must not flap liveness.
    telemetry_stale_s: float = 30.0
    # TraceRecorder ring capacity (completed learner-timeline spans kept for
    # the Chrome trace export at result_dir/trace.json). The recorder only
    # exists when result_dir is set.
    trace_capacity: int = 4096
    # Declarative SLO rules evaluated over aggregator snapshots each
    # telemetry tick, e.g.
    # "p99:inference-rtt<5ms@window=30s,gauge:learner-mfu>0.002,
    #  rate:transport-rejected-frames<1/s".
    # Grammar and semantics: tpu_rl/obs/slo.py. Served at /slo on the
    # telemetry HTTP port (200 while passing, 503 on a hard failure) and
    # written to result_dir/slo.json at shutdown. None = no engine
    # constructed, no per-tick cost.
    slo_spec: str | None = None
    # Fail-the-run exit gate: when the final SLO verdict at storage
    # shutdown has any hard-failing rule, the storage child exits nonzero
    # so smokes/CI fail loudly instead of averaging over a breached run.
    slo_fail_run: bool = False
    # ---- run-history plane (tpu_rl.obs.history) ----
    # Where the embedded time-series store lives. None = result_dir/history
    # (the default wiring); set explicitly to split history from the other
    # run artifacts. The store exists iff telemetry_enabled AND one of the
    # two paths resolves — off costs one `is None` check per exporter tick.
    history_dir: str | None = None
    # Active-chunk rotation period: one chunk-<unix_ms>.jsonl file per this
    # many seconds of samples. Smaller = finer-grained GC + smaller torn-
    # crash exposure; larger = fewer files for long queries to open.
    history_chunk_s: float = 60.0
    # Retention horizon: on every rotation, chunks whose coverage ended
    # more than this long ago are deleted. Disk is bounded by
    # retention_s/chunk_s files regardless of run length.
    history_retention_s: float = 3600.0
    # ---- population plane (tpu_rl.population) ----
    # PBT search-space + schedule grammar, e.g.
    # "lr:log[1e-4,1e-2] entropy_coef:lin[0,0.05] perturb=1.2,0.8
    #  interval=200u k=4 quantile=0.25". Whitespace-separated clauses:
    # sampled dimensions (field:log/lin/choice[...]) plus schedule knobs
    # (perturb factors, eval interval in member updates 'u' or wall seconds
    # 's', truncation quantile, population size k, fitness metric). Grammar
    # and semantics: tpu_rl/population/spec.py. Parse-checked (including
    # the searchable-field rule: sampled fields must be numeric and
    # fingerprint-exempt) at config load, like chaos_spec.
    pop_spec: str | None = None
    # Base seed for the population plane. Member seeds, initial sampling
    # and exploit mutations all derive via fold_in(pop_seed, member_idx,
    # ...), so identical (pop_spec, pop_seed) reproduce identical
    # populations.
    pop_seed: int = 0
    # ---- autopilot plane (tpu_rl.autopilot) ----
    # Closed-loop autoscaling rules mapping fleet health signals to
    # scale/respawn actions, e.g.
    # "scale_out:replicas?burn:inference-rtt>0.5@sustain=3@cooldown=10s@max=4,
    #  scale_in:replicas?burn:inference-rtt<0.05@sustain=8@min=1,
    #  respawn:worker?straggler:score>8@cooldown=60s,limit=6/60s".
    # Grammar and anti-flap semantics (sustain/cooldown/hysteresis/bounds/
    # rate limit): tpu_rl/autopilot/policy.py. Parse-checked at config
    # load, like chaos_spec/pop_spec. None = no engine, no controller.
    autopilot_spec: str | None = None
    # Seconds between autopilot control ticks (scrape -> decide -> actuate).
    autopilot_poll_s: float = 1.0
    # Grace between a scale-in decision and the replica kill, so in-flight
    # requests (ms-scale) complete; clients hedge over the tail.
    autopilot_drain_s: float = 0.5
    # Rollout-lineage sampling: every Nth worker tick ships a 28-byte trace
    # context (wid, seq, trace id, send timestamp) as an optional THIRD wire
    # part; each hop (worker, manager, storage, assembler, learner) records
    # a span keyed by the trace id, and tpu_rl.obs.merge joins the dumps
    # into result_dir/fleet_trace.json with linked Perfetto arrows. 0 = off:
    # no trailer is ever attached and every hop's trace branch reduces to a
    # single truthiness/length check (same cost model as the telemetry
    # plane's `is None`).
    trace_sample_n: int = 0

    # ---- runtime-derived (filled by the runner, not the JSON) ----
    obs_shape: tuple[int, ...] = (4,)
    action_space: int = 2
    is_continuous: bool = False

    @classmethod
    def from_json(cls, path: str | os.PathLike, **overrides: Any) -> "Config":
        with open(path) as f:
            raw = json.load(f)
        return cls.from_dict({**raw, **overrides})

    @classmethod
    def from_dict(cls, raw: dict[str, Any]) -> "Config":
        names = {f.name for f in dataclasses.fields(cls)}
        kwargs = {k: v for k, v in raw.items() if k in names}
        # JSON has no tuples; re-tuple the tuple-typed fields so a config
        # that round-trips through to_json/from_json compares equal (==) to
        # the original — the population controller relies on this when it
        # respawns members from rewritten config.json files.
        for k in ("obs_shape", "value_target_clip"):
            if isinstance(kwargs.get(k), list):
                kwargs[k] = tuple(kwargs[k])
        cfg = cls(**kwargs)
        cfg.validate()
        return cfg

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self, path: str | os.PathLike) -> None:
        """Write this config as a parameters.json-shaped file — the exact
        shape ``from_json`` loads, completing the round trip. Written
        crash-atomically (tmp + ``os.replace``) because the population
        controller rewrites a live member's config.json on exploit: a
        member respawning mid-rewrite must read either the old or the new
        config, never a torn one."""
        path = os.fspath(path)
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, indent=2)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)

    def validate(self) -> None:
        assert self.seq_len >= 2, "seq_len must be >= 2 (losses bootstrap from t+1)"
        assert self.batch_size >= 1
        assert self.buffer_size >= self.batch_size
        assert 0.0 <= self.gamma <= 1.0
        assert 0.0 <= self.lmbda <= 1.0
        # Structural/positivity gates for the remaining numeric knobs —
        # every Config field is either read here or exempted (with a reason)
        # in tools/analysis/checks/drift.py's CONFIG_VALIDATE_EXEMPT.
        assert self.height >= 1 and self.width >= 1, (self.height, self.width)
        assert self.hidden_size >= 1, self.hidden_size
        assert self.n_heads >= 1 and self.n_layers >= 1, (
            self.n_heads, self.n_layers,
        )
        assert self.act_ctx >= 0, self.act_ctx
        assert self.time_horizon >= 1, self.time_horizon
        assert self.reward_scale != 0.0, (
            "reward_scale 0 zeroes every reward — no learning signal"
        )
        assert self.eps_clip > 0, self.eps_clip
        assert self.alpha > 0, self.alpha
        assert 0.0 < self.tau <= 1.0, self.tau
        assert self.alpha_min >= 0, self.alpha_min
        assert self.alpha_lr is None or self.alpha_lr > 0, self.alpha_lr
        assert 0.0 < self.rho_min <= self.rho_bar, (self.rho_min, self.rho_bar)
        assert self.c_bar > 0, self.c_bar
        assert self.coef_eta > 0, self.coef_eta
        assert self.K_epoch >= 1, self.K_epoch
        assert self.lr > 0, self.lr
        assert self.max_grad_norm > 0, self.max_grad_norm
        assert self.profile_start >= 0, self.profile_start
        assert self.profile_steps >= 1, self.profile_steps
        assert self.mesh_data >= 1, self.mesh_data
        assert self.worker_step_sleep >= 0, self.worker_step_sleep
        assert self.rollout_lag_sec > 0, self.rollout_lag_sec
        assert self.compute_dtype in (
            "float32",
            "bfloat16",
        ), f"compute_dtype must be float32 or bfloat16, got {self.compute_dtype!r}"
        assert self.model in ARCH_CHECKS or self.model in ("lstm", "transformer"), self.model
        if self.model in ARCH_CHECKS:
            assert not is_off_policy(self.algo) and not self.algo.endswith(
                "-Continuous"
            ), f"{self.model} backbone supports the discrete on-policy algorithms"
            assert self.mesh_seq == 1, f"{self.model} has no sequence-parallel path"
            ARCH_CHECKS[self.model](self.arch)
        else:
            assert self.arch is None, (
                f"arch is read by model={sorted(ARCH_CHECKS)} only, "
                f"not {self.model!r}"
            )
        # bfloat16 is wired for both backbones: the transformer via flax
        # module dtype (transformer.py), the LSTM families via
        # LSTMCell.dtype mixed precision (params f32, matmul compute bf16,
        # carry/gates/heads f32 — models/cells.py).
        assert self.attention_impl in (
            "full", "blockwise", "flash", "ring", "ulysses"
        )
        assert self.learner_device in ("auto", "cpu"), self.learner_device
        assert self.worker_num_envs >= 1, self.worker_num_envs
        assert self.env_mode in ("distributed", "colocated"), self.env_mode
        assert self.colocated_envs >= 0, self.colocated_envs
        if self.env_mode == "colocated":
            # Off-policy replay lives in host shared memory (data/shm_ring);
            # the colocated loop is on-device and consumes each rollout once
            # — on-policy by construction. SAC needs the distributed path.
            assert not is_off_policy(self.algo), (
                f"env_mode='colocated' is on-policy only (each fused rollout "
                f"trains once, no replay); {self.algo} needs "
                f"env_mode='distributed'"
            )
            assert not self.need_conv, (
                "colocated mode has no image-env dynamics (tpu_rl.envs)"
            )
            if self.multihost:
                # Static half of the pod divisibility contract: the env
                # batch shards over the global data axis, so it must at
                # least divide by the process count (the full per-device
                # check needs jax.device_count() and runs in ColocatedLoop).
                nproc = int(self.multihost.get("num_processes", 1))
                envs = self.colocated_envs or self.batch_size
                assert nproc >= 1, self.multihost
                assert envs % nproc == 0, (
                    f"colocated env batch ({envs}) not divisible by "
                    f"multihost num_processes ({nproc}) — it shards over "
                    "the global data axis"
                )
        assert self.sebulba_split >= 0, self.sebulba_split
        assert self.sebulba_queue >= 1, self.sebulba_queue
        if self.sebulba_split:
            assert self.env_mode == "colocated", (
                "sebulba_split splits the colocated plane's device groups; "
                "set env_mode='colocated'"
            )
            assert self.multihost is None, (
                "sebulba_split is a per-host (single-process) split; "
                "multihost pod scaling uses the fused Anakin path"
            )
        assert self.act_mode in ("local", "remote"), self.act_mode
        assert self.relay_mode in ("raw", "decode"), self.relay_mode
        assert self.transport in ("tcp", "shm", "auto"), self.transport
        assert self.inference_batch >= 1, self.inference_batch
        assert self.inference_flush_us >= 0, self.inference_flush_us
        assert self.inference_timeout_ms > 0, self.inference_timeout_ms
        assert self.inference_retries >= 0, self.inference_retries
        assert self.inference_reprobe_s >= 0, self.inference_reprobe_s
        assert self.inference_reprobe_max_s >= self.inference_reprobe_s, (
            f"inference_reprobe_max_s ({self.inference_reprobe_max_s}) must "
            f"be >= inference_reprobe_s ({self.inference_reprobe_s})"
        )
        assert self.inference_replicas >= 1, self.inference_replicas
        assert self.inference_hedge_ms >= 0, self.inference_hedge_ms
        assert self.inference_hedge_ms <= self.inference_timeout_ms, (
            f"inference_hedge_ms ({self.inference_hedge_ms}) past the "
            f"request timeout ({self.inference_timeout_ms} ms) can never fire"
        )
        assert self.inference_mesh_data >= 1, self.inference_mesh_data
        assert self.inference_dtype in ("f32", "bf16", "int8"), (
            self.inference_dtype
        )
        assert self.inference_buckets >= 0, self.inference_buckets
        assert self.act_kernel in ("xla", "pallas"), self.act_kernel
        if self.inference_base_port:
            # Explicit replica port range: must fit the port space and must
            # not collide with the telemetry HTTP port (learner/model/worker
            # ports live in MachinesConfig — inference_ports() checks those).
            assert (
                0 < self.inference_base_port
                and self.inference_base_port + self.inference_replicas <= 65536
            ), (
                f"inference replica ports "
                f"[{self.inference_base_port}, "
                f"{self.inference_base_port + self.inference_replicas}) "
                f"fall outside the port space"
            )
            assert not (
                self.inference_base_port
                <= self.telemetry_port
                < self.inference_base_port + self.inference_replicas
            ), (
                f"telemetry_port {self.telemetry_port} collides with the "
                f"inference replica port range "
                f"[{self.inference_base_port}, "
                f"{self.inference_base_port + self.inference_replicas})"
            )
        assert self.heartbeat_timeout_s > 0, self.heartbeat_timeout_s
        assert self.startup_grace_s >= 0, self.startup_grace_s
        assert self.supervise_poll_s > 0, self.supervise_poll_s
        assert self.max_restarts >= 0, self.max_restarts
        assert self.restart_window_s > 0, self.restart_window_s
        assert self.restart_backoff_s >= 0, self.restart_backoff_s
        assert self.restart_backoff_max_s >= 0, self.restart_backoff_max_s
        assert self.rebroadcast_idle_s >= 0, self.rebroadcast_idle_s
        assert self.loss_log_interval >= 1, self.loss_log_interval
        assert self.model_save_interval >= 1, self.model_save_interval
        assert self.ckpt_keep >= 1, (
            f"ckpt_keep must be >= 1 (got {self.ckpt_keep}): GC may never "
            "remove the newest committed checkpoint"
        )
        assert self.membership_lease_s > 0, self.membership_lease_s
        assert self.watchdog_window >= 2, self.watchdog_window
        assert self.watchdog_z > 0, self.watchdog_z
        assert self.watchdog_sustain >= 1, self.watchdog_sustain
        assert self.watchdog_nonfinite >= 1, self.watchdog_nonfinite
        assert self.max_rollbacks >= 1, self.max_rollbacks
        assert self.rollback_window_s > 0, self.rollback_window_s
        assert self.ingress_abs_max > 0, self.ingress_abs_max
        assert self.quarantine_strikes >= 1, self.quarantine_strikes
        assert self.quarantine_clear_s >= 0, self.quarantine_clear_s
        if self.watchdog_enabled:
            # The rollback path restores the PREVIOUS committed checkpoint
            # (the newest may already hold the divergence), so GC must keep
            # at least two; and the nonfinite trigger channel reads the
            # guard counter, so the guards must be on.
            assert self.update_guard, (
                "watchdog_enabled requires update_guard: the nonfinite "
                "trigger channel reads the in-jit guard counter"
            )
            assert self.ckpt_keep >= 2, (
                f"watchdog_enabled requires ckpt_keep >= 2 (got "
                f"{self.ckpt_keep}): rollback restores the previous "
                "committed checkpoint"
            )
        if self.watchdog_diag:
            assert self.watchdog_enabled, (
                "watchdog_diag extends the watchdog's signal set; enable "
                "watchdog_enabled (and its prerequisites) first"
            )
            assert self.learn_diag, (
                "watchdog_diag requires learn_diag: the approx-KL/ESS "
                "signals come from the learning-dynamics diagnostics"
            )
        if self.chaos_spec:
            # Parse-check here so a bad plan fails at config load, not
            # minutes later inside a spawned child. plan.py is stdlib-only,
            # so this import stays cheap.
            from tpu_rl.chaos.plan import FaultPlan

            FaultPlan.parse(self.chaos_spec)
        if self.slo_spec:
            # Same fail-at-load contract as chaos_spec: a typo'd rule dies
            # here, not silently mid-run. slo.py is stdlib + registry math.
            from tpu_rl.obs.slo import parse_slo_spec

            parse_slo_spec(self.slo_spec)
        if self.pop_spec:
            # Same fail-at-load contract again, plus the searchable-field
            # rule: a sampled dimension must name a numeric Config field
            # OUTSIDE FINGERPRINT_FIELDS (mutating a structural field would
            # strand every checkpoint the exploit step copies). spec.py is
            # stdlib-only, so this import stays cheap.
            from tpu_rl.population.spec import PopSpec

            PopSpec.parse(self.pop_spec).check_searchable()
        assert self.pop_seed >= 0, self.pop_seed
        if self.autopilot_spec:
            # Same fail-at-load contract as chaos/slo/pop specs: a typo'd
            # rule dies at config load with the offending clause named.
            # policy.py is stdlib-only, so this import stays cheap.
            from tpu_rl.autopilot.policy import AutopilotSpec

            AutopilotSpec.parse(self.autopilot_spec)
        assert self.autopilot_poll_s > 0, self.autopilot_poll_s
        assert self.autopilot_drain_s >= 0, self.autopilot_drain_s
        assert 0 <= self.telemetry_port < 65536, self.telemetry_port
        assert self.telemetry_interval_s > 0, self.telemetry_interval_s
        assert self.telemetry_stale_s > 0, self.telemetry_stale_s
        assert self.history_chunk_s > 0, self.history_chunk_s
        assert self.history_retention_s >= self.history_chunk_s, (
            f"history_retention_s ({self.history_retention_s}) must cover at "
            f"least one chunk ({self.history_chunk_s}s) — a shorter horizon "
            "would GC every chunk at rotation time"
        )
        assert self.trace_capacity >= 1, self.trace_capacity
        assert self.trace_sample_n >= 0, self.trace_sample_n
        assert self.action_repeat >= 1, self.action_repeat
        assert self.std_floor >= 0.0, (
            f"std_floor must be >= 0 (got {self.std_floor}): a negative floor "
            "makes the Gaussian std negative and log-probs NaN"
        )
        if self.mesh_seq > 1:
            assert self.model == "transformer", (
                "sequence parallelism (mesh_seq>1) requires model='transformer'"
            )
            assert self.attention_impl in ("ring", "ulysses")
            assert self.seq_len % self.mesh_seq == 0, (
                f"seq_len {self.seq_len} not divisible by mesh_seq {self.mesh_seq}"
            )
            if self.attention_impl == "ulysses":
                assert self.n_heads % self.mesh_seq == 0, (
                    f"ulysses needs n_heads ({self.n_heads}) divisible by "
                    f"mesh_seq ({self.mesh_seq})"
                )
        if self.model == "transformer":
            assert not is_off_policy(self.algo), (
                "transformer backbone supports the on-policy algorithms"
            )
        # A continuous env paired with a discrete-only algo would otherwise
        # build DiscreteActorCritic unconditionally (families.py) and fail
        # obscurely downstream; fail fast here instead. (is_continuous is
        # runtime-derived: this check fires on the post-probe replace().
        # Discreteness follows the registry's "-Continuous" naming
        # convention so future algos are covered without editing this list.)
        if self.is_continuous and not self.algo.endswith("-Continuous"):
            raise ValueError(
                f"algo {self.algo!r} is discrete-only but env {self.env!r} "
                "has a continuous action space; use PPO-Continuous or "
                "SAC-Continuous"
            )
        if self.zero_window_carry and self.algo.removesuffix(
            "-Continuous"
        ) in ("PPO", "V-MPO"):  # PPO-Continuous shares ppo.td_target_and_gae
            # Measured, five-run discriminating experiment
            # (CLUSTER_R5_VMPO.md / CLUSTER_R5_PPO.md): the window-carry
            # policy follows the advantage estimator. Zero-init rescues
            # V-trace (IMPALA) from stale-carry value hallucination under
            # async lag, but GAE has no per-step importance correction —
            # the carry-induced value bias shifts every advantage, capping
            # distributed PPO at fleet mean ~25 and flatlining V-MPO at
            # random, while stored carries solved both. Warn, don't raise:
            # single-process/inline training is unaffected by lag.
            import warnings

            warnings.warn(
                f"zero_window_carry=True with {self.algo}: GAE-based "
                "algorithms measurably fail under async lag with zeroed "
                "training carries (capped/flat fleet reward); use stored "
                "carries (zero_window_carry=False) for PPO/V-MPO — "
                "zero-init is the V-trace/IMPALA fix (CLUSTER_R5_PPO.md)",
            )
        assert self.learner_chain >= 1, self.learner_chain
        assert self.learner_prefetch >= 0, (
            f"learner_prefetch must be >= 0 (0 = synchronous feed), "
            f"got {self.learner_prefetch}"
        )
        if self.max_update_data_ratio is not None:
            assert self.max_update_data_ratio > 0, (
                f"max_update_data_ratio must be > 0 (updates per received "
                f"transition), got {self.max_update_data_ratio}"
            )
        if self.learner_chain > 1:
            # Chained dispatch rides make_parallel_train_step's scan; the
            # (data, seq) mesh step and the multihost global-array feed
            # have no chained layout defined (yet) — fail fast.
            assert self.mesh_seq == 1, (
                "learner_chain > 1 is not supported with sequence "
                "parallelism (mesh_seq > 1)"
            )
            assert self.multihost is None, (
                "learner_chain > 1 is not supported with a multihost learner"
            )
            assert self.sebulba_split == 0, (
                "learner_chain > 1 is not supported with a sebulba split"
            )
        if self.sac_reference_alpha and self.target_entropy is not None:
            # The parity branch takes precedence in algos/sac.py; silently
            # ignoring an explicit target would mislead an audit run.
            raise ValueError(
                "sac_reference_alpha=True pins target_entropy to the "
                "reference's +action_space rule; unset target_entropy "
                f"(got {self.target_entropy})"
            )
        if self.value_target_clip is not None:
            lo, hi = self.value_target_clip  # must be a (lo, hi) pair
            assert float(lo) < float(hi), self.value_target_clip
        if self.entropy_anneal is not None:
            a = self.entropy_anneal
            assert "coef" in a, "entropy_anneal needs 'coef' (final entropy_coef)"
            assert ("at" in a) or ("frac" in a), (
                "entropy_anneal needs a switch point: 'at' (absolute update "
                "index) or 'frac' (fraction of the run's update budget)"
            )
            if "frac" in a:
                assert 0.0 < float(a["frac"]) < 1.0, a["frac"]

    @property
    def effective_act_ctx(self) -> int:
        return self.act_ctx or self.seq_len

    @property
    def telemetry_enabled(self) -> bool:
        """The single gate for the telemetry plane: collect iff the metrics
        have somewhere to go — an HTTP scrape port or a result_dir (JSON
        snapshot + tensorboard). Disabled (the default for tests and bare
        runs) means registries, emitters, and the aggregator are never
        constructed: role hot paths guard on ``is None``, so the off state
        adds no per-frame allocations and opens no sockets."""
        return self.telemetry_port > 0 or self.result_dir is not None

    def replace(self, **kw: Any) -> "Config":
        new = dataclasses.replace(self, **kw)
        new.validate()
        return new


@dataclass
class WorkerMachine:
    """One actor machine entry (reference ``utils/machines.json:6-25``)."""

    num_p: int = 2
    manager_ip: str = "127.0.0.1"
    ip: str = "127.0.0.1"
    port: int = 27165


@dataclass
class MachinesConfig:
    """Cluster topology (reference ``utils/machines.json`` via
    ``utils/utils.py:30-44``)."""

    learner_ip: str = "127.0.0.1"
    learner_port: int = 47165
    workers: list[WorkerMachine] = field(default_factory=lambda: [WorkerMachine()])

    @classmethod
    def from_json(cls, path: str | os.PathLike) -> "MachinesConfig":
        with open(path) as f:
            raw = json.load(f)
        return cls.from_dict(raw)

    @classmethod
    def from_dict(cls, raw: dict[str, Any]) -> "MachinesConfig":
        learner = raw.get("learner", {})
        workers = [WorkerMachine(**w) for w in raw.get("workers", [])]
        return cls(
            learner_ip=learner.get("ip", "127.0.0.1"),
            learner_port=int(learner.get("port", 47165)),
            workers=workers or [WorkerMachine()],
        )

    @property
    def model_port(self) -> int:
        """Model-broadcast port = learner_port + 1 (reference
        ``agents/learner.py:88-90``)."""
        return self.learner_port + 1

    @property
    def inference_port(self) -> int:
        """Centralized-inference ROUTER port = learner_port + 2 (the service
        is colocated with the learner, ``runtime/inference_service.py``)."""
        return self.learner_port + 2

    def inference_ports(self, cfg: Config) -> list[int]:
        """Explicit, collision-checked port allocation for the inference
        fleet: ``cfg.inference_replicas`` consecutive ports starting at
        ``cfg.inference_base_port`` (or the legacy ``learner_port + 2``
        convention when unset). Replaces the silent +2 convention for
        N-replica fleets — a range that lands on the learner/model/stat
        ports or any worker manager port fails HERE, at topology load, not
        as an EADDRINUSE minutes later inside a spawned replica."""
        # Delegated to the shared allocator (runtime/portplan.py) since the
        # population plane plans member ports with the same arithmetic;
        # lazy import because portplan duck-types this topology and must
        # not be imported back into config at module level.
        from tpu_rl.runtime.portplan import plan_range, reserved_ports

        base = cfg.inference_base_port or self.inference_port
        return plan_range(
            base,
            cfg.inference_replicas,
            reserved_ports(self, cfg),
            "inference replica",
        )


def default_result_dirs(base: str = "results") -> tuple[str, str]:
    """Timestamped result/model dirs (reference ``utils/utils.py:79-81``)."""
    import datetime

    ts = datetime.datetime.now().strftime("%d%m%Y-%H_%M_%S")
    result_dir = os.path.join(base, ts)
    model_dir = os.path.join(result_dir, "models")
    return result_dir, model_dir
