"""Plain float32 forward of the Nemotron-H policy core: one mixer a layer, in
the order ``arch["hybrid_override_pattern"]`` gives — ``M`` Mamba-2, ``*``
grouped-query attention, ``E`` a sparse-expert block.

Written from the published description (the model's ``config.json`` keys, read
from ``params["arch"]``; Mamba-2 / SSD, arXiv:2405.21060), not from
``tpu_rl/models`` or ``tpu_rl/ops``: the recurrence runs one step at a time
(``reference/granite_hybrid.mamba2``: the same published mixer, handed this
model's widths under the names it reads), attention is dense and masked, and
the experts are a loop over the held ones under a mask — no sort, no grouped
product, no chunks, no kernels, no mixed precision, no flax. It reads only the
parameter tree, so system and reference run on the same seeded weights.
Callers wrap it in ``jax.default_matmul_precision("highest")``.

    x = obs @ W_e + b_e
    per layer:  x = x + mixer(RMSNorm(x))
    logits = log_softmax(h @ W_pi + b_pi);  value = h @ W_v + b_v;  h = RMSNorm(x)

Expert block, for a token ``u``:

    s = sigmoid(u @ W_r)                              every published expert
    chosen = the num_experts_per_tok largest of s + b
    w_e = routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)
    y = relu(u @ W1_shared)^2 @ W2_shared
        + sum over chosen e that are held of  w_e * relu(u @ W1_e)^2 @ W2_e

Departures from the published language model, each the system's too:

- an observation projection (with bias) replaces the token embedding, a policy
  head and a value head (with bias) the LM head; the residual stream is
  float32 (``residual_in_fp32`` is false in the source);
- depth: the layers of the pattern in ``arch``, a cut of the published 52;
- the share: ``arch["expert_parallel"]`` (``published_n_routed_experts``,
  ``chips``, ``rank``) says which ``n_routed_experts`` routed experts are held;
  the router scores all of them and the absent ones' part of ``y`` is left out;
- attention applies no rotary embedding (the ``nemotron_h`` modelling code
  applies none in these layers though the config carries ``rope_theta``);
- the correction bias ``b`` is a fixed leaf: the rule that updates it in
  pre-training is not in ``config.json``;
- an episode seam (``is_fir[t]``) zeroes the state and the convolution's taps
  before ``t``, and attention sees only the query's own episode.

``choices``: per expert layer the experts (B, T, k) to use *instead of* the
reference's own choice — the system's, for the routed comparison: a discrete
choice made on states that differ by rounding cannot be held to a tolerance,
the arithmetic given the choice can. ``forward_routed`` also returns, per
expert layer, the reference's own choice on the states it reached and the
margin between the lowest chosen and the highest unchosen ``s + b``.
``operand_dtype``: round both operands of every projection and expert matmul
to that dtype first (a reading of what a lower precision would give).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference.granite_hybrid import mamba2, rms_norm

KINDS = {"M": "mamba", "*": "attention", "E": "experts"}


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def matmul(x, w, dtype=None):
    return _rounded(x, dtype) @ _rounded(w, dtype)


def _mamba_keys(arch: dict) -> dict:
    """This model's Mamba-2 widths under the names ``mamba2`` reads."""
    return {
        "mamba_n_heads": arch["mamba_num_heads"], "mamba_d_head": arch["mamba_head_dim"],
        "mamba_d_state": arch["ssm_state_size"], "mamba_n_groups": arch["n_groups"],
        "mamba_conv_bias": arch["use_conv_bias"], "rms_norm_eps": arch["layer_norm_epsilon"],
    }


def attention(u, first, p, arch, dtype=None):
    B, T, _ = u.shape
    n_q, n_kv, D = arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    q = matmul(u, p["q_proj"]["kernel"], dtype).reshape(B, T, n_kv, n_q // n_kv, D)
    k = matmul(u, p["k_proj"]["kernel"], dtype).reshape(B, T, n_kv, D)
    v = matmul(u, p["v_proj"]["kernel"], dtype).reshape(B, T, n_kv, D)
    scores = jnp.einsum("btgrd,bsgd->bgrts", q, k) / jnp.sqrt(jnp.float32(D))
    episode = jnp.cumsum(first.astype(jnp.int32), axis=1)
    t = jnp.arange(T)
    mask = (episode[:, :, None] == episode[:, None, :]) & (t[:, None] >= t[None, :])
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    out = jnp.einsum("bgrts,bsgd->btgrd", jax.nn.softmax(scores, axis=-1), v)
    return matmul(out.reshape(B, T, n_q * D), p["o_proj"]["kernel"], dtype)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def experts(u, p, arch, choice=None, dtype=None):
    """``u`` (B, T, d). Returns the block's output and its routing."""
    k = arch["num_experts_per_tok"]
    held = arch["n_routed_experts"]
    first = arch.get("expert_parallel", {}).get("rank", 0) * held
    s = 1.0 / (1.0 + jnp.exp(-(u @ p["router"])))  # the router is float32 in every precision
    biased = s + p["router_bias"]
    ranked = jnp.argsort(-biased, axis=-1, stable=True)
    by_rank = jnp.take_along_axis(biased, ranked, axis=-1)
    own = ranked[..., :k]
    margin = by_rank[..., k - 1] - by_rank[..., k] if biased.shape[-1] > k else None
    if choice is None:
        choice = own
    chosen = jnp.take_along_axis(s, choice, axis=-1)
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)  # norm_topk_prob
    weight = arch["routed_scaling_factor"] * chosen  # (B, T, k)
    y = matmul(relu2(matmul(u, p["shared_in"]["kernel"], dtype)), p["shared_out"]["kernel"], dtype)
    for e in range(held):
        gate = jnp.sum(jnp.where(choice == first + e, weight, 0.0), axis=-1, keepdims=True)
        y = y + gate * matmul(relu2(matmul(u, p["w_in"][e], dtype)), p["w_out"][e], dtype)
    return y, {"choice": own, "margin": margin}


def forward_routed(actor_params, batch: dict, params: dict, choices=None, operand_dtype=None,
                   carry0=None):
    """``batch``: field -> (B, T, width) float32. Returns log-softmax logits
    (B, T, A), value (B, T, 1) and one routing record per expert layer.
    ``carry0``: one ``(state, tail)`` per Mamba layer to start the window from
    (zeros if None)."""
    arch = params["arch"]
    dt = operand_dtype
    p = jax.tree.map(lambda a: a.astype(jnp.float32), actor_params["params"])
    first = batch["is_fir"][..., 0] > 0
    B = first.shape[0]
    eps = arch["layer_norm_epsilon"]
    heads = (arch["mamba_num_heads"], arch["mamba_head_dim"], arch["ssm_state_size"])
    conv_ch = heads[0] * heads[1] + 2 * arch["n_groups"] * heads[2]
    x = matmul(batch["obs"], p["embed"]["kernel"], dt) + p["embed"]["bias"]
    n_mamba, routes = 0, []
    for i, c in enumerate(arch["hybrid_override_pattern"]):
        lp = p[f"layer{i}"]
        u = rms_norm(x, lp["norm"]["scale"], eps)
        if KINDS[c] == "mamba":
            if carry0 is None:
                state0 = jnp.zeros((B, *heads))
                tail0 = jnp.zeros((B, arch["conv_kernel"] - 1, conv_ch))
            else:
                state0, tail0 = carry0[n_mamba]
            n_mamba += 1
            if dt is None:
                mixed, _ = mamba2(u, first, lp["mamba"], _mamba_keys(arch), state0, tail0)
            else:
                mixed, _ = _mamba2_rounded(u, first, lp["mamba"], arch, state0, tail0, dt)
        elif KINDS[c] == "attention":
            mixed = attention(u, first, lp["attention"], arch, dt)
        else:
            forced = None if choices is None else choices[len(routes)]
            mixed, route = experts(u, lp["experts"], arch, forced, dt)
            routes.append(route)
        x = x + mixed
    h = rms_norm(x, p["norm_f"]["scale"], eps)
    logits = h @ p["logits"]["kernel"] + p["logits"]["bias"]
    return jax.nn.log_softmax(logits), h @ p["value"]["kernel"] + p["value"]["bias"], routes


def _mamba2_rounded(u, first, mp, arch, state0, tail0, dtype):
    """``mamba2`` with both operands of its two projections rounded. The
    out-projection's input arises inside it, so it runs with an identity there
    and the projection is applied here."""
    inner = arch["mamba_num_heads"] * arch["mamba_head_dim"]
    inside = {**mp, "in_proj": {"kernel": _rounded(mp["in_proj"]["kernel"], dtype)},
              "out_proj": {"kernel": jnp.eye(inner, dtype=jnp.float32)}}
    y, carry = mamba2(_rounded(u, dtype), first, inside, _mamba_keys(arch), state0, tail0)
    return matmul(y, mp["out_proj"]["kernel"], dtype), carry


def forward(actor_params, batch: dict, params: dict, choices=None, carry0=None):
    """Log-softmax logits (B, T, A) and value (B, T, 1)."""
    return forward_routed(actor_params, batch, params, choices, carry0=carry0)[:2]
