"""Plain float32 forward of the SmallThinker policy core: every layer is
grouped-query attention and a sparse-expert block; the published layouts make
a layer's attention global without positions or a sliding window with rotary
ones.

Written from the published description (the model's ``config.json`` keys, read
from ``params["arch"]``, and the catalog's account of the family), not from
``tpu_rl/models`` or ``tpu_rl/ops``: attention is dense and masked, a block of
queries at a time against every key (a float32 ``(28, T, T)`` score tensor at
T = 16,384 is 30 GB; 1,024 queries are 1.9 GB); the experts are a loop over
the held ones under a mask — no window tiles, no sort, no grouped product, no
chunks, no kernels, no mixed precision, no flax. It reads only the parameter
tree, so system and reference run on the same seeded weights. Callers wrap it
in ``jax.default_matmul_precision("highest")``.

    x = obs @ W_e + b_e
    per layer i:
        a = RMSNorm_1(x)                                  eps 1e-6
        q, k, v = a Wq, a Wk, a Wv                        28 : 4 heads of 128, no bias
        if rope_layout[i]:  q, k = RoPE(q, pos), RoPE(k, pos)
        mask = causal and same episode and (q_pos - k_pos < window if sliding_window_layout[i])
        x = x + softmax(q k^T / sqrt(128) + mask) v Wo
        h = RMSNorm_2(x)
        l = a W_router                                    the state BEFORE attention
        E = the moe_num_active_primary_experts largest of l
        w = softmax(l)[E] / sum(softmax(l)[E])            apply_softmax, norm_topk_prob
        x = x + sum over e in E that are held of  w_e W_out,e (relu(W_gate,e h) * W_in,e h)
    logits = log_softmax(f @ W_pi + b_pi);  value = f @ W_v + b_v;  f = RMSNorm(x)

Taken from the catalog's description of the family, not from a config key
(the configuration file lists each under ``assumed``): the relu gate ("sparse
ReGLU"), the router's input before attention, rotate-half RoPE over the whole
head without scaling, a window that counts the query itself, no bias.

Departures from the published language model, each the system's too:

- an observation projection (with bias) replaces the token embedding, a policy
  head and a value head (with bias) the LM head; the residual stream is float32;
- depth: ``num_hidden_layers`` layers, a cut of the published 52;
- the share: ``arch["expert_parallel"]`` (``published_n_routed_experts``,
  ``chips``, ``rank``) says which ``moe_num_primary_experts`` experts are held;
  the router scores all of them and the absent ones' part is left out;
- ``pos`` is the step's index in its **episode** (the sequence a language
  model would see); attention sees only the query's own episode.

``choices``: per layer the experts (B, T, k) to use *instead of* the
reference's own choice — the system's, for the routed comparison.
``forward_routed`` also returns, per layer, the reference's own choice on the
states it reached and the margin between its lowest chosen and its highest
unchosen logit. ``operand_dtype``: round both operands of every projection and
expert matmul to that dtype first (a reading of what a lower precision gives).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def matmul(x, w, dtype=None):
    return _rounded(x, dtype) @ _rounded(w, dtype)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def episode_positions(first):
    """``first`` (B, T) bool. Each step's episode id, and its index in its
    episode (the window's first step opens an episode whatever its flag)."""
    T = first.shape[1]
    t = jnp.arange(T)
    episode = jnp.cumsum(first.astype(jnp.int32), axis=1)
    opens = first.at[:, 0].set(True)
    began = jax.lax.cummax(jnp.where(opens, t, 0), axis=1)
    return episode, t - began


def rotary(x, pos, theta):
    """``x`` (B, T, H, D), ``pos`` (B, T): ``x cos + rotate_half(x) sin`` with
    the angles of a head laid out ``[f_0 .. f_{D/2-1}, f_0 .. f_{D/2-1}]``."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = pos.astype(jnp.float32)[..., None] * inv_freq  # (B, T, D/2)
    angle = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def attention(a, first, p, arch, index, dtype=None):
    B, T, _ = a.shape
    n_q, n_kv, D = arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    q = matmul(a, p["q_proj"]["kernel"], dtype).reshape(B, T, n_q, D)
    k = matmul(a, p["k_proj"]["kernel"], dtype).reshape(B, T, n_kv, D)
    v = matmul(a, p["v_proj"]["kernel"], dtype).reshape(B, T, n_kv, D)
    episode, pos = episode_positions(first)
    if arch["rope_layout"][index]:
        q, k = rotary(q, pos, arch["rope_theta"]), rotary(k, pos, arch["rope_theta"])
    window = arch["sliding_window_size"] if arch["sliding_window_layout"][index] else None
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)
    t = jnp.arange(T)

    def queries(start):
        """The ``block`` queries from ``start`` on against every key."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        qb = qb.reshape(B, block, n_kv, n_q // n_kv, D)  # consecutive query heads share a k/v head
        at = start + jnp.arange(block)
        mine = jax.lax.dynamic_slice_in_dim(episode, start, block, axis=1)
        mask = (mine[:, :, None] == episode[:, None, :]) & (at[:, None] >= t[None, :])
        if window is not None:
            mask &= at[:, None] - t[None, :] < window
        scores = jnp.einsum("btgrd,bsgd->bgrts", qb, k) / jnp.sqrt(jnp.float32(D))
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        out = jnp.einsum("bgrts,bsgd->btgrd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(B, block, n_q * D)

    out = jax.lax.map(queries, jnp.arange(0, T, block))  # (T / block, B, block, n_q D)
    out = out.transpose(1, 0, 2, 3).reshape(B, T, n_q * D)
    return matmul(out, p["o_proj"]["kernel"], dtype)


def experts(h, scored, p, arch, choice=None, dtype=None):
    """``h`` (B, T, d): what the experts compute on; ``scored`` (B, T, d):
    what the router reads. Returns the block's output and its routing."""
    k = arch["moe_num_active_primary_experts"]
    held = arch["moe_num_primary_experts"]
    first = arch.get("expert_parallel", {}).get("rank", 0) * held
    logit = scored @ p["router"]  # the router is float32 in every precision
    ranked = jnp.argsort(-logit, axis=-1, stable=True)
    by_rank = jnp.take_along_axis(logit, ranked, axis=-1)
    own = ranked[..., :k]
    margin = by_rank[..., k - 1] - by_rank[..., k] if logit.shape[-1] > k else None
    if choice is None:
        choice = own
    chosen = jnp.take_along_axis(jax.nn.softmax(logit, axis=-1), choice, axis=-1)
    weight = chosen / jnp.sum(chosen, axis=-1, keepdims=True)  # norm_topk_prob

    def add_expert(y, expert):
        """One held expert applied to every step, under its weight (0 where
        the step did not choose it). A ``scan`` and not a Python loop: one
        body to compile for the sixteen, the same sum in the same order."""
        e, w_gate, w_in, w_out = expert
        gate = jnp.sum(jnp.where(choice == first + e, weight, 0.0), axis=-1, keepdims=True)
        hidden = jax.nn.relu(matmul(h, w_gate, dtype)) * matmul(h, w_in, dtype)
        return y + gate * matmul(hidden, w_out, dtype), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h), (jnp.arange(held), p["w_gate"], p["w_in"], p["w_out"]))
    return y, {"choice": own, "margin": margin}


def forward_routed(actor_params, batch: dict, params: dict, choices=None, operand_dtype=None):
    """``batch``: field -> (B, T, width) float32. Returns log-softmax logits
    (B, T, A), value (B, T, 1) and one routing record per layer."""
    arch = params["arch"]
    dt = operand_dtype
    p = jax.tree.map(lambda a: a.astype(jnp.float32), actor_params["params"])
    first = batch["is_fir"][..., 0] > 0
    eps = arch["rms_norm_eps"]
    x = matmul(batch["obs"], p["embed"]["kernel"], dt) + p["embed"]["bias"]
    routes = []
    for i in range(arch["num_hidden_layers"]):
        lp = p[f"layer{i}"]
        a = rms_norm(x, lp["input_norm"]["scale"], eps)
        x = x + attention(a, first, lp["attention"], arch, i, dt)
        h = rms_norm(x, lp["post_norm"]["scale"], eps)
        mixed, route = experts(h, a, lp["experts"], arch, None if choices is None else choices[i], dt)
        routes.append(route)
        x = x + mixed
    f = rms_norm(x, p["norm_f"]["scale"], eps)
    logits = f @ p["logits"]["kernel"] + p["logits"]["bias"]
    return jax.nn.log_softmax(logits), f @ p["value"]["kernel"] + p["value"]["bias"], routes


def forward(actor_params, batch: dict, params: dict, choices=None):
    """Log-softmax logits (B, T, A) and value (B, T, 1)."""
    return forward_routed(actor_params, batch, params, choices)[:2]
