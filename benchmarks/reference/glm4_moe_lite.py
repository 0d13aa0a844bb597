"""Plain float32 forward of the GLM-4.7-Flash (``glm4_moe_lite``) policy core:
every layer multi-head latent attention, the leading ``first_k_dense_replace``
layers with a dense SwiGLU MLP, the others with a sparse-expert block and its
shared expert.

Written from the published description (the model's ``config.json`` keys, read
from ``params["arch"]``, and the family's published modelling code as the
configuration file's ``assumed`` records it), not from ``tpu_rl/models`` or
``tpu_rl/ops``: latent attention in its **expanded** form — every head's keys
and values are made from the latent and the one rotated key is copied to every
head —, dense and masked, a block of queries at a time against every key (a
float32 ``(20, T, T)`` score tensor at T = 16,384 is 21 GB; 1,024 queries are
1.3 GB); the experts are a loop over the held ones under a mask — no latent
cache, no absorbed products, no sort, no grouped product, no kernels, no mixed
precision, no flax. It reads only the parameter tree, so system and reference
run on the same seeded weights. Callers wrap it in
``jax.default_matmul_precision("highest")``.

    N(x) = x rsqrt(mean x^2 + eps) w                              plain, w starts at 1
    x = obs @ W_e + b_e
    per layer i:
      u = N_1(x)
      c_q = N_q(u W_qa);  q_h = [q_h^nope (d_nope) ; q_h^rope (d_rope)] = (c_q W_qb)_h
      [c_kv ; k^r] = u W_kva;  c_kv = N_kv(c_kv)                  k^r: one key for all heads, not normed
      [k_h^nope (d_nope) ; v_h (d_v)] = (c_kv W_kvb)_h
      q_h^rope, k^r = RoPE(q_h^rope, pos), RoPE(k^r, pos)         over all d_rope features
      k_h = [k_h^nope ; k^r]
      x = x + [softmax(q_h k_h^T / sqrt(d_nope + d_rope) + mask) v_h]_h W_o
      h = N_2(x)
      i < first_k_dense_replace:
        x = x + (silu(h W_gate) * h W_up) W_down
      else:
        s = sigmoid(h W_router)                                   every published expert
        E = the num_experts_per_tok largest of s + b              b: the correction bias
        w_e = routed_scaling_factor s_e / (sum of the chosen s + 1e-20)
        x = x + sum over e in E that are held of  w_e W_out,e (silu(W_gate,e h) * W_in,e h)
              + W_so (silu(W_sg h) * W_si h)                      the shared expert, ungated
    logits = log_softmax(N(x) @ W_pi + b_pi);  value = N(x) @ W_v + b_v

Departures from the published language model, each the system's too:

- an observation projection (with bias) replaces the token embedding, a policy
  head and a value head (with bias) the LM head; the residual stream is float32;
- depth: ``num_hidden_layers`` layers, a cut of the published 47; no
  multi-token-prediction module (it embeds the next token and predicts through
  the LM head: a policy here has neither);
- the share: ``arch["expert_parallel"]`` (``published_n_routed_experts``,
  ``chips``, ``rank``) says which ``n_routed_experts`` experts are held; the
  router scores all of them and the absent ones' part is left out;
- the rotation pairs feature ``i`` with ``i + d_rope / 2`` (rotate-half): the
  published code pairs adjacent features after a permutation of ``W_qb``'s and
  ``W_kva``'s columns, which with seeded weights is the same distribution;
- ``n_group`` 1 and ``topk_group`` 1 make the published group limit the
  identity: there is no group stage;
- the correction bias ``b`` is a fixed leaf: the rule that updates it in
  pre-training is not in ``config.json``;
- ``pos`` is the step's index in its **episode** (the sequence a language
  model would see); attention sees only the query's own episode.

``choices``: per expert layer the experts (B, T, k) to use *instead of* the
reference's own choice — the system's, for the routed comparison.
``forward_routed`` also returns, per expert layer, the reference's own choice
on the states it reached and the margin between its lowest chosen and its
highest unchosen ``s + b``. ``operand_dtype``: round both operands of every
projection and expert matmul to that dtype first (a reading of what a lower
precision gives).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def matmul(x, w, dtype=None):
    return _rounded(x, dtype) @ _rounded(w, dtype)


def norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


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
    """``x`` (B, T, ..., D), ``pos`` (B, T): ``x cos + rotate_half(x) sin``
    with the angles laid out ``[f_0 .. f_{D/2-1}, f_0 .. f_{D/2-1}]``."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = pos.astype(jnp.float32)[..., None] * inv_freq  # (B, T, D/2)
    angle = jnp.concatenate([freqs, freqs], axis=-1)
    angle = angle.reshape(*pos.shape, *(1,) * (x.ndim - 3), D)
    half = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def latent_attention(u, first, p, arch, dtype=None):
    B, T, _ = u.shape
    H, rank = arch["num_attention_heads"], arch["kv_lora_rank"]
    d_nope, d_rope, d_v = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"], arch["v_head_dim"]
    eps = arch["rms_norm_eps"]
    c_q = norm(matmul(u, p["q_a_proj"]["kernel"], dtype), p["q_a_norm"]["scale"], eps)
    q = matmul(c_q, p["q_b_proj"]["kernel"], dtype).reshape(B, T, H, d_nope + d_rope)
    down = matmul(u, p["kv_a_proj"]["kernel"], dtype)
    c_kv, k_rope = norm(down[..., :rank], p["kv_a_norm"]["scale"], eps), down[..., rank:]
    kv = matmul(c_kv, p["kv_b_proj"]["kernel"], dtype).reshape(B, T, H, d_nope + d_v)
    k_nope, v = kv[..., :d_nope], kv[..., d_nope:]
    episode, pos = episode_positions(first)
    theta = arch["rope_theta"]
    q = jnp.concatenate([q[..., :d_nope], rotary(q[..., d_nope:], pos, theta)], axis=-1)
    k_rope = rotary(k_rope, pos, theta)  # (B, T, d_rope): no head axis
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (B, T, H, d_rope))], axis=-1)
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)
    t = jnp.arange(T)

    @jax.checkpoint  # a gradient keeps one block's scores at a time, not every block's
    def queries(start):
        """The ``block`` queries from ``start`` on against every key."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        at = start + jnp.arange(block)
        mine = jax.lax.dynamic_slice_in_dim(episode, start, block, axis=1)
        mask = (mine[:, :, None] == episode[:, None, :]) & (at[:, None] >= t[None, :])
        scores = jnp.einsum("bthd,bshd->bhts", qb, k) / jnp.sqrt(jnp.float32(d_nope + d_rope))
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(B, block, H * d_v)

    out = jax.lax.map(queries, jnp.arange(0, T, block))  # (T / block, B, block, H d_v)
    out = out.transpose(1, 0, 2, 3).reshape(B, T, H * d_v)
    return matmul(out, p["o_proj"]["kernel"], dtype)


def swiglu(h, w_gate, w_in, w_out, dtype=None):
    return matmul(jax.nn.silu(matmul(h, w_gate, dtype)) * matmul(h, w_in, dtype), w_out, dtype)


def experts(h, p, arch, choice=None, dtype=None):
    """``h`` (B, T, d). Returns the block's output (the held routed experts'
    part and the shared expert) and its routing."""
    k = arch["num_experts_per_tok"]
    held = arch["n_routed_experts"]
    first = arch.get("expert_parallel", {}).get("rank", 0) * held
    s = 1.0 / (1.0 + jnp.exp(-(h @ p["router"])))  # the router is float32 in every precision
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

    def add_expert(y, expert):
        """One held expert applied to every step, under its weight (0 where
        the step did not choose it). A ``scan`` and not a Python loop: one
        body to compile for all of them, the same sum in the same order."""
        e, w_gate, w_in, w_out = expert
        gate = jnp.sum(jnp.where(choice == first + e, weight, 0.0), axis=-1, keepdims=True)
        return y + gate * swiglu(h, w_gate, w_in, w_out, dtype), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h), (jnp.arange(held), p["w_gate"], p["w_in"], p["w_out"]))
    shared = swiglu(
        h, *(p[leaf]["kernel"] for leaf in ("shared_gate", "shared_in", "shared_out")), dtype)
    return y + shared, {"choice": own, "margin": margin}


def forward_routed(actor_params, batch: dict, params: dict, choices=None, operand_dtype=None):
    """``batch``: field -> (B, T, width) float32. Returns log-softmax logits
    (B, T, A), value (B, T, 1) and one routing record per expert layer."""
    arch = params["arch"]
    dt = operand_dtype
    p = jax.tree.map(lambda a: a.astype(jnp.float32), actor_params["params"])
    first = batch["is_fir"][..., 0] > 0
    eps = arch["rms_norm_eps"]
    x = matmul(batch["obs"], p["embed"]["kernel"], dt) + p["embed"]["bias"]
    routes = []
    for i in range(arch["num_hidden_layers"]):
        lp = p[f"layer{i}"]
        u = norm(x, lp["input_norm"]["scale"], eps)
        x = x + latent_attention(u, first, lp["attention"], arch, dt)
        h = norm(x, lp["post_norm"]["scale"], eps)
        if i < arch["first_k_dense_replace"]:
            x = x + swiglu(
                h, *(lp[leaf]["kernel"] for leaf in ("gate_proj", "up_proj", "down_proj")), dt)
            continue
        forced = None if choices is None else choices[len(routes)]
        mixed, route = experts(h, lp["experts"], arch, forced, dt)
        routes.append(route)
        x = x + mixed
    f = norm(x, p["norm_f"]["scale"], eps)
    logits = f @ p["logits"]["kernel"] + p["logits"]["bias"]
    return jax.nn.log_softmax(logits), f @ p["value"]["kernel"] + p["value"]["bias"], routes


def forward(actor_params, batch: dict, params: dict, choices=None):
    """Log-softmax logits (B, T, A) and value (B, T, 1)."""
    return forward_routed(actor_params, batch, params, choices)[:2]
