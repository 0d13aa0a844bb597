"""Plain float32 forward of the Ling-3.0-flash (``ling_flash``) policy core:
Kimi Delta Attention (the delta rule with a decay per key channel) in five
layers of six and multi-head latent attention in the sixth, a leading dense
SwiGLU MLP, then sparse experts under a group-limited sigmoid router with one
shared expert.

Written from the published description (the model's ``config.json`` keys, read
from ``params["arch"]``; the Kimi Linear paper's recurrence, arXiv:2510.26692;
DeepSeek-V3's group-limited router, arXiv:2412.19437, as the Ling family runs
it), as the configuration file's ``assumed`` records each reading, not from
``tpu_rl/models`` or ``tpu_rl/ops``: the delta rule is the **step recurrence**
under ``lax.scan`` — no chunks, no triangular inverse, no factored decays, no
exponent of a positive number anywhere —; latent attention in its expanded
form, dense and masked, a block of queries at a time against every key; the
experts a loop over the held ones under a mask — no sort, no grouped product,
no kernels, no mixed precision, no flax. It reads only the parameter tree, so
system and reference run on the same seeded weights. Callers wrap it in
``jax.default_matmul_precision("highest")``.

    N(x) = x rsqrt(mean x^2 + eps) w                              plain, w starts at 1
    x = obs @ W_e + b_e
    per layer j (published layer i = j + layer_offset):
      u = N_1(x)
      (i + 1) % layer_group_size != 0:                            Kimi Delta Attention
        [q, k, v] = silu(conv(u W_qkv))                           depthwise, causal, 4 taps, within the episode
        q_h, k_h = q_h / |q_h| / sqrt(d), k_h / |k_h|             per head, eps 1e-6 under the root
        a = u W_a + dt_bias;  alpha = exp(bound * sigmoid(exp(A_log_h) a))   a decay a head and key channel
        [b, z] = u W_bz;  beta = sigmoid(b)                       one a head
        S = Diag(alpha_t) S;  delta = beta_t (v_t - S^T k_t);  S = S + k_t delta^T;  o_t = S^T q_t
                                                                  S := 0 where an episode starts
        x = x + [sigmoid(z_h) * o_h rsqrt(mean o_h^2 + eps) w_n]_h W_o
      else:                                                       latent attention
        q_h = [q_h^nope (d_nope) ; q_h^rope (d_rope)] = (u W_q)_h no query latent
        [c ; k^r] = u W_kva;  c = N_kv(c)                         k^r: one key for all heads, not normed
        [k_h^nope (d_nope) ; v_h (d_v)] = (c W_kvb)_h             d_v != d_nope + d_rope
        q_h^rope, k^r = RoPE(q_h^rope, pos), RoPE(k^r, pos)
        x = x + [sigmoid((u W_g)_h) * softmax(q_h [k_h^nope ; k^r]^T / sqrt(d_nope + d_rope) + mask) v_h]_h W_o
      h = N_2(x)
      j < first_k_dense_replace:
        x = x + (silu(h W_gate) * h W_up) W_down
      else:
        s = sigmoid(h W_router);  t = s + b                       every published expert; b: the expert bias
        G = the topk_group groups (of n_group, consecutive ids) with the largest sum of their two largest t
        E = the num_experts_per_tok largest t among the experts of G
        w_e = routed_scaling_factor s_e / (sum of the chosen s + 1e-20)
        x = x + sum over e in E that are held of  w_e W_out,e (silu(W_gate,e h) * W_in,e h)
              + W_so (silu(W_sg h) * W_si h)                      the shared expert, ungated
    logits = log_softmax(N(x) @ W_pi + b_pi);  value = N(x) @ W_v + b_v

Departures from the published model, each the system's too (the configuration
file's ``assumed`` has the reasons):

- an observation projection (with bias) replaces the token and patch
  embeddings, a policy head and a value head (with bias) the LM head; no vision
  tower, no multi-token-prediction module; the residual stream is float32;
- depth: ``num_hidden_layers`` layers from published layer ``layer_offset`` on;
- the share: ``arch["expert_parallel"]`` says which ``num_experts`` experts are
  held; the router scores all of them and the absent ones' part is left out;
- the bounded gate's form, ``kda_lower_bound * sigmoid(exp(A_log) (W_a u +
  dt_bias))``: the config fixes the bound (``kda_safe_gate``,
  ``kda_lower_bound``), not the form;
- the head-wise gates multiply a head's output after the per-head norm (KDA)
  and before ``W_o`` (both mixers); ``use_qk_norm`` is the l2 norm of q and k in
  a KDA layer and adds nothing to the latent layer beyond the latent's norm;
- ``[q, k, v]`` are one projection's columns laid out flat, with one
  convolution over all of them; the rotation pairs feature ``i`` with
  ``i + d_rope / 2``: with seeded weights the published layouts are the same
  distribution;
- the expert bias ``b`` is a fixed leaf: the rule that updates it in
  pre-training is not in ``config.json``; no SwiGLU clamp (the published limits
  are 0 at every layer of the cut);
- ``pos`` is the step's index in its **episode**; attention and the
  recurrence see only the step's own episode.

``choices``: per expert layer the experts (B, T, k) to use *instead of* the
reference's own choice — the system's, for the routed comparison.
``forward_routed`` also returns, per expert layer, the reference's own choice
on the states it reached and its margin: the smaller of the gap between its
lowest chosen and highest unchosen ``t`` among the kept groups' experts and the
gap between its last kept and first dropped group score. ``operand_dtype``:
round both operands of every projection and expert matmul to that dtype first
(a reading of what a lower precision gives).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024
L2_EPS = 1e-6


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


def conv_in_episode(x, episode, weight):
    """``y_t = sum_j weight[K-1-j] x_{t-j}`` over the taps ``j`` whose step
    lies in the window and in step ``t``'s episode. ``x`` (B, T, C)."""
    K, T = weight.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(K):
        back = jnp.pad(x, ((0, 0), (j, 0), (0, 0)))[:, :T]
        theirs = jnp.pad(episode, ((0, 0), (j, 0)), constant_values=-1)[:, :T]
        y = y + jnp.where((theirs == episode)[..., None], back, 0.0) * weight[K - 1 - j]
    return y


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def delta_attention(u, first, p, arch, dtype=None):
    B, T, _ = u.shape
    H, d = arch["num_attention_heads"], arch["head_dim"]
    width = H * d
    qkv = matmul(u, p["in_proj_qkv"]["kernel"], dtype)
    a = (matmul(u, p["a_proj"]["kernel"], dtype) + p["dt_bias"]).reshape(B, T, H, d)
    bz = matmul(u, p["in_proj_bz"]["kernel"], dtype)
    beta, z = jax.nn.sigmoid(bz[..., :H]), bz[..., H:]
    rate = jnp.exp(p["A_log"])[:, None]  # a head's, over its key channels
    alpha = jnp.exp(arch["kda_lower_bound"] * jax.nn.sigmoid(rate * a))  # in (e^bound, 1)
    episode, _ = episode_positions(first)
    qkv = jax.nn.silu(conv_in_episode(qkv, episode, p["conv_weight"]))
    q = unit(qkv[..., :width].reshape(B, T, H, d)) / jnp.sqrt(jnp.float32(d))
    k = unit(qkv[..., width: 2 * width].reshape(B, T, H, d))
    v = qkv[..., 2 * width:].reshape(B, T, H, d)

    def step(S, at):
        """``S`` (B, H, d_k, d_v): the state after the step before."""
        q_t, k_t, v_t, alpha_t, beta_t, first_t = at
        S = jnp.where(first_t[:, None, None, None], 0.0, S) * alpha_t[..., None]
        # S^T k and S^T q as sums over the key axis: float32 on the vector unit, no matmul passes
        delta = beta_t[..., None] * (v_t - jnp.sum(S * k_t[..., :, None], axis=-2))
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.sum(S * q_t[..., :, None], axis=-2)

    steps_first = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, alpha, beta, first))
    _, o = jax.lax.scan(step, jnp.zeros((B, H, d, d), jnp.float32), steps_first)
    o = jnp.moveaxis(o, 0, 1)  # (B, T, H, d)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + arch["rms_norm_eps"])
    y = o * p["norm_scale"] * jax.nn.sigmoid(z)[..., None]
    return matmul(y.reshape(B, T, width), p["o_proj"]["kernel"], dtype)


def latent_attention(u, first, p, arch, dtype=None):
    B, T, _ = u.shape
    H, rank = arch["num_attention_heads"], arch["kv_lora_rank"]
    d_nope, d_rope, d_v = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"], arch["v_head_dim"]
    q = matmul(u, p["q_proj"]["kernel"], dtype).reshape(B, T, H, d_nope + d_rope)
    down = matmul(u, p["kv_a_proj"]["kernel"], dtype)
    c = norm(down[..., :rank], p["kv_a_norm"]["scale"], arch["rms_norm_eps"])
    k_rope = down[..., rank:]
    kv = matmul(c, p["kv_b_proj"]["kernel"], dtype).reshape(B, T, H, d_nope + d_v)
    k_nope, v = kv[..., :d_nope], kv[..., d_nope:]
    gate = jax.nn.sigmoid(matmul(u, p["g_proj"]["kernel"], dtype))  # (B, T, H)
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
        return jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)

    out = jax.lax.map(queries, jnp.arange(0, T, block))  # (T / block, B, block, H, d_v)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, T, H, d_v) * gate[..., None]
    return matmul(out.reshape(B, T, H * d_v), p["o_proj"]["kernel"], dtype)


def swiglu(h, w_gate, w_in, w_out, dtype=None):
    return matmul(jax.nn.silu(matmul(h, w_gate, dtype)) * matmul(h, w_in, dtype), w_out, dtype)


def group_limited_choice(t, k: int, n_group: int, topk_group: int):
    """``t`` (..., E) the biased scores. Returns the ``k`` chosen experts
    (..., k), best first, and the margin (...,): the smaller of the gap
    between the last chosen and the first unchosen ``t`` among the kept groups'
    experts and the gap between the last kept and the first dropped group's
    score (a group's score: the sum of its two largest ``t``)."""
    E = t.shape[-1]
    size = E // n_group
    in_groups = jnp.sort(t.reshape(*t.shape[:-1], n_group, size), axis=-1)
    group_score = in_groups[..., -1] + (in_groups[..., -2] if size > 1 else 0.0)
    group_rank = jnp.argsort(-group_score, axis=-1, stable=True)
    kept = group_rank[..., :topk_group]  # (..., topk_group)
    survives = jnp.any(
        (jnp.arange(E) // size)[:, None] == kept[..., None, :], axis=-1)  # (..., E)
    masked = jnp.where(survives, t, -jnp.inf)
    ranked = jnp.argsort(-masked, axis=-1, stable=True)
    by_rank = jnp.take_along_axis(masked, ranked, axis=-1)
    margin = by_rank[..., k - 1] - by_rank[..., k] if E > k else jnp.full(t.shape[:-1], jnp.inf)
    if topk_group < n_group:
        scores = jnp.take_along_axis(group_score, group_rank, axis=-1)
        margin = jnp.minimum(margin, scores[..., topk_group - 1] - scores[..., topk_group])
    return ranked[..., :k], margin


def experts(h, p, arch, choice=None, dtype=None):
    """``h`` (B, T, d). Returns the block's output (the held routed experts'
    part and the shared expert) and its routing."""
    k = arch["num_experts_per_tok"]
    held = arch["num_experts"]
    first = arch.get("expert_parallel", {}).get("rank", 0) * held
    s = 1.0 / (1.0 + jnp.exp(-(h @ p["router"])))  # the router is float32 in every precision
    own, margin = group_limited_choice(
        s + p["router_bias"], k, arch["n_group"], arch["topk_group"])
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
    for j in range(arch["num_hidden_layers"]):
        lp = p[f"layer{j}"]
        u = norm(x, lp["input_norm"]["scale"], eps)
        if (j + arch.get("layer_offset", 0) + 1) % arch["layer_group_size"] == 0:
            x = x + latent_attention(u, first, lp["attention"], arch, dt)
        else:
            x = x + delta_attention(u, first, lp["linear_attn"], arch, dt)
        h = norm(x, lp["post_norm"]["scale"], eps)
        if j < arch["first_k_dense_replace"]:
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
