"""Plain float32 forward of the Qwen3-Next policy core: three Gated-DeltaNet
linear-attention layers to every gated full-attention layer, each followed by
a sparse-expert block with a gated shared expert.

Written from the published description (the model's ``config.json`` keys, read
from ``params["arch"]``, and the family's published modelling code as the
configuration file's ``assumed`` records it), not from ``tpu_rl/models`` or
``tpu_rl/ops``: the delta rule is its **step recurrence**, one ``lax.scan``
over the steps of the window (no chunks, no triangular inverse); attention is
dense and masked, a block of queries at a time against every key; the experts
are a loop over the held ones under a mask — no sort, no grouped product, no
kernels, no mixed precision, no flax. It reads only the parameter tree, so
system and reference run on the same seeded weights. Callers wrap it in
``jax.default_matmul_precision("highest")``.

    N(x) = x rsqrt(mean x^2 + eps) (1 + w)                    zero-centred, w starts at 0
    x = obs @ W_e + b_e
    per layer i (full where (i + 1) % full_attention_interval == 0, else linear):
      u = N_1(x)
      linear:
        [q, k, v, z] = u W_qkvz;  [b, a] = u W_ba
        [q, k, v] = silu(conv(q, k, v))          depthwise, causal, K taps, none across a seam
        per value head h (key head h // (value heads / key heads)):
          q^ = q / sqrt(|q|^2 + 1e-6) / sqrt(d_k);  k^ = k / sqrt(|k|^2 + 1e-6)
          beta = sigmoid(b);  alpha = exp(-exp(A_log) softplus(a + dt_bias))
          S~ = alpha S_{t-1}            S_{t-1} = 0 at the window's and every episode's first step
          S_t = S~ + k^ (beta (v - S~^T k^))^T;   o_t = S_t^T q^
        x = x + (o rsqrt(mean o^2 + eps) w_n * silu(z)) W_out       the mean over a head
      full:
        [q, gate] = u W_q (per head);  k, v = u W_k, u W_v          16 : 2 heads of 256
        q, k = N_q(q), N_k(k)                                       per head, zero-centred
        q, k = RoPE over features 0 .. 63 of each head, the rest pass
        x = x + (softmax(q k^T / sqrt(256) + mask) v * sigmoid(gate)) W_o
      h = N_2(x)
      p = softmax(h W_router);  E = the num_experts_per_tok largest;  w = p[E] / sum p[E]
      x = x + sum over e in E that are held of  w_e W_out,e (silu(W_gate,e h) * W_in,e h)
            + sigmoid(h w_s) * W_so (silu(W_sg h) * W_si h)
    logits = log_softmax(N(x) @ W_pi + b_pi);  value = N(x) @ W_v + b_v

Departures from the published language model, each the system's too:

- an observation projection (with bias) replaces the token embedding, a policy
  head and a value head (with bias) the LM head; the residual stream is float32;
- depth: ``num_hidden_layers`` layers, a cut of the published 48; no
  multi-token-prediction module (there is no LM head for it to feed);
- the share: ``arch["expert_parallel"]`` (``published_n_routed_experts``,
  ``chips``, ``rank``) says which ``num_experts`` experts are held; the router
  scores all of them and the absent ones' part is left out;
- ``pos`` is the step's index in its **episode** (the sequence a language
  model would see); attention and the recurrence see only the step's own
  episode.

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
L2_EPS = 1e-6


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def matmul(x, w, dtype=None):
    return _rounded(x, dtype) @ _rounded(w, dtype)


def norm(x, w, eps):
    """The zero-centred RMSNorm."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def episode_positions(first):
    """``first`` (B, T) bool. Each step's episode id, and its index in its
    episode (the window's first step opens an episode whatever its flag)."""
    T = first.shape[1]
    t = jnp.arange(T)
    episode = jnp.cumsum(first.astype(jnp.int32), axis=1)
    opens = first.at[:, 0].set(True)
    began = jax.lax.cummax(jnp.where(opens, t, 0), axis=1)
    return episode, t - began


def rotary(x, pos, theta, width):
    """``x`` (B, T, H, D), ``pos`` (B, T): the first ``width`` features of each
    head as ``x cos + rotate_half(x) sin`` with the angles laid out ``[f_0 ..
    f_{width/2-1}, f_0 .. f_{width/2-1}]``; the others as they are."""
    turned, passed = x[..., :width], x[..., width:]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    freqs = pos.astype(jnp.float32)[..., None] * inv_freq  # (B, T, width/2)
    angle = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None, :]
    half = jnp.concatenate([-turned[..., width // 2:], turned[..., : width // 2]], axis=-1)
    return jnp.concatenate([turned * jnp.cos(angle) + half * jnp.sin(angle), passed], axis=-1)


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


def linear_attention(u, first, p, arch, dtype=None):
    B, T, _ = u.shape
    hk, hv = arch["linear_num_key_heads"], arch["linear_num_value_heads"]
    dk, dv = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    keys, values = hk * dk, hv * dv
    qkvz = matmul(u, p["in_proj_qkvz"]["kernel"], dtype)
    qkv, z = qkvz[..., : 2 * keys + values], qkvz[..., 2 * keys + values:]
    ba = matmul(u, p["in_proj_ba"]["kernel"], dtype)
    beta = jax.nn.sigmoid(ba[..., :hv])
    alpha = jnp.exp(-jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"]))
    episode, _ = episode_positions(first)
    qkv = jax.nn.silu(conv_in_episode(qkv, episode, p["conv_weight"]))
    q = qkv[..., :keys].reshape(B, T, hk, dk)
    k = qkv[..., keys: 2 * keys].reshape(B, T, hk, dk)
    v = qkv[..., 2 * keys:].reshape(B, T, hv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / jnp.sqrt(
        jnp.float32(dk))
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    # value head h reads key head h // (hv / hk)
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))

    def step(S, at):
        """``S`` (B, hv, dk, dv): the state after the step before."""
        q_t, k_t, v_t, alpha_t, beta_t, first_t = at
        S = jnp.where(first_t[:, None, None, None], 0.0, S) * alpha_t[..., None, None]
        # S^T k and S^T q as sums over the key axis: float32 on the vector unit, no matmul passes
        delta = beta_t[..., None] * (v_t - jnp.sum(S * k_t[..., :, None], axis=-2))
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.sum(S * q_t[..., :, None], axis=-2)

    steps_first = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, alpha, beta, first))
    _, o = jax.lax.scan(step, jnp.zeros((B, hv, dk, dv), jnp.float32), steps_first)
    o = jnp.moveaxis(o, 0, 1)  # (B, T, hv, dv)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + arch["rms_norm_eps"])
    y = o * p["norm_scale"] * jax.nn.silu(z.reshape(B, T, hv, dv))
    return matmul(y.reshape(B, T, values), p["out_proj"]["kernel"], dtype)


def attention(u, first, p, arch, dtype=None):
    B, T, _ = u.shape
    n_q, n_kv, D = arch["num_attention_heads"], arch["num_key_value_heads"], arch["head_dim"]
    eps = arch["rms_norm_eps"]
    q_gate = matmul(u, p["q_proj"]["kernel"], dtype).reshape(B, T, n_q, 2 * D)
    q, gate = q_gate[..., :D], q_gate[..., D:]
    k = matmul(u, p["k_proj"]["kernel"], dtype).reshape(B, T, n_kv, D)
    v = matmul(u, p["v_proj"]["kernel"], dtype).reshape(B, T, n_kv, D)
    q, k = norm(q, p["q_norm"]["scale"], eps), norm(k, p["k_norm"]["scale"], eps)
    episode, pos = episode_positions(first)
    width = int(D * arch["partial_rotary_factor"])
    q, k = (rotary(x, pos, arch["rope_theta"], width) for x in (q, k))
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
        scores = jnp.einsum("btgrd,bsgd->bgrts", qb, k) / jnp.sqrt(jnp.float32(D))
        scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
        out = jnp.einsum("bgrts,bsgd->btgrd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(B, block, n_q, D)

    out = jax.lax.map(queries, jnp.arange(0, T, block))  # (T / block, B, block, n_q, D)
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, T, n_q, D) * jax.nn.sigmoid(gate)
    return matmul(out.reshape(B, T, n_q * D), p["o_proj"]["kernel"], dtype)


def experts(h, p, arch, choice=None, dtype=None):
    """``h`` (B, T, d). Returns the block's output (the held routed experts'
    part and the gated shared expert) and its routing."""
    k = arch["num_experts_per_tok"]
    held = arch["num_experts"]
    first = arch.get("expert_parallel", {}).get("rank", 0) * held
    logit = h @ p["router"]  # the router is float32 in every precision
    ranked = jnp.argsort(-logit, axis=-1, stable=True)
    by_rank = jnp.take_along_axis(logit, ranked, axis=-1)
    own = ranked[..., :k]
    margin = by_rank[..., k - 1] - by_rank[..., k] if logit.shape[-1] > k else None
    if choice is None:
        choice = own
    chosen = jnp.take_along_axis(jax.nn.softmax(logit, axis=-1), choice, axis=-1)
    weight = chosen / jnp.sum(chosen, axis=-1, keepdims=True)  # norm_topk_prob

    def swiglu(w_gate, w_in, w_out):
        return matmul(jax.nn.silu(matmul(h, w_gate, dtype)) * matmul(h, w_in, dtype), w_out, dtype)

    def add_expert(y, expert):
        """One held expert applied to every step, under its weight (0 where
        the step did not choose it). A ``scan`` and not a Python loop: one
        body to compile for all of them, the same sum in the same order."""
        e, w_gate, w_in, w_out = expert
        gate = jnp.sum(jnp.where(choice == first + e, weight, 0.0), axis=-1, keepdims=True)
        return y + gate * swiglu(w_gate, w_in, w_out), None

    y, _ = jax.lax.scan(
        add_expert, jnp.zeros_like(h), (jnp.arange(held), p["w_gate"], p["w_in"], p["w_out"]))
    shared = swiglu(*(p[leaf]["kernel"] for leaf in ("shared_gate", "shared_in", "shared_out")))
    shared = jax.nn.sigmoid(matmul(h, p["shared_weight"]["kernel"], dtype)) * shared
    return y + shared, {"choice": own, "margin": margin}


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
        u = norm(x, lp["input_norm"]["scale"], eps)
        if (i + 1) % arch["full_attention_interval"] == 0:
            x = x + attention(u, first, lp["attention"], arch, dt)
        else:
            x = x + linear_attention(u, first, lp["linear_attn"], arch, dt)
        h = norm(x, lp["post_norm"]["scale"], eps)
        mixed, route = experts(
            h, lp["experts"], arch, None if choices is None else choices[i], dt)
        routes.append(route)
        x = x + mixed
    f = norm(x, p["norm_f"]["scale"], eps)
    logits = f @ p["logits"]["kernel"] + p["logits"]["bias"]
    return jax.nn.log_softmax(logits), f @ p["value"]["kernel"] + p["value"]["bias"], routes


def forward(actor_params, batch: dict, params: dict, choices=None):
    """Log-softmax logits (B, T, A) and value (B, T, 1)."""
    return forward_routed(actor_params, batch, params, choices)[:2]
