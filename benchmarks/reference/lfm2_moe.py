"""Plain float32 forward of the LFM2-MoE (``lfm2_moe``) policy core: each layer
a gated short convolution or grouped-query attention, as ``layer_types`` says;
the leading ``num_dense_layers`` layers with a dense SwiGLU MLP, the others
with a sparse-expert block and no shared expert.

Written from the published description (the model's ``config.json`` keys, read
from ``params["arch"]``, and the family's published modelling code as the
configuration file's ``assumed`` records it), not from ``tpu_rl/models`` or
``tpu_rl/ops``: the convolution is ``conv_L_cache`` shifted, seam-masked
products; attention is dense and masked, a block of queries at a time against
every key (a float32 ``(32, T, T)`` score tensor at T = 8,192 is 8.6 GB a
row; 1,024 queries are 1.1 GB); the experts are a loop over the held ones
under a mask — no tail cache, no K/V cache, no sort, no grouped product, no
kernels, no mixed precision, no flax. It reads only the parameter tree, so
system and reference run on the same seeded weights. Callers wrap it in
``jax.default_matmul_precision("highest")``.

    N(x) = x rsqrt(mean x^2 + eps) w                              plain, w starts at 1
    x = obs @ W_e + b_e
    per layer i:
      u = N_1(x)                                                  operator_norm
      layer_types[i] == "conv":
        [b ; c ; x~] = u W_in                                     three chunks of hidden_size
        z = b * x~
        h_t = sum_{j=0..K-1} w_j * z_{t-(K-1)+j}                  K = conv_L_cache; a tap outside the
                                                                  window or the step's episode adds 0
        x = x + (c * h) W_out
      layer_types[i] == "full_attention":
        q, k, v = u W_q, u W_k, u W_v                             heads of hidden_size / num_attention_heads
        q, k = N_q(q), N_k(k)                                     per head, plain, one weight for all heads
        q, k = RoPE(q, pos), RoPE(k, pos)                         over the whole head
        x = x + [softmax(q_h k_g(h)^T / sqrt(D) + mask) v_g(h)]_h W_o
      h = N_2(x)                                                  ffn_norm
      i < num_dense_layers:
        x = x + (silu(h W_1) * h W_3) W_2
      else:
        s = sigmoid(h W_router)                                   every published expert
        E = the num_experts_per_tok largest of s + b              b: the expert bias
        w_e = routed_scaling_factor s_e / (sum of the chosen s + 1e-20)
        x = x + sum over e in E that are held of  w_e W_out,e (silu(W_gate,e h) * W_in,e h)
    logits = log_softmax(N(x) @ W_pi + b_pi);  value = N(x) @ W_v + b_v

Departures from the published language model, each the system's too:

- an observation projection (with bias) replaces the token embedding, a policy
  head and a value head (with bias) the LM head; the residual stream is float32;
- depth: ``num_hidden_layers`` layers, a cut of the published 40, with
  ``layer_types`` and ``num_dense_layers`` cut with it;
- the share: ``arch["expert_parallel"]`` (``published_n_routed_experts``,
  ``chips``, ``rank``) says which ``num_experts`` experts are held; the router
  scores all of them and the absent ones' part is left out;
- a tap of the convolution that would reach across an episode seam adds zero
  (a packed window holds several episodes, a published sequence one document),
  and a window starts from an empty tail;
- the rotation pairs feature ``i`` with ``i + D / 2`` (rotate-half);
- the expert bias ``b`` is a fixed leaf: the rule that updates it in
  pre-training is not in ``config.json``; the published code adds 1e-6 to the
  sum of the chosen scores, this 1e-20;
- ``pos`` is the step's index in its **episode** (the sequence a language
  model would see); attention and the convolution see only the step's own
  episode.

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
    """``x`` (B, T, H, D), ``pos`` (B, T): ``x cos + rotate_half(x) sin`` with
    the angles laid out ``[f_0 .. f_{D/2-1}, f_0 .. f_{D/2-1}]``."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = pos.astype(jnp.float32)[..., None] * inv_freq  # (B, T, D/2)
    angle = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def conv_in_episode(z, episode, weight):
    """``h_t = sum_j weight[K-1-j] z_{t-j}`` over the taps ``j`` whose step
    lies in the window and in step ``t``'s episode. ``z`` (B, T, C)."""
    K, T = weight.shape[0], z.shape[1]
    h = jnp.zeros_like(z)
    for j in range(K):
        back = jnp.pad(z, ((0, 0), (j, 0), (0, 0)))[:, :T]
        theirs = jnp.pad(episode, ((0, 0), (j, 0)), constant_values=-1)[:, :T]
        h = h + jnp.where((theirs == episode)[..., None], back, 0.0) * weight[K - 1 - j]
    return h


def short_conv(u, first, p, arch, dtype=None):
    b, c, x = jnp.split(matmul(u, p["in_proj"]["kernel"], dtype), 3, axis=-1)
    episode, _ = episode_positions(first)
    h = conv_in_episode(b * x, episode, p["conv_weight"])
    return matmul(c * h, p["out_proj"]["kernel"], dtype)


def attention(u, first, p, arch, dtype=None):
    B, T, _ = u.shape
    n_q, n_kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    D, eps = arch["hidden_size"] // n_q, arch["norm_eps"]
    q = matmul(u, p["q_proj"]["kernel"], dtype).reshape(B, T, n_q, D)
    k = matmul(u, p["k_proj"]["kernel"], dtype).reshape(B, T, n_kv, D)
    v = matmul(u, p["v_proj"]["kernel"], dtype).reshape(B, T, n_kv, D)
    q, k = norm(q, p["q_norm"]["scale"], eps), norm(k, p["k_norm"]["scale"], eps)
    episode, pos = episode_positions(first)
    theta = arch["rope_parameters"]["rope_theta"]
    q, k = rotary(q, pos, theta), rotary(k, pos, theta)
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)
    t = jnp.arange(T)

    @jax.checkpoint  # a gradient keeps one block's scores at a time, not every block's
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
        return out.reshape(B, block, n_q * D)

    out = jax.lax.map(queries, jnp.arange(0, T, block))  # (T / block, B, block, n_q D)
    out = out.transpose(1, 0, 2, 3).reshape(B, T, n_q * D)
    return matmul(out, p["o_proj"]["kernel"], dtype)


def swiglu(h, w_gate, w_in, w_out, dtype=None):
    return matmul(jax.nn.silu(matmul(h, w_gate, dtype)) * matmul(h, w_in, dtype), w_out, dtype)


def experts(h, p, arch, choice=None, dtype=None):
    """``h`` (B, T, d). Returns the held routed experts' part of the block's
    output and its routing."""
    k = arch["num_experts_per_tok"]
    held = arch["num_experts"]
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
    return y, {"choice": own, "margin": margin}


def forward_routed(actor_params, batch: dict, params: dict, choices=None, operand_dtype=None):
    """``batch``: field -> (B, T, width) float32. Returns log-softmax logits
    (B, T, A), value (B, T, 1) and one routing record per expert layer."""
    arch = params["arch"]
    dt = operand_dtype
    p = jax.tree.map(lambda a: a.astype(jnp.float32), actor_params["params"])
    first = batch["is_fir"][..., 0] > 0
    eps = arch["norm_eps"]
    x = matmul(batch["obs"], p["embed"]["kernel"], dt) + p["embed"]["bias"]
    routes = []
    for i, kind in enumerate(arch["layer_types"]):
        lp = p[f"layer{i}"]
        u = norm(x, lp["operator_norm"]["scale"], eps)
        if kind == "conv":
            x = x + short_conv(u, first, lp["conv"], arch, dt)
        else:
            x = x + attention(u, first, lp["attention"], arch, dt)
        h = norm(x, lp["ffn_norm"]["scale"], eps)
        if i < arch["num_dense_layers"]:
            x = x + swiglu(h, *(lp[leaf]["kernel"] for leaf in ("w1", "w3", "w2")), dt)
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
