"""Plain float32 forward of the EvaByte (``evabyte``) policy core: every layer
EVA attention — a query reads the exact keys of its own ``window_size``-step
block and one learned summary for every ``chunk_size``-step chunk of the
blocks before it, under one softmax — and a dense SwiGLU MLP.

Written from the published description (the model's ``config.json`` keys, read
from ``params["arch"]``; the EVA estimator of arXiv:2302.04542 in the chunked
form of the model's modelling code, as the configuration file's ``assumed``
records it), not from ``tpu_rl/models`` or ``tpu_rl/parallel``: no kernels, no
cache, no gather, no compaction, no second call, no mixed precision, no flax.
A summary row is made for *every* step — pooled over the ``chunk_size`` steps
that end there, by that many shifted products — and the mask says which rows
are a complete chunk's; every query is scored against ``[K ; all T rows]``
under the mask written straight from the definitions of ``E`` and ``S``, a
block of queries at a time (a float32 ``(32, T, 2 T)`` score tensor at
T = 16,384 is 68.7 GB; 256 queries are 1.1 GB). It reads only the parameter
tree, so system and reference run on the same seeded weights. Callers wrap it
in ``jax.default_matmul_precision("highest")``.

    N(x) = x rsqrt(mean x^2 + eps) (1 + w)                        w starts at 0
    x = obs @ W_e + b_e
    e(t), p(t): step t's episode and its index in it; b = p // W, chunk p // C
    per layer i:
      u = N_1(x)
      q, k, v = u W_q, u W_k, u W_v                               H heads of D = hidden / H
      q, k = RoPE(q, p), RoPE(k, p)                               over the whole head, theta
      for every step r with p(r) mod C = C - 1 (it ends a complete chunk):
        k~_r = sum_{j<C} softmax_j(s mu_h . k_{r-j}) k_{r-j}      s = D^-1/2, per head h
        v~_r = sum_{j<C} softmax_j(s phi_h . k_{r-j}) v_{r-j}
      E(t) = {m <= t : e(m) = e(t), b(m) = b(t)}
      S(t) = {r : r ends a complete chunk, e(r) = e(t), b(r) < b(t)}
      o_t = [sum_E e^{s q_t.k_m} v_m + sum_S e^{s q_t.k~_r} v~_r]
            / [sum_E e^{s q_t.k_m} + sum_S e^{s q_t.k~_r}]        one normaliser
      x = x + [o_1 .. o_H] W_o
      h = N_2(x);  x = x + (silu(h W_1) * h W_3) W_2
    logits = log_softmax(N(x) @ W_pi + b_pi);  value = N(x) @ W_v + b_v

Departures from the published language model, each the system's too:

- an observation projection (with bias) replaces the byte embedding, a policy
  head and a value head (with bias) the LM head and the multi-byte prediction
  heads; the residual stream is float32;
- depth: ``num_hidden_layers`` layers, a cut of the published 32;
- the grid is the **episode's**: a packed window holds several episodes, a
  published sequence one document from position 0; blocks, chunks and the
  rotation count from the episode's first step, and from the window's first
  step for the fragment a window opens with;
- a chunk's summary is read from the *next block* on, never inside its own
  block (its members are exact keys there);
- the pooling: two softmax poolings a chunk with one learned vector each per
  head (``pool_k`` = mu, ``pool_v`` = phi), logits on the rotated keys;
- the rotation pairs feature ``i`` with ``i + D / 2`` (rotate-half).

``operand_dtype``: round both operands of every projection and MLP matmul to
that dtype first (a reading of what a lower precision gives).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256


def _rounded(x, dtype):
    return x if dtype is None else x.astype(dtype).astype(jnp.float32)


def matmul(x, w, dtype=None):
    return _rounded(x, dtype) @ _rounded(w, dtype)


def norm(x, w, eps):
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


def rotary(x, pos, theta):
    """``x`` (B, T, H, D), ``pos`` (B, T): ``x cos + rotate_half(x) sin`` with
    the angles laid out ``[f_0 .. f_{D/2-1}, f_0 .. f_{D/2-1}]``."""
    D = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    freqs = pos.astype(jnp.float32)[..., None] * inv_freq  # (B, T, D/2)
    angle = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None, :]
    half = jnp.concatenate([-x[..., D // 2:], x[..., : D // 2]], axis=-1)
    return x * jnp.cos(angle) + half * jnp.sin(angle)


def _back(x, j):
    """``x`` (B, T, ...) shifted ``j`` steps back: row t holds ``x_{t-j}`` (0
    before the window)."""
    return jnp.pad(x, ((0, 0), (j, 0)) + ((0, 0),) * (x.ndim - 2))[:, : x.shape[1]]


def pooled_rows(k, x, w, chunk, scale):
    """For every step r: ``sum_{j<chunk} softmax_j(scale w_h . k_{r-j}) x_{r-j}``.
    ``k``, ``x`` (B, T, H, D); ``w`` (H, D). Rows that end no complete chunk
    hold numbers nobody reads."""
    logits = jnp.stack(
        [scale * jnp.einsum("bthd,hd->bth", _back(k, j), w) for j in range(chunk)])
    weight = jax.nn.softmax(logits, axis=0)  # over the chunk's members
    out = jnp.zeros_like(x)
    for j in range(chunk):
        out = out + weight[j][..., None] * _back(x, j)
    return out


def eva_attention(u, first, p, arch, dtype=None):
    B, T, _ = u.shape
    H = arch["num_attention_heads"]
    D = arch["hidden_size"] // H
    W, C = arch["window_size"], arch["chunk_size"]
    scale = 1.0 / jnp.sqrt(jnp.float32(D))
    q = matmul(u, p["q_proj"]["kernel"], dtype).reshape(B, T, H, D)
    k = matmul(u, p["k_proj"]["kernel"], dtype).reshape(B, T, H, D)
    v = matmul(u, p["v_proj"]["kernel"], dtype).reshape(B, T, H, D)
    episode, pos = episode_positions(first)
    q, k = rotary(q, pos, arch["rope_theta"]), rotary(k, pos, arch["rope_theta"])
    k_sum = pooled_rows(k, k, p["pool_k"], C, scale)
    v_sum = pooled_rows(k, v, p["pool_v"], C, scale)
    keys = jnp.concatenate([k, k_sum], axis=1)  # (B, 2 T, H, D)
    values = jnp.concatenate([v, v_sum], axis=1)
    block_of = pos // W
    complete = pos % C == C - 1
    block = min(QUERY_BLOCK, T)
    assert T % block == 0, (T, block)
    t = jnp.arange(T)

    @jax.checkpoint  # a gradient keeps one block's scores at a time, not every block's
    def queries(start):
        """The ``block`` queries from ``start`` on against every key and every row."""
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        at = start + jnp.arange(block)
        mine = jax.lax.dynamic_slice_in_dim(episode, start, block, axis=1)[:, :, None]
        my_block = jax.lax.dynamic_slice_in_dim(block_of, start, block, axis=1)[:, :, None]
        same = mine == episode[:, None, :]
        exact = same & (my_block == block_of[:, None, :]) & (at[:, None] >= t[None, :])
        summary = same & (block_of[:, None, :] < my_block) & complete[:, None, :]
        mask = jnp.concatenate([exact, summary], axis=-1)  # (B, block, 2 T)
        scores = scale * jnp.einsum("bthd,bshd->bhts", qb, keys)
        scores = jnp.where(mask[:, None], scores, -jnp.inf)
        out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), values)
        return out.reshape(B, block, H * D)

    out = jax.lax.map(queries, jnp.arange(0, T, block))  # (T / block, B, block, H D)
    out = out.transpose(1, 0, 2, 3).reshape(B, T, H * D)
    return matmul(out, p["o_proj"]["kernel"], dtype)


def swiglu(h, w_gate, w_in, w_out, dtype=None):
    return matmul(jax.nn.silu(matmul(h, w_gate, dtype)) * matmul(h, w_in, dtype), w_out, dtype)


def forward(actor_params, batch: dict, params: dict, operand_dtype=None):
    """``batch``: field -> (B, T, width) float32. Returns log-softmax logits
    (B, T, A) and value (B, T, 1)."""
    arch = params["arch"]
    dt = operand_dtype
    p = jax.tree.map(lambda a: a.astype(jnp.float32), actor_params["params"])
    first = batch["is_fir"][..., 0] > 0
    eps = arch["rms_norm_eps"]
    x = matmul(batch["obs"], p["embed"]["kernel"], dt) + p["embed"]["bias"]
    for i in range(arch["num_hidden_layers"]):
        lp = p[f"layer{i}"]
        u = norm(x, lp["input_layernorm"]["scale"], eps)
        x = x + eva_attention(u, first, lp["attention"], arch, dt)
        h = norm(x, lp["post_attention_layernorm"]["scale"], eps)
        x = x + swiglu(
            h, *(lp[leaf]["kernel"] for leaf in ("gate_proj", "up_proj", "down_proj")), dt)
    f = norm(x, p["norm_f"]["scale"], eps)
    logits = f @ p["logits"]["kernel"] + p["logits"]["bias"]
    return jax.nn.log_softmax(logits), f @ p["value"]["kernel"] + p["value"]["bias"]
