"""Plain float32 forward of the repo's long-context transformer actor-critic.

Written from the model's description, not from ``tpu_rl/models``: no kernels,
no mixed precision, no flax. It reads only the parameter tree (names and
shapes), so the system and the reference run on the same seeded weights.
Callers wrap it in ``jax.default_matmul_precision("highest")``: on a TPU a
float32 matmul otherwise runs as one bfloat16 pass.

The architecture (decoder-only, causal, episode seams as segment masks):

    x   = obs @ W_e + b_e + sinusoidal(pos)            pos restarts at a seam
    for each block:
        x = x + Attn(LN1(x))                           pre-norm attention
        x = x + W_2 gelu_tanh(W_1 LN2(x) + b_1) + b_2  LN2 reads the new x
    h      = LN_f(x)
    logits = log_softmax(h @ W_pi + b_pi);  value = h @ W_v + b_v

    Attn: heads of size d/H, scores q.k / sqrt(d/H), token t sees token s iff
    same segment and pos[t] >= pos[s]; LayerNorm eps 1e-6.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6


def layer_norm(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def sinusoidal(pos, dim: int):
    half = dim // 2
    freqs = np.exp(-np.log(10000.0) * np.arange(half) / max(half - 1, 1))
    ang = pos[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(np.sqrt(2.0 / np.pi) * (x + 0.044715 * x**3)))


def positions_and_segments(is_fir):
    """(B, T, 1) episode-first flags -> segment-relative positions and
    segment ids, both (B, T)."""
    first = is_fir[..., 0] > 0
    T = first.shape[1]
    idx = jnp.broadcast_to(jnp.arange(T), first.shape)
    seam = jax.lax.cummax(jnp.where(first, idx, 0), axis=1)
    return idx - seam, jnp.cumsum(first.astype(jnp.int32), axis=1)


def attention(x, p, n_heads: int, pos, seg):
    B, T, C = x.shape
    D = C // n_heads
    qkv = dense(x, p["qkv"]).reshape(B, T, 3, n_heads, D)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    scores = jnp.einsum("bthd,bshd->bhts", q, k) / np.sqrt(D)
    mask = (seg[:, :, None] == seg[:, None, :]) & (pos[:, :, None] >= pos[:, None, :])
    scores = jnp.where(mask[:, None], scores, -jnp.inf)
    out = jnp.einsum("bhts,bshd->bthd", jax.nn.softmax(scores, axis=-1), v)
    return dense(out.reshape(B, T, C), p["out"])


def forward(actor_params, batch: dict, params: dict):
    """``batch``: field -> (B, T, width) float32. Returns log-softmax logits
    (B, T, A) and value (B, T, 1). The carry fields are not read."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), actor_params["params"])
    pos, seg = positions_and_segments(batch["is_fir"])
    d = int(params["hidden_size"])
    x = dense(batch["obs"], p["embed"]) + sinusoidal(pos, d)
    for i in range(int(params["n_layers"])):
        blk = p[f"block{i}"]
        x = x + attention(layer_norm(x, blk["ln1"]), blk["attn"],
                          int(params["n_heads"]), pos, seg)
        x = x + dense(gelu_tanh(dense(layer_norm(x, blk["ln2"]), blk["ff1"])),
                      blk["ff2"])
    h = layer_norm(x, p["ln_f"])
    return jax.nn.log_softmax(dense(h, p["logits"])), dense(h, p["value"])
