"""Plain float32 forward of the granite-4.0-h policy core: Mamba-2 layers and
grouped-query attention layers in the ``GraniteMoeHybrid`` arrangement.

Written from the published description (Mamba-2 / SSD, arXiv:2405.21060; the
model's ``config.json`` keys, read from ``params["arch"]``), not from
``tpu_rl/models``: the recurrence runs one step at a time in a ``lax.scan`` —
no chunks, no rematerialisation, no kernels, no mixed precision, no flax —
and attention is dense and masked. It reads only the parameter tree (names and
shapes), so the system and the reference run on the same seeded weights.
Callers wrap it in ``jax.default_matmul_precision("highest")``.

Departures from the published language model, each the system's too:

- an observation projection (with bias) replaces the token embedding:
  ``x = embedding_multiplier * (obs @ W_e + b_e)``;
- a policy head and a value head (with bias) replace the tied LM head:
  ``logits = log_softmax((h @ W_pi + b_pi) / logits_scaling)``,
  ``value = h @ W_v + b_v``, ``h`` the final RMSNorm's output;
- depth: the layers are those of ``arch["layer_types"]``, a cut of the
  published forty;
- an episode seam (``is_fir[t]``) zeroes the state and the convolution's taps
  before ``t``, and attention sees only the query's own episode.

Every layer:

    x = x + residual_multiplier * mixer(RMSNorm(x))
    x = x + residual_multiplier * (silu(a) * b) @ W_out,  [a, b] = RMSNorm(x) @ W_in

Mamba-2 mixer, per step ``t`` (h heads of size p, g groups of state size n):

    [z, xBC, dt] = u_t @ W_in                        inner + (inner + 2 g n) + h
    xBC  = silu(bias + sum_k w[k] * xBC[t - (K-1) + k])     taps of this episode only
    [x, B, C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    state = exp(dt A) state + dt x (x) B              per head; B, C of the head's group
    y     = state C + D x
    out   = (RMSNorm_per_group(y * silu(z)) * w) @ W_out

Attention: ``q, k, v`` without bias or positions; a key/value head serves
``n_q / n_kv`` consecutive query heads; scores ``q.k * attention_multiplier``;
step ``t`` sees step ``s <= t`` of the same episode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def silu(x):
    return x / (1.0 + jnp.exp(-x))


def linear(x, p):
    """No projection of the published model has a bias; the key says so."""
    return x @ p["kernel"] + p.get("bias", 0.0)


def mamba2(u, first, p, arch, state0, tail0):
    """``u`` (B, T, d); ``first`` (B, T) bool; ``state0`` (B, h, p, n);
    ``tail0`` (B, K-1, C). One ``lax.scan`` step per time step."""
    h, dh, n = arch["mamba_n_heads"], arch["mamba_d_head"], arch["mamba_d_state"]
    g = arch["mamba_n_groups"]
    inner = h * dh
    zxbcdt = linear(u, p["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * g * n], axis=-1)
    dt = jax.nn.softplus(dt + p["dt_bias"])  # (B, T, h)
    A = -jnp.exp(p["A_log"])
    w = p["conv_weight"]  # (K, C)
    bias = p["conv_bias"] if arch["mamba_conv_bias"] else 0.0

    def step(carry, inp):
        state, tail = carry
        xbc_t, dt_t, first_t = inp  # (B, C), (B, h), (B,)
        state = jnp.where(first_t[:, None, None, None], 0.0, state)
        tail = jnp.where(first_t[:, None, None], 0.0, tail)
        window = jnp.concatenate([tail, xbc_t[:, None]], axis=1)  # (B, K, C)
        conv = silu(bias + jnp.sum(window * w, axis=1))
        x, B, C = jnp.split(conv, [inner, inner + g * n], axis=-1)
        x = x.reshape(-1, h, dh)
        B = jnp.repeat(B.reshape(-1, g, n), h // g, axis=1)  # the head's group
        C = jnp.repeat(C.reshape(-1, g, n), h // g, axis=1)
        keep = jnp.exp(dt_t * A)[..., None, None]
        state = keep * state + (dt_t[..., None] * x)[..., None] * B[:, :, None, :]
        y = jnp.sum(state * C[:, :, None, :], axis=-1) + p["D"][:, None] * x
        return (state, window[:, 1:]), y.reshape(-1, inner)

    carry, y = jax.lax.scan(
        step, (state0, tail0),
        (xbc.transpose(1, 0, 2), dt.transpose(1, 0, 2), first.T),
    )
    y = y.transpose(1, 0, 2) * silu(z)
    grouped = y.reshape(*y.shape[:2], g, inner // g)
    y = rms_norm(grouped, 1.0, arch["rms_norm_eps"]).reshape(y.shape) * p["norm_scale"]
    return linear(y, p["out_proj"]), carry


def attention(u, first, p, arch):
    B, T, d = u.shape
    n_q, n_kv = arch["num_attention_heads"], arch["num_key_value_heads"]
    D = d // n_q
    q = linear(u, p["q_proj"]).reshape(B, T, n_kv, n_q // n_kv, D)
    k = linear(u, p["k_proj"]).reshape(B, T, n_kv, D)
    v = linear(u, p["v_proj"]).reshape(B, T, n_kv, D)
    scores = jnp.einsum("btgrd,bsgd->bgrts", q, k) * arch["attention_multiplier"]
    episode = jnp.cumsum(first.astype(jnp.int32), axis=1)
    t = jnp.arange(T)
    mask = (episode[:, :, None] == episode[:, None, :]) & (t[:, None] >= t[None, :])
    scores = jnp.where(mask[:, None, None], scores, -jnp.inf)
    out = jnp.einsum("bgrts,bsgd->btgrd", jax.nn.softmax(scores, axis=-1), v)
    return linear(out.reshape(B, T, d), p["o_proj"])


def forward(actor_params, batch: dict, params: dict, carry0=None):
    """``batch``: field -> (B, T, width) float32. Returns log-softmax logits
    (B, T, A) and value (B, T, 1). ``carry0``: one ``(state, tail)`` per Mamba
    layer to start the window from (zeros if None); the batch's carry fields
    are not read."""
    arch = params["arch"]
    p = jax.tree.map(lambda a: a.astype(jnp.float32), actor_params["params"])
    first = batch["is_fir"][..., 0] > 0
    B = first.shape[0]
    eps, res = arch["rms_norm_eps"], arch["residual_multiplier"]
    heads = (arch["mamba_n_heads"], arch["mamba_d_head"], arch["mamba_d_state"])
    conv_ch = heads[0] * heads[1] + 2 * arch["mamba_n_groups"] * heads[2]
    x = arch["embedding_multiplier"] * linear(batch["obs"], p["embed"])
    n_mamba = 0
    for i, kind in enumerate(arch["layer_types"]):
        lp = p[f"layer{i}"]
        u = rms_norm(x, lp["input_norm"]["scale"], eps)
        if kind == "mamba":
            if carry0 is None:
                state0 = jnp.zeros((B, *heads))
                tail0 = jnp.zeros((B, arch["mamba_d_conv"] - 1, conv_ch))
            else:
                state0, tail0 = carry0[n_mamba]
            n_mamba += 1
            mixed, _ = mamba2(u, first, lp["mamba"], arch, state0, tail0)
        else:
            mixed = attention(u, first, lp["attention"], arch)
        x = x + res * mixed
        u = rms_norm(x, lp["post_norm"]["scale"], eps)
        a, b = jnp.split(linear(u, lp["mlp_in"]), 2, axis=-1)
        x = x + res * linear(silu(a) * b, lp["mlp_out"])
    h = rms_norm(x, p["norm_f"]["scale"], eps)
    logits = linear(h, p["logits"]) / arch["logits_scaling"]
    return jax.nn.log_softmax(logits), linear(h, p["value"])
