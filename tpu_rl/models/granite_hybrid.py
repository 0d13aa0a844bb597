"""Granite-4.0-H policy core: Mamba-2 layers with grouped-query attention
layers between them, as ``GraniteMoeHybrid`` arranges them (dense: no experts).

Widths come from ``Config.arch``, the model's own ``config.json`` under its
published key names. Same ``unroll`` / ``act`` contract as the LSTM and
transformer families, so PPO / IMPALA / V-MPO take it unchanged. Two
departures from the published language model: an observation projection
replaces the token embedding, and a policy and a value head replace the tied
LM head.

    x = embedding_multiplier * Dense(obs)
    per layer:  x = x + residual_multiplier * mixer(RMSNorm(x))
                x = x + residual_multiplier * W_out(silu(a) * b),  [a, b] = W_in RMSNorm(x)
    logits = log_softmax(Dense(RMSNorm(x)) / logits_scaling);  value = Dense(RMSNorm(x))

Mamba-2 mixer (SSD, arXiv:2405.21060): ``[z, xBC, dt] = W_in u``; a causal
depthwise convolution and SiLU over ``xBC``; ``h_t = exp(dt_t A) h_{t-1} +
dt_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``; ``W_out RMSNorm(y * silu(z))``.
Attention: no positions, ``attention_multiplier`` as the softmax scale.

Episode seams: ``is_fir[t]`` zeroes the state and the convolution's taps
before ``t``. Training runs the chunked form of the recurrence, in which a
seam is a same-segment mask on every decay factor (never ``-inf`` inside a
cumulative sum, whose differences are NaN); acting runs the one-step form and
relies on the worker zeroing the carry at episode starts. Every layer is
rematerialised in the backward pass: one layer keeps ~150 KB per token.

Which form of the chunked recurrence trains where (``ssd_chunked``): on a TPU,
at widths that tile (chunk and state multiples of 128, as published), one
Pallas kernel per pass (``ops/pallas_ssd.py``, scope ``ssd_pallas`` inside
``ssd_scan``), under a registered data mesh as a ``shard_map`` island over its
``"data"`` axis; everywhere else — the CPU, the tests' 8-step chunks, a batch
that does not tile the mesh — the ``jnp``/``einsum`` body ``_ssd_jnp``, which
is also the kernels' oracle.
``models.cells.set_pallas_mode`` overrides as for the LSTM: ``"interpret"``
runs the kernels in the interpreter, ``"off"`` forces the ``jnp`` body.

Acting carry (worker-local; ``store_carry=False``): ``h`` holds each Mamba
layer's state and convolution tail, flattened; ``c`` each attention layer's
K/V ring and one step counter, as the transformer family packs its caches. A
training window starts from the ``h`` it is handed (zeros when the batch
carries a placeholder) and from an empty attention context — the truncation
``models/transformer.py`` documents.
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from tpu_rl.models import cells
from tpu_rl.ops import pallas_ssd
from tpu_rl.parallel.sequence import flash_attention_tpu, segment_ids_from_firsts


# The kinds of layer that carry a state and a convolution tail from step to
# step (Mamba-2's; ``models/qwen3_next.py``'s linear attention): ``h`` packs
# one pair per such layer. An ``"attention"`` layer carries a K/V ring in ``c``.
RECURRENT = ("mamba", "linear")


def carry_widths(arch: dict, ctx: int) -> tuple[int, int]:
    """Widths of the flattened acting carry ``(h, c)``."""
    conv_ch = _conv_channels(arch)
    per_mamba = (
        arch["mamba_n_heads"] * arch["mamba_d_head"] * arch["mamba_d_state"]
        + (arch["mamba_d_conv"] - 1) * conv_ch
    )
    head_dim = arch["hidden_size"] // arch["num_attention_heads"]
    per_attn = 2 * ctx * arch["num_key_value_heads"] * head_dim
    kinds = arch["layer_types"]
    return kinds.count("mamba") * per_mamba, kinds.count("attention") * per_attn + 1


def _conv_channels(arch: dict) -> int:
    inner = arch["mamba_n_heads"] * arch["mamba_d_head"]
    return inner + 2 * arch["mamba_n_groups"] * arch["mamba_d_state"]


def _rms_norm(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


class RMSNorm(nn.Module):
    eps: float
    dtype: Any = None  # output dtype (statistics are float32)
    zero_centered: bool = False  # the leaf starts at 0 and scales by 1 + itself

    @nn.compact
    def __call__(self, x):
        if self.zero_centered:
            scale = 1.0 + self.param("scale", nn.initializers.zeros, (x.shape[-1],))
        else:
            scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return _rms_norm(x, scale, self.eps).astype(self.dtype or jnp.float32)


def seam_conv(xbc, tail, seg, weight, bias, scope: str = "ssd_conv"):
    """Causal depthwise convolution whose taps stop at an episode seam.
    ``xbc`` (B, T, C); ``tail`` (B, K-1, C) the steps before the window
    (segment 0); ``seg`` (B, T) int; ``weight`` (K, C). Float32. ``scope``
    names it in the device trace (Mamba-2's by default)."""
    K = weight.shape[0]
    T = xbc.shape[1]
    with jax.named_scope(scope):
        xp = jnp.concatenate([tail, xbc], axis=1).astype(jnp.float32)
        segp = jnp.concatenate([jnp.zeros_like(seg[:, : K - 1]), seg], axis=1)
        out = jnp.broadcast_to(bias, xbc.shape).astype(jnp.float32)
        for k in range(K):
            same = segp[:, k : k + T] == seg
            out = out + jnp.where(same[..., None], xp[:, k : k + T], 0.0) * weight[k]
        return out


def _ssd_kernel_block(b: int, h: int, p: int, g: int, n: int, Q: int) -> tuple[int | None, bool]:
    """(heads per grid step of the Pallas scan, interpret), or (None, False)
    for the ``jnp`` body: the gate of ``models/cells.py`` (``set_pallas_mode``,
    the platform of the program being traced) applied to the scan. The CPU,
    widths that are no lane multiples and a batch that does not tile a
    registered data mesh (init and act traces: a Mosaic call has no SPMD
    rule outside its island) keep the ``jnp`` form."""
    mode = cells._PALLAS_MODE
    if mode == "off":
        return None, False
    if mode == "interpret":  # any width: whole windows of every head where none tiles
        return pallas_ssd.head_block(h, p, g, n, Q) or h, True
    platform, n_data = cells._program_devices()
    if platform != "tpu" or b % n_data:
        return None, False
    return pallas_ssd.head_block(h, p, g, n, Q), False


def _ssd_kernels(x, dt, A, B, C, D, seg, state0, chunk, dtype, hb, interpret):
    """The Pallas pair (``ops/pallas_ssd.py``); under a registered data mesh
    whose width the batch tiles, as a ``shard_map`` island over the
    ``"data"`` axis, as the LSTM kernel and the flash kernel run there."""
    scan = functools.partial(
        pallas_ssd.scan_window, chunk=chunk, dtype=dtype, hb=hb, interpret=interpret)
    mesh = cells._DATA_MESH
    if mesh is not None and x.shape[0] % cells._program_devices()[1] == 0:
        from jax.sharding import PartitionSpec as P

        from tpu_rl.parallel.mesh import DATA_AXIS

        rows = P(DATA_AXIS)  # every operand but A and D: its leading (batch) dim
        # no collectives inside; pallas out_shapes carry no vma annotations
        scan = jax.shard_map(
            scan, mesh=mesh, in_specs=(rows, rows, P(), rows, rows, P(), rows, rows),
            out_specs=(rows, rows), check_vma=False)
    with jax.named_scope("ssd_pallas"):  # the backward's ops carry it too
        return scan(x, dt, A, B, C, D, seg, state0)


@jax.named_scope("ssd_scan")
def ssd_chunked(x, dt, A, B, C, D, seg, state0, chunk: int, dtype, kernel=None):
    """The SSD recurrence over a whole window in matmul form.

    ``x`` (b, T, h, p); ``dt`` (b, T, h) float32, after softplus; ``A`` (h,)
    negative; ``B``, ``C`` (b, T, g, n); ``seg`` (b, T) int, 0 = the episode
    ``state0`` (b, h, p, n) belongs to. Returns ``y`` (b, T, h, p) float32
    and the state after the last step. Matmul operands in ``dtype``; decays,
    cumulative sums and the carried state in float32. ``kernel``: ``(heads a
    grid step of the Pallas pair or None for the jnp body, interpret)``
    where the caller and not the gate chooses (tests, ``chip_smoke.py``)."""
    b, T, h, p = x.shape
    g, n = B.shape[2:]
    pad = (-T) % chunk
    if pad:  # dt = 0: the state passes through, nothing is added
        x, dt, B, C = (
            jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (x, dt, B, C)
        )
        seg = jnp.concatenate([seg, jnp.repeat(seg[:, -1:], pad, axis=1)], axis=1)
    hb, interpret = kernel or _ssd_kernel_block(b, h, p, g, n, chunk)
    if hb is None:
        y, last = _ssd_jnp(x, dt, A, B, C, D, seg, state0, chunk, dtype)
    else:
        y, last = _ssd_kernels(x, dt, A, B, C, D, seg, state0, chunk, dtype, hb, interpret)
    return y[:, :T], last


def _ssd_jnp(x, dt, A, B, C, D, seg, state0, Q: int, dtype):
    """``ssd_chunked`` on a window of whole chunks as ``einsum``s and one
    ``lax.scan`` over the chunks: the CPU's path and the kernels' oracle."""
    b, T, h, p = x.shape
    g, n = B.shape[2:]
    r, nc = h // g, T // Q
    cd = dtype or jnp.float32
    f32 = jnp.float32
    xc = x.reshape(b, nc, Q, h, p)
    dtc = dt.reshape(b, nc, Q, h)
    Bc = B.reshape(b, nc, Q, g, n).astype(cd)
    Cc = C.reshape(b, nc, Q, g, n).astype(cd)
    segc = seg.reshape(b, nc, Q)
    # the segment a chunk is entered in: that of the step before it
    seg_in = jnp.concatenate([jnp.zeros_like(segc[:, :1, 0]), segc[:, :-1, -1]], axis=1)

    acum = jnp.cumsum((dtc * A).transpose(0, 1, 3, 2), axis=-1)  # (b, nc, h, Q)
    dtx = xc.astype(f32) * dtc[..., None]  # (b, nc, Q, h, p)

    def decay(exponent, keep):
        return jnp.exp(jnp.where(keep, exponent, -jnp.inf))

    # inside a chunk: step s reaches step l >= s of the same segment
    reach = (segc[:, :, :, None] == segc[:, :, None, :]) & jnp.tril(jnp.ones((Q, Q), bool))
    L = decay(acum[..., :, None] - acum[..., None, :], reach[:, :, None])  # (b,nc,h,l,s)
    CB = jnp.einsum("bclgn,bcsgn->bcgls", Cc, Bc, preferred_element_type=f32)
    M = (CB[:, :, :, None] * L.reshape(b, nc, g, r, Q, Q)).reshape(b, nc, h, Q, Q)
    y = jnp.einsum(
        "bchls,bcshp->bclhp", M.astype(cd), dtx.astype(cd), preferred_element_type=f32
    )

    # what each chunk adds to the state at its end
    to_end = decay(acum[..., -1:] - acum, (segc == segc[:, :, -1:])[:, :, None])  # (b,nc,h,Q)
    xw = (dtx * to_end.transpose(0, 1, 3, 2)[..., None]).astype(cd)
    S = jnp.einsum(
        "bcsgrp,bcsgn->bcgrpn", xw.reshape(b, nc, Q, g, r, p), Bc,
        preferred_element_type=f32,
    ).reshape(b, nc, h, p, n)
    # what a chunk keeps of the state it is entered with: nothing past a seam
    through = decay(acum[..., -1], (segc[:, :, -1] == seg_in)[:, :, None])  # (b, nc, h)

    def across(state, c):
        S_c, through_c = c
        return through_c[..., None, None] * state + S_c, state

    last, entered = jax.lax.scan(
        across, state0.astype(f32),
        (S.transpose(1, 0, 2, 3, 4), through.transpose(1, 0, 2)),
    )
    entered = entered.transpose(1, 0, 2, 3, 4)  # (b, nc, h, p, n): state before chunk c
    into = decay(acum, (segc == seg_in[:, :, None])[:, :, None])  # (b, nc, h, Q)
    y_in = jnp.einsum(
        "bclgn,bcgrpn->bclgrp", Cc, entered.astype(cd).reshape(b, nc, g, r, p, n),
        preferred_element_type=f32,
    ).reshape(b, nc, Q, h, p)
    y = y + y_in * into.transpose(0, 1, 3, 2)[..., None]
    y = y + xc.astype(f32) * D[:, None]
    return y.reshape(b, T, h, p), last


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """Inverse softplus of step sizes log-uniform in [1e-3, 1e-1] (Mamba-2)."""
    dt = jnp.exp(
        jax.random.uniform(key, shape, dtype) * (np.log(0.1) - np.log(0.001))
        + np.log(0.001)
    )
    return dt + jnp.log(-jnp.expm1(-dt))


def _a_log_init(key, shape, dtype=jnp.float32):
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


class Mamba2Mixer(nn.Module):
    """``__call__`` (training) runs ``ssd_chunked``: the Pallas kernels on a
    TPU, the ``jnp`` body elsewhere; ``step`` (acting) is the one-step form.
    The widths are fields, so that a family whose ``config.json`` names them
    otherwise (``models/nemotron_h.py``) builds the same mixer."""

    hidden: int
    heads: int
    d_head: int
    groups: int
    d_state: int
    d_conv: int
    chunk: int
    eps: float
    conv_bias: bool = True
    proj_bias: bool = False
    dtype: Any = None

    def setup(self):
        self.inner = self.heads * self.d_head
        self.conv_ch = self.inner + 2 * self.groups * self.d_state
        proj = dict(use_bias=self.proj_bias, dtype=self.dtype)
        self.in_proj = nn.Dense(self.inner + self.conv_ch + self.heads, name="in_proj", **proj)
        self.out_proj = nn.Dense(self.hidden, name="out_proj", **proj)
        self.conv_weight = self.param(
            "conv_weight", nn.initializers.variance_scaling(1.0, "fan_in", "uniform", in_axis=0),
            (self.d_conv, self.conv_ch),
        )
        self.conv_b = (
            self.param("conv_bias", nn.initializers.zeros, (self.conv_ch,))
            if self.conv_bias else jnp.zeros((self.conv_ch,))
        )
        self.dt_bias = self.param("dt_bias", _dt_bias_init, (self.heads,))
        self.A_log = self.param("A_log", _a_log_init, (self.heads,))
        self.D = self.param("D", nn.initializers.ones, (self.heads,))
        self.norm_scale = self.param("norm_scale", nn.initializers.ones, (self.inner,))

    def _split(self, u):
        zxbcdt = self.in_proj(u)
        z, xbc, dt = jnp.split(zxbcdt, [self.inner, self.inner + self.conv_ch], axis=-1)
        return z, xbc, jax.nn.softplus(dt.astype(jnp.float32) + self.dt_bias)

    def _heads(self, xbc):
        """Convolved, activated ``xBC`` -> x (..., h, p), B and C (..., g, n)."""
        gn = self.groups * self.d_state
        x, B, C = jnp.split(jax.nn.silu(xbc), [self.inner, self.inner + gn], axis=-1)
        lead = xbc.shape[:-1]
        return (
            x.reshape(*lead, self.heads, self.d_head),
            B.reshape(*lead, self.groups, self.d_state),
            C.reshape(*lead, self.groups, self.d_state),
        )

    def _out(self, y, z):
        """Gated RMSNorm over each group's channels, then the output
        projection. ``y`` float32 (..., inner)."""
        lead = y.shape[:-1]
        gated = (y * jax.nn.silu(z.astype(jnp.float32))).reshape(*lead, self.groups, -1)
        normed = _rms_norm(gated, 1.0, self.eps).reshape(*lead, self.inner)
        return self.out_proj((normed * self.norm_scale).astype(self.dtype or jnp.float32))

    def __call__(self, u, seg, state0, tail0):
        """``u`` (B, T, d); ``state0`` (B, h, p, n), ``tail0`` (B, K-1, C):
        the carry the window starts from. Returns the output and the carry
        after the last step."""
        z, xbc, dt = self._split(u)
        x, B, C = self._heads(seam_conv(xbc, tail0, seg, self.conv_weight, self.conv_b))
        y, state = ssd_chunked(
            x, dt, -jnp.exp(self.A_log), B, C, self.D, seg, state0, self.chunk, self.dtype,
        )
        K = self.conv_weight.shape[0]
        keep = (seg[:, -(K - 1):] == seg[:, -1:])[..., None]  # taps of the last episode only
        tail = jnp.where(keep, xbc[:, -(K - 1):].astype(jnp.float32), 0.0)
        return self._out(y.reshape(*y.shape[:2], self.inner), z), state, tail

    def step(self, u, state, tail):
        """One acting step: ``u`` (B, d)."""
        z, xbc, dt = self._split(u)
        window = jnp.concatenate([tail, xbc[:, None].astype(jnp.float32)], axis=1)
        conv = jnp.einsum("bkc,kc->bc", window, self.conv_weight) + self.conv_b
        x, B, C = self._heads(conv)
        r = self.heads // self.groups
        x = x.astype(jnp.float32)
        Bh, Ch = (jnp.repeat(a.astype(jnp.float32), r, axis=1) for a in (B, C))
        keep = jnp.exp(dt * -jnp.exp(self.A_log))  # (B, h)
        state = keep[..., None, None] * state + (dt[..., None] * x)[..., None] * Bh[:, :, None]
        y = jnp.einsum("bhpn,bhn->bhp", state, Ch) + x * self.D[:, None]
        return self._out(y.reshape(-1, self.inner), z), state, window[:, 1:]


@jax.named_scope("attn_rope")
def rope(x, pos, theta: float, rotary_dim: int | None = None):
    """Rotary positions, rotate-half pairing, no scaling: ``x`` (B, ..., H, D)
    with ``pos`` (B, ...) int. Over the whole head (feature ``i`` with
    ``i + D/2``), or with ``rotary_dim`` over the head's first ``rotary_dim``
    features alone (``i`` with ``i + rotary_dim/2``, frequencies
    ``theta^(-2i / rotary_dim)``) while the others pass as they are. Angles,
    sines and the rotation in float32; ``x``'s dtype comes back."""
    passed = None
    if rotary_dim is not None:
        x, passed = x[..., :rotary_dim], x[..., rotary_dim:]
    D = x.shape[-1]
    inv = theta ** (-jnp.arange(0, D, 2, dtype=jnp.float32) / D)
    angle = pos.astype(jnp.float32)[..., None, None] * inv  # (B, ..., 1, D/2)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    turned = jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)
    return turned if passed is None else jnp.concatenate([turned, passed], axis=-1)


class GQAttention(nn.Module):
    """Grouped-query attention, causal and masked to the episode. Widths as
    fields: the head size need not be ``hidden / n_q``. Two more fields, both
    off by default (granite's and nemotron's layers: no positions, ``nope``,
    and the whole episode so far): ``rope_theta`` rotates q and k (``rope``),
    ``window`` keeps the last ``window`` keys, the query's own among them.
    Three more, off by default too (``models/qwen3_next.py`` sets all three):
    ``rotary_dim`` rotates each head's first ``rotary_dim`` features alone;
    ``qk_norm`` (an epsilon) puts a zero-centred RMSNorm over each head of q
    and of k before the rotation (leaves ``q_norm``, ``k_norm``); ``gated``
    doubles ``q_proj`` — each head's columns are its query, then its gate —
    and multiplies the attention's output by ``sigmoid(gate)`` before
    ``o_proj``.

    The rotation's position is the step's index in the training window, and
    in acting the steps of the episode so far: the same scores, because the
    rotation enters a score only through ``q_pos - k_pos`` and the episode
    mask kills every pair that crosses a seam — within an episode the two
    count from different origins and differ by a constant."""

    hidden: int
    n_q: int
    n_kv: int
    head_dim: int
    scale: float  # of the scores, before the softmax
    bias: bool = False
    dtype: Any = None
    rope_theta: float | None = None
    window: int | None = None
    rotary_dim: int | None = None
    qk_norm: float | None = None
    gated: bool = False

    def setup(self):
        proj = dict(use_bias=self.bias, dtype=self.dtype)
        self.q_proj = nn.Dense(
            (2 if self.gated else 1) * self.n_q * self.head_dim, name="q_proj", **proj)
        if self.qk_norm is not None:
            norm = dict(eps=self.qk_norm, dtype=self.dtype, zero_centered=True)
            self.q_norm = RMSNorm(name="q_norm", **norm)
            self.k_norm = RMSNorm(name="k_norm", **norm)
        self.k_proj = nn.Dense(self.n_kv * self.head_dim, name="k_proj", **proj)
        self.v_proj = nn.Dense(self.n_kv * self.head_dim, name="v_proj", **proj)
        self.o_proj = nn.Dense(self.hidden, name="o_proj", **proj)

    @nn.nowrap
    def _queries(self, u, heads: tuple):
        """``q_proj(u)`` as heads ``(..., *heads, head_dim)`` and, where the
        layer is gated, each head's gate beside its query (else None)."""
        q = self.q_proj(u)
        if not self.gated:
            return q.reshape(*u.shape[:-1], *heads, self.head_dim), None
        q, gate = jnp.split(q.reshape(*u.shape[:-1], *heads, 2 * self.head_dim), 2, axis=-1)
        return q, gate

    @nn.nowrap
    def _positioned(self, q, k, pos):
        """q and k normed per head and rotated, as the fields say."""
        if self.qk_norm is not None:
            q, k = self.q_norm(q), self.k_norm(k)
        if self.rope_theta is not None:
            q, k = (rope(x, pos, self.rope_theta, self.rotary_dim) for x in (q, k))
        return q, k

    @staticmethod
    def _gate(o, gate):
        return o if gate is None else o * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(o.dtype)

    def __call__(self, u, seg):
        B, T, _ = u.shape
        q, gate = self._queries(u, (self.n_q,))
        # every key/value head serves n_q // n_kv consecutive query heads
        k, v = (
            p(u).reshape(B, T, self.n_kv, self.head_dim)
            for p in (self.k_proj, self.v_proj)
        )
        pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
        q, k = self._positioned(q, k, pos)
        o = flash_attention_tpu(
            q, k, v, pos, seg, causal=True, sm_scale=self.scale, window=self.window
        )
        return self.o_proj(self._gate(o, gate).reshape(B, T, -1))

    def step(self, u, k_cache, v_cache, count):
        """One acting step over a K/V ring of ``ctx`` slots (B, ctx, kv, D);
        ``count`` (B,) int: steps of this episode already cached. The ring is
        an exact sliding window of ``ctx`` keys (a ``window`` layer's ring has
        ``window`` slots): without positions because a key carries none, with
        them because a key is stored as rotated at its own step and a score
        reads only the difference to the query's."""
        B = u.shape[0]
        ctx = k_cache.shape[1]
        rep = self.n_q // self.n_kv
        q, gate = self._queries(u, (self.n_kv, rep))
        k_new, v_new = (
            p(u).reshape(B, 1, self.n_kv, self.head_dim) for p in (self.k_proj, self.v_proj)
        )
        q, k_new = self._positioned(q, k_new, count[:, None])
        write = (jnp.arange(ctx)[None] == jnp.mod(count, ctx)[:, None])[:, :, None, None]
        k_cache = jnp.where(write, k_new.astype(k_cache.dtype), k_cache)
        v_cache = jnp.where(write, v_new.astype(v_cache.dtype), v_cache)
        valid = jnp.arange(ctx)[None] <= count[:, None]
        scores = jnp.einsum(
            "bgrd,btgd->bgrt", q, k_cache.astype(q.dtype), preferred_element_type=jnp.float32
        ) * jnp.float32(self.scale)
        w = jax.nn.softmax(jnp.where(valid[:, None, None], scores, -jnp.inf), axis=-1)
        o = jnp.einsum(
            "bgrt,btgd->bgrd", w.astype(q.dtype), v_cache.astype(q.dtype),
            preferred_element_type=jnp.float32,
        )
        o = self._gate(o.astype(q.dtype), gate)
        return self.o_proj(o.reshape(B, -1)), k_cache, v_cache


class HybridLayer(nn.Module):
    """One published layer: the mixer of its kind, then the shared gated MLP,
    each behind an RMSNorm and scaled by ``residual_multiplier``."""

    arch: dict
    kind: str  # "mamba" | "attention"
    dtype: Any = None

    def setup(self):
        a = self.arch
        norm = dict(eps=a["rms_norm_eps"], dtype=self.dtype)
        self.input_norm = RMSNorm(name="input_norm", **norm)
        self.post_norm = RMSNorm(name="post_norm", **norm)
        if self.kind == "mamba":
            self.mixer = Mamba2Mixer(
                hidden=a["hidden_size"], heads=a["mamba_n_heads"], d_head=a["mamba_d_head"],
                groups=a["mamba_n_groups"], d_state=a["mamba_d_state"], d_conv=a["mamba_d_conv"],
                chunk=a["mamba_chunk_size"], eps=a["rms_norm_eps"],
                conv_bias=bool(a["mamba_conv_bias"]), proj_bias=bool(a["mamba_proj_bias"]),
                dtype=self.dtype, name="mamba",
            )
        else:
            self.mixer = GQAttention(
                hidden=a["hidden_size"], n_q=a["num_attention_heads"],
                n_kv=a["num_key_value_heads"],
                head_dim=a["hidden_size"] // a["num_attention_heads"],
                scale=a["attention_multiplier"], bias=bool(a["attention_bias"]),
                dtype=self.dtype, name="attention",
            )
        self.mlp_in = nn.Dense(
            2 * a["intermediate_size"], use_bias=False, dtype=self.dtype, name="mlp_in"
        )
        self.mlp_out = nn.Dense(a["hidden_size"], use_bias=False, dtype=self.dtype, name="mlp_out")

    def _mlp(self, x):
        a, b = jnp.split(self.mlp_in(self.post_norm(x)), 2, axis=-1)
        return x + self.arch["residual_multiplier"] * self.mlp_out(jax.nn.silu(a) * b)

    def __call__(self, x, seg, *carry):
        """Training window. ``carry``: the Mamba layer's (state0, tail0)."""
        mixed, *carry = (
            self.mixer(self.input_norm(x), seg, *carry)
            if self.kind == "mamba" else (self.mixer(self.input_norm(x), seg),)
        )
        x = x + self.arch["residual_multiplier"] * mixed
        return (self._mlp(x), *carry)

    def step(self, x, *carry):
        mixed, *carry = self.mixer.step(self.input_norm(x), *carry)
        x = x + self.arch["residual_multiplier"] * mixed
        return (self._mlp(x), *carry)


class GraniteHybridActorCritic(nn.Module):
    n_actions: int
    arch: dict
    act_ctx: int  # slots of the acting K/V ring
    dtype: Any = None  # matmul operand dtype; the residual stream is float32
    remat: bool = True  # tests only: the gradients must not depend on it
    # arrays an attention layer's acting ring holds: keys and values (latent
    # attention, ``models/glm4_moe_lite.py``: one, the latent beside the shared key)
    ring_parts = 2

    def setup(self):
        a = self.arch
        self.embed = nn.Dense(a["hidden_size"], name="embed", dtype=self.dtype)
        layer = nn.remat(HybridLayer) if self.remat else HybridLayer
        self.layers = [
            layer(a, kind, self.dtype, name=f"layer{i}")
            for i, kind in enumerate(a["layer_types"])
        ]
        self.norm_f = RMSNorm(a["rms_norm_eps"], name="norm_f")
        self.logits_head = nn.Dense(self.n_actions, name="logits")
        self.value_head = nn.Dense(1, name="value")
        self.h_width, self.c_width = carry_widths(a, self.act_ctx)
        self.state_shape = (a["mamba_n_heads"], a["mamba_d_head"], a["mamba_d_state"])
        self.tail_shape = (a["mamba_d_conv"] - 1, _conv_channels(a))
        ring = (self.act_ctx, a["num_key_value_heads"], a["hidden_size"] // a["num_attention_heads"])
        self.kv_shapes = [ring] * a["layer_types"].count("attention")

    def _embed(self, obs):
        return self.arch["embedding_multiplier"] * self.embed(obs).astype(jnp.float32)

    def _heads(self, x):
        h = self.norm_f(x)
        logits = self.logits_head(h) / self.arch["logits_scaling"]
        return jax.nn.log_softmax(logits), self.value_head(h)

    def _unpack_h(self, h):
        """(B, h_width) -> one (state, tail) per recurrent layer, float32."""
        if not self.h_width:
            return []
        n_state, n_tail = int(np.prod(self.state_shape)), int(np.prod(self.tail_shape))
        per = h.reshape(h.shape[0], -1, n_state + n_tail)
        return [
            (
                per[:, i, :n_state].reshape(-1, *self.state_shape),
                per[:, i, n_state:].reshape(-1, *self.tail_shape),
            )
            for i in range(per.shape[1])
        ]

    def _unpack_c(self, c):
        """(B, c_width) -> one ring per attention layer — ``ring_parts``
        arrays, each of the layer's own ``kv_shapes`` entry — and the step
        counter (B,) int."""
        rings, at = [], 0
        for shape in self.kv_shapes:
            n = int(np.prod(shape))
            rings.append(tuple(
                c[:, at + i * n: at + (i + 1) * n].reshape(-1, *shape)
                for i in range(self.ring_parts)))
            at += self.ring_parts * n
        return rings, c[:, -1].astype(jnp.int32)

    @staticmethod
    def _pack(pairs, B):
        return jnp.concatenate(
            [jnp.zeros((B, 0), jnp.float32)]
            + [a.reshape(B, -1).astype(jnp.float32) for pair in pairs for a in pair],
            axis=1,
        )

    def _unroll(self, obs, carry0, firsts):
        """``carry0 = (h, c)``: ``h`` of the acting width is the state the
        window starts from; any other width (the batch's 1-float placeholder)
        means zeros. ``c`` is returned as it came. Also returns what the
        layers without a carry handed back beside their output (this family:
        nothing)."""
        B = obs.shape[0]
        h0, c0 = carry0
        if h0.shape[-1] != self.h_width:
            h0 = jnp.zeros((B, self.h_width), jnp.float32)
        seg = segment_ids_from_firsts(firsts)
        x = self._embed(obs)
        mamba = iter(self._unpack_h(h0))
        carried, extras = [], []
        for layer in self.layers:
            if layer.kind in RECURRENT:
                x, state, tail, *more = layer(x, seg, *next(mamba))
                carried.append((state, tail))
            else:
                x, *more = layer(x, seg)
            extras.extend(more)
        logits, value = self._heads(x)
        return logits, value, (self._pack(carried, B), c0), extras

    def __call__(self, obs, carry0, firsts):
        return self._unroll(obs, carry0, firsts)[:3]

    unroll = __call__

    def act(self, obs, h, c):
        """One step for every row of ``obs`` (B, obs_dim)."""
        B = obs.shape[0]
        rings, count = self._unpack_c(c)
        x = self._embed(obs)
        mamba, rings = iter(self._unpack_h(h)), iter(rings)
        carried, caches = [], []
        for layer in self.layers:
            if layer.kind in RECURRENT:
                x, state, tail = layer.step(x, *next(mamba))
                carried.append((state, tail))
            elif layer.kind == "attention":
                x, *ring = layer.step(x, *next(rings), count)
                caches.append(ring)
            else:  # a layer that carries nothing from step to step
                (x,) = layer.step(x)
        logits, value = self._heads(x)
        c2 = jnp.concatenate(
            [self._pack(caches, B), (count + 1).astype(jnp.float32)[:, None]], axis=1
        )
        return logits, value, (self._pack(carried, B), c2)
