"""Sequence-parallelism tests: ring / Ulysses attention must be EXACTLY
equivalent (up to float tolerance) to single-device attention, for outputs
and gradients, on the virtual 8-device CPU mesh (SURVEY.md §4 pattern)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P


from tpu_rl.parallel.sequence import (
    SEQ_AXIS,
    full_attention,
    make_sp_mesh,
    ring_attention,
    segment_ids_from_firsts,
    ulysses_attention,
)


def _inputs(rng, B=2, T=32, H=4, D=8, n_segments=3):
    q = rng.normal(size=(B, T, H, D)).astype(np.float32)
    k = rng.normal(size=(B, T, H, D)).astype(np.float32)
    v = rng.normal(size=(B, T, H, D)).astype(np.float32)
    pos = np.broadcast_to(np.arange(T, dtype=np.int32), (B, T)).copy()
    # random episode seams -> segment ids
    firsts = np.zeros((B, T, 1), np.float32)
    firsts[:, 0] = 1.0
    for b in range(B):
        seams = rng.choice(np.arange(1, T), size=n_segments - 1, replace=False)
        firsts[b, seams] = 1.0
    seg = np.asarray(segment_ids_from_firsts(jnp.asarray(firsts)))
    return map(jnp.asarray, (q, k, v, pos, seg))


def _sharded_attn(impl, mesh, n_seq):
    """shard_map the impl over the seq axis of a (1, n_seq) mesh."""
    spec = P(None, SEQ_AXIS)  # (B, T) ints
    qspec = P(None, SEQ_AXIS, None, None)  # (B, T, H, D)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(qspec, qspec, qspec, spec, spec),
        out_specs=qspec,
    )
    def fn(q, k, v, pos, seg):
        return impl(q, k, v, pos, seg, axis_name=SEQ_AXIS, causal=True)

    return fn


@pytest.mark.parametrize("impl_name", ["ring", "ulysses"])
def test_sharded_matches_full(devices, rng, impl_name):
    impl = {"ring": ring_attention, "ulysses": ulysses_attention}[impl_name]
    n_seq = 4
    mesh = make_sp_mesh(1, n_seq)
    q, k, v, pos, seg = _inputs(rng)
    want = full_attention(q, k, v, pos, seg, causal=True)
    got = jax.jit(_sharded_attn(impl, mesh, n_seq))(q, k, v, pos, seg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


@pytest.mark.parametrize("impl_name", ["ring", "ulysses"])
def test_sharded_gradients_match(devices, rng, impl_name):
    """Backprop through ppermute/all_to_all is exact."""
    impl = {"ring": ring_attention, "ulysses": ulysses_attention}[impl_name]
    mesh = make_sp_mesh(1, 4)
    q, k, v, pos, seg = _inputs(rng, T=16)
    sharded = _sharded_attn(impl, mesh, 4)

    def loss_full(qkv):
        return (full_attention(*qkv, pos, seg, causal=True) ** 2).sum()

    def loss_sharded(qkv):
        return (sharded(*qkv, pos, seg) ** 2).sum()

    g_want = jax.grad(loss_full)((q, k, v))
    g_got = jax.jit(jax.grad(loss_sharded))((q, k, v))
    for a, b in zip(g_got, g_want, strict=True):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_causal_masking(rng):
    """Row t must not depend on any input at positions > t."""
    q, k, v, pos, seg = _inputs(rng, B=1, T=8, n_segments=1)
    out1 = full_attention(q, k, v, pos, seg, causal=True)
    # perturb the future of position 3
    k2 = k.at[:, 5:].set(0.0)
    v2 = v.at[:, 5:].set(99.0)
    out2 = full_attention(q, k2, v2, pos, seg, causal=True)
    np.testing.assert_allclose(
        np.asarray(out1[:, :5]), np.asarray(out2[:, :5]), atol=1e-6
    )
    assert not np.allclose(np.asarray(out1[:, 5:]), np.asarray(out2[:, 5:]))


def test_segment_masking_blocks_cross_episode(rng):
    """Attention must not cross an is_fir seam (episode boundary)."""
    B, T = 1, 8
    q, k, v, _, _ = _inputs(rng, B=B, T=T, n_segments=1)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    # seam at t=4: two episodes [0..3], [4..7]
    firsts = np.zeros((B, T, 1), np.float32)
    firsts[:, 0] = 1.0
    firsts[:, 4] = 1.0
    seg = segment_ids_from_firsts(jnp.asarray(firsts))
    out1 = full_attention(q, k, v, pos, seg, causal=True)
    # changing episode-1 inputs must not affect episode-2 outputs
    k2 = k.at[:, :4].set(7.0)
    v2 = v.at[:, :4].set(-3.0)
    out2 = full_attention(q, k2, v2, pos, seg, causal=True)
    np.testing.assert_allclose(
        np.asarray(out1[:, 4:]), np.asarray(out2[:, 4:]), atol=1e-6
    )


def test_segment_ids_from_firsts():
    firsts = jnp.asarray(
        [[[1.0], [0.0], [1.0], [0.0], [0.0]]], jnp.float32
    )
    seg = segment_ids_from_firsts(firsts)
    np.testing.assert_array_equal(np.asarray(seg), [[1, 1, 2, 2, 2]])


def test_ring_backward_residuals_scale_with_shard_not_ring(devices, rng):
    """Round-1 judge finding: autodiff of the ring scan saved the rotating
    K/V blocks once per ring step — O(n · Tl) = full-sequence residuals per
    chip. The custom VJP recomputes K/V by re-rotating, so residuals must be
    O(Tl): roughly q+k+v+o+lse, and — the load-bearing property — the SAME
    total for a 4-ring and an 8-ring over the same global sequence."""
    try:
        from jax._src.ad_checkpoint import saved_residuals
    except ImportError:
        pytest.skip("saved_residuals not available in this jax")

    B, T = 2, 64
    q, k, v, pos, seg = _inputs(rng, B=B, T=T)
    qkv_bytes = sum(int(np.prod(a.shape)) * 4 for a in (q, k, v))

    def residual_bytes(n_seq):
        sharded = _sharded_attn(ring_attention, make_sp_mesh(1, n_seq), n_seq)

        def loss(q, k, v):
            return (sharded(q, k, v, pos, seg) ** 2).sum()

        res = saved_residuals(loss, q, k, v)
        return sum(
            int(np.prod(aval.shape)) * aval.dtype.itemsize for aval, _ in res
        )

    r4, r8 = residual_bytes(4), residual_bytes(8)
    # Same global problem -> same residual footprint regardless of ring size.
    assert r8 <= r4 * 1.1, (r4, r8)
    # And the footprint is a small multiple of the inputs, not n x inputs.
    assert r8 <= 2.5 * qkv_bytes, (r8, qkv_bytes)


def test_dp_sp_mesh_shapes(devices):
    mesh = make_sp_mesh(2, 4)
    assert mesh.shape == {"data": 2, "seq": 4}
    with pytest.raises(ValueError):
        make_sp_mesh(4, 4)  # 16 > 8 devices


class TestBlockwiseAttention:
    """Single-device memory-efficient attention (no (T,T) scores) must match
    full attention exactly — outputs AND gradients — including segment seams
    and non-divisible block sizes."""

    def _case(self, rng, T=48, block=16):
        from tpu_rl.parallel.sequence import blockwise_attention

        q, k, v, pos, seg = _inputs(rng, B=2, T=T, H=4, D=8, n_segments=3)
        w = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

        def loss_full(q, k, v):
            o = full_attention(q, k, v, pos, seg, causal=True)
            return (o * w).mean()

        def loss_blk(q, k, v):
            o = blockwise_attention(
                q, k, v, pos, seg, causal=True, block=block
            )
            return (o * w).mean()

        vf, gf = jax.value_and_grad(loss_full, argnums=(0, 1, 2))(q, k, v)
        vb, gb = jax.value_and_grad(loss_blk, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(float(vb), float(vf), rtol=2e-5)
        for a, b in zip(gb, gf, strict=True):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
            )

    def test_multi_block_matches_full(self, rng):
        self._case(rng, T=48, block=16)

    def test_non_divisible_block_pads(self, rng):
        self._case(rng, T=50, block=16)  # 4 tiles of 13, 2 masked pad rows

    def test_prime_length_pads(self, rng):
        self._case(rng, T=53, block=16)  # padding, not block-1 degeneration

    def test_single_block_degenerates_to_full(self, rng):
        self._case(rng, T=32, block=512)

    def test_transformer_blockwise_unroll_matches_full(self, rng):
        """End-to-end through the policy module: same params, same batch,
        attention_impl full vs blockwise."""
        from tests.conftest import small_config
        from tpu_rl.models.families import build_family

        kw = dict(
            algo="PPO", model="transformer", hidden_size=32, n_heads=4,
            n_layers=2, seq_len=32, batch_size=2, obs_shape=(4,),
            action_space=2,
        )
        fam_f = build_family(small_config(**kw, attention_impl="full"))
        fam_b = build_family(small_config(**kw, attention_impl="blockwise"))
        params = fam_f.init_params(jax.random.key(0), seq_len=32)
        obs = jnp.asarray(rng.normal(size=(2, 32, 4)).astype(np.float32))
        firsts = np.zeros((2, 32, 1), np.float32)
        firsts[:, 0] = 1.0
        firsts[0, 11] = 1.0
        firsts = jnp.asarray(firsts)
        lf, vf, _ = fam_f.actor_unroll(params["actor"], obs, None, firsts)
        lb, vb, _ = fam_b.actor_unroll(params["actor"], obs, None, firsts)
        np.testing.assert_allclose(
            np.asarray(lb), np.asarray(lf), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(vb), np.asarray(vf), rtol=1e-5, atol=1e-5
        )


def _segment_relative(seg):
    """Positions that restart at every seam, as the transformer passes them."""
    seg_np = np.asarray(seg)
    idx = np.broadcast_to(np.arange(seg_np.shape[1], dtype=np.int32), seg_np.shape)
    seam = np.concatenate(
        [np.ones_like(seg_np[:, :1], bool), seg_np[:, 1:] != seg_np[:, :-1]], axis=1
    )
    starts = np.maximum.accumulate(np.where(seam, idx, 0), axis=1)
    return jnp.asarray(idx - starts)


class TestFlashImpl:
    """The "flash" impl (the library's splash kernel, tpu_rl.parallel.sequence
    .flash_attention_tpu). The Mosaic kernel cannot execute on the CPU test
    backend, but its arithmetic can: the production construction
    (``_splash_mha``: scale folded into q, causal-by-index +
    ``SegmentIds``, grouped heads unrepeated, the rule's backward form) runs in
    interpret mode against full_attention, forward and gradients. Off-TPU the
    impl itself falls back to full_attention exactly."""

    @pytest.mark.parametrize("sm_scale", [None, 1.0 / 64], ids=["scale-default", "scale-1/64"])
    @pytest.mark.parametrize("positions", ["global", "segment-relative"])
    @pytest.mark.parametrize("n_kv", [4, 2], ids=["equal-heads", "grouped-4:2"])
    def test_splash_matches_full_attention(self, rng, n_kv, positions, sm_scale):
        """T 256 in tiles of 128 (a diagonal, an interior and a skipped tile
        per head), head dim 64, 4 segments a row. Segment-relative positions
        must still equal causal-by-global-index: positions are monotone within
        a segment and the segment mask kills every cross-segment pair."""
        from tpu_rl.parallel.sequence import _splash_mha, _splash_block_sizes

        T, H, D = 256, 4, 64
        q, k, v, pos, seg = _inputs(rng, T=T, H=H, D=D, n_segments=4)
        k, v = k[:, :, :n_kv], v[:, :, :n_kv]
        if positions == "segment-relative":
            pos = _segment_relative(seg)
            assert int((np.asarray(pos) == 0).sum(axis=1).min()) >= 4
        scale = 1.0 / np.sqrt(D) if sm_scale is None else sm_scale
        tiles = _tiles_of(_splash_block_sizes(T), 128)
        cot = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

        def splash(q, k, v):
            out = _splash_mha(
                q, k, v, seg, causal=True, scale=float(scale),
                block_sizes=tiles, interpret=True,
            )
            return (out * cot).sum(), out

        def full(q, k, v):
            kr, vr = (jnp.repeat(x, H // n_kv, axis=2) for x in (k, v))
            out = full_attention(q, kr, vr, pos, seg, causal=True, sm_scale=sm_scale)
            return (out * cot).sum(), out

        grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        got_g, got = grad(splash)
        want_g, want = grad(full)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
        for name, g, w in zip(("dq", "dk", "dv"), got_g, want_g):
            assert g.shape == w.shape
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5, err_msg=name
            )

    @pytest.mark.parametrize("window", [160, 128, 512], ids=["window-160", "one-tile", "whole-T"])
    def test_splash_with_a_window_matches_full_attention(self, rng, window):
        """T 512 in tiles of 128, 4 : 2 heads, 4 segments a row, so seams fall
        inside a band and behind it. At 160 keys a query's band crosses two tile
        edges (the diagonal tile and up to two behind it, the fourth row's
        first tile never visited); at 128 one. Forward and the three gradients."""
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_mask as masks,
            splash_attention_mask_info as mask_info,
        )
        from tpu_rl.parallel.sequence import _splash_mha, _splash_block_sizes

        T, H, D, n_kv = 512, 4, 64, 2
        q, k, v, pos, seg = _inputs(rng, T=T, H=H, D=D, n_segments=4)
        k, v = k[:, :, :n_kv], v[:, :, :n_kv]
        tiles = _tiles_of(_splash_block_sizes(T), 128)
        cot = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

        def splash(q, k, v):
            out = _splash_mha(
                q, k, v, seg, causal=True, scale=float(1.0 / np.sqrt(D)),
                block_sizes=tiles, interpret=True, window=window,
            )
            return (out * cot).sum(), out

        def full(q, k, v):
            kr, vr = (jnp.repeat(x, H // n_kv, axis=2) for x in (k, v))
            out = full_attention(q, kr, vr, pos, seg, causal=True, window=window)
            return (out * cot).sum(), out

        grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        got_g, got = grad(splash)
        want_g, want = grad(full)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
        for name, g, w in zip(("dq", "dk", "dv"), got_g, want_g):
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5, err_msg=name
            )
        # the band's tiles alone are in the kernel's grid: what the window saves
        visited = lambda m: int((np.asarray(mask_info.process_mask(  # noqa: E731
            masks.MultiHeadMask([m]), (128, 128), is_dkv=False)[0].block_mask) > 0).sum())
        causal = visited(masks.CausalMask((T, T)))
        band = visited(masks.LocalMask((T, T), (window - 1, 0), 0))
        assert causal == 10 and band == {160: 9, 128: 7, 512: 10}[window]
        if window < T:
            whole = full_attention(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
                                   pos, seg, causal=True)
            assert float(jnp.abs(whole - want).max()) > 1e-3  # the window cuts something

    def test_falls_back_to_full_off_tpu(self, rng):
        from tpu_rl.parallel.sequence import flash_attention_tpu

        if jax.default_backend() == "tpu":
            pytest.skip("fallback path only exists off-TPU")
        q, k, v, pos, seg = _inputs(rng)
        want = full_attention(q, k, v, pos, seg, causal=True)
        got = flash_attention_tpu(q, k, v, pos, seg, causal=True)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    @pytest.mark.parametrize("T", [128, 256, 384, 512, 1536, 2048, 4096])
    def test_block_size_selection(self, T):
        """The tile rule, asserted on the PRODUCTION selector the dispatch
        calls: every edge divides T (grid exactness) and its compute edge,
        and the backward has its tiles."""
        from tpu_rl.parallel.sequence import _splash_block_sizes

        bs = _splash_block_sizes(T)
        assert bs.has_backward_blocks
        for mem, comp in ((bs.block_kv, bs.block_kv_compute),
                          (bs.block_kv_dkv, bs.block_kv_dkv_compute)):
            assert T % mem == 0 and mem % comp == 0 and comp % 128 == 0, bs
        for edge in (bs.block_q, bs.block_q_dkv, bs.block_q_dq, bs.block_kv_dq):
            assert edge is None or (T % edge == 0 and edge % 128 == 0), bs

    @pytest.mark.parametrize("T", [64, 96, 100, 200])
    def test_untileable_length_takes_full_attention(self, T):
        """T % 128 != 0: no tiles, and the dispatch has no library-default
        branch left — it takes full_attention, as off-TPU."""
        from tpu_rl.parallel.sequence import _splash_block_sizes

        assert _splash_block_sizes(T) is None

    def test_transformer_flash_config_builds_and_matches_full(self, rng):
        from tests.conftest import small_config
        from tpu_rl.models.families import build_family

        kw = dict(
            algo="PPO", model="transformer", hidden_size=32, n_heads=4,
            n_layers=2, seq_len=32, batch_size=2, obs_shape=(4,),
            action_space=2,
        )
        fam_f = build_family(small_config(**kw, attention_impl="full"))
        fam_x = build_family(small_config(**kw, attention_impl="flash"))
        params = fam_f.init_params(jax.random.key(0), seq_len=32)
        obs = jnp.asarray(rng.normal(size=(2, 32, 4)).astype(np.float32))
        firsts = np.zeros((2, 32, 1), np.float32)
        firsts[:, 0] = 1.0
        firsts[1, 7] = 1.0
        firsts = jnp.asarray(firsts)
        lf, vf, _ = fam_f.actor_unroll(params["actor"], obs, None, firsts)
        lx, vx, _ = fam_x.actor_unroll(params["actor"], obs, None, firsts)
        np.testing.assert_array_equal(np.asarray(lx), np.asarray(lf))
        np.testing.assert_array_equal(np.asarray(vx), np.asarray(vf))


# ------------------------------------------------- seam-empty tiles (PR 35)
def _tiles_of(rule, edge):
    """The production block sizes with every edge set to ``edge``."""
    import dataclasses

    return dataclasses.replace(rule, **{
        f.name: edge for f in dataclasses.fields(rule)
        if f.name.startswith("block_") and getattr(rule, f.name) is not None
    })


def _seg_from_seams(T, rows):
    """(B, T) monotone segment ids: row b starts an episode at 0 and at every
    step of ``rows[b]``."""
    fir = np.zeros((len(rows), T), np.int32)
    fir[:, 0] = 1
    for b, seams in enumerate(rows):
        fir[b, list(seams)] = 1
    return np.cumsum(fir, axis=1).astype(np.int32)


def _empty_twin(seg, edge):
    """``seam_empty_tiles`` as loops over numpy rows and blocks."""
    B, T = seg.shape
    n = T // edge
    out = np.zeros((B, n, n), bool)
    for b in range(B):
        for i in range(n):
            qs = seg[b, i * edge:(i + 1) * edge]
            for j in range(n):
                ks = seg[b, j * edge:(j + 1) * edge]
                out[b, i, j] = ks.max() < qs.min() or ks.min() > qs.max()
    return out


def _tiles_with_a_pair(seg, edge):
    """Brute force over the (T, T) same-segment mask: the tiles that hold at
    least one query-key pair of equal ids."""
    B, T = seg.shape
    n = T // edge
    same = seg[:, :, None] == seg[:, None, :]
    return same.reshape(B, n, edge, n, edge).any(axis=(2, 4))


# T 512 in tiles of 128: seams by row
SEAMS = {
    "no-seam": [(), ()],
    "inside-a-tile": [(200,), (200,)],
    "on-a-tile-edge": [(256,), (128, 384)],
    "every-few-steps": [range(5, 512, 7), range(3, 512, 11)],
    "two-rows-differ": [(130, 300), (256,)],
}


class TestSeamSkipping:
    """PR 35: where the splash grid has ``_SEAM_BLOCKS`` blocks an edge or more,
    ``_splash_mha`` zeroes each row's block-mask entries for the tiles in
    which no query and key share a segment (``seam_empty_tiles``), so the
    kernels step over them. Interpret mode on the CPU: the result must equal
    the static call's to the bit, since the in-kernel segment mask gave those
    tiles no weight before. The production gate is eight blocks an edge; the
    cases here run the rule at its own minimum, three (4 x 4 grids of
    128-tiles), and two tests hold the production value."""

    @pytest.fixture(autouse=True)
    def three_blocks_engage(self, monkeypatch):
        from tpu_rl.parallel import sequence

        self.production_gate = sequence._SEAM_BLOCKS
        monkeypatch.setattr(sequence, "_SEAM_BLOCKS", 3)

    @pytest.mark.parametrize("ids", [*SEAMS, "non-monotone", "random-monotone"])
    @pytest.mark.parametrize("edge", [128, 32])
    def test_empty_tiles_against_the_twin_and_a_brute_force_mask(self, rng, ids, edge):
        from tpu_rl.parallel.sequence import seam_empty_tiles

        T = 512
        if ids == "non-monotone":  # ids that come back: sound, not complete
            seg = rng.integers(0, 3, size=(2, T // 16)).repeat(16, axis=1).astype(np.int32)
            seg[1] = np.where(np.arange(T) < 256, 7, np.where(np.arange(T) % 2, 9, 5))
        elif ids == "random-monotone":
            seg = _seg_from_seams(T, [rng.choice(np.arange(1, T), 6, replace=False) for _ in range(3)])
        else:
            seg = _seg_from_seams(T, SEAMS[ids])
        got = np.asarray(seam_empty_tiles(jnp.asarray(seg), edge))
        assert got.dtype == bool and got.shape == (seg.shape[0], T // edge, T // edge)
        np.testing.assert_array_equal(got, _empty_twin(seg, edge))
        np.testing.assert_array_equal(seam_empty_tiles(seg, edge), got)  # numpy in, numpy out
        holds_a_pair = _tiles_with_a_pair(seg, edge)
        assert not (got & holds_a_pair).any()  # sound: never a kept pair in a tile called empty
        assert not got[:, np.arange(T // edge), np.arange(T // edge)].any()  # the diagonal
        if ids == "non-monotone":
            assert (~got & ~holds_a_pair).any()  # ranges meet, ids do not: computed, masked inside
        else:
            np.testing.assert_array_equal(got, ~holds_a_pair)  # complete for monotone ids
        if ids == "no-seam":
            assert not got.any()

    @pytest.mark.parametrize("T,edge,window", [
        (16384, 1024, None), (16384, 1024, 4096), (4096, 1024, None), (2048, 1024, None),
        (512, 128, 160), (512, 128, 128), (512, 128, 512), (1024, 128, 129), (1024, 128, 1),
    ])
    def test_band_tiles_are_the_librarys_block_mask(self, T, edge, window):
        """The static band the counters use against what the kernels read:
        the fused backward's unshrunk [query block, key block] mask."""
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_mask as masks,
            splash_attention_mask_info as mask_info,
        )
        from tpu_rl.parallel.sequence import band_tiles

        mask = (masks.CausalMask((T, T)) if window is None
                else masks.LocalMask((T, T), (window - 1, 0), 0))
        info, _ = mask_info.process_mask_dkv(
            masks.MultiHeadMask([mask]), (edge, edge), shrink_grid=False)
        np.testing.assert_array_equal(band_tiles(T, edge, window), np.asarray(info.block_mask)[0] > 0)
        if (T, window) == (16384, None):
            assert band_tiles(T, edge, window).sum() == 136
        if (T, window) == (16384, 4096):
            assert band_tiles(T, edge, window).sum() == 70

    @pytest.mark.parametrize("T,edge,window", [
        (16384, 1024, None), (16384, 1024, 4096), (4096, 1024, None), (512, 128, 160),
        (512, 128, 128), (1024, 128, 300),
    ])
    def test_the_prefetch_indices_follow_the_librarys_rule(self, T, edge, window):
        """``_next_computed`` on the library's own block masks gives back the
        library's ``data_next``, in the forward's (shrunk) grid walked query
        block by query block and in the fused backward's walked key block by
        key block; with tiles zeroed, every step names the block of the next
        step that computes."""
        from jax.experimental.pallas.ops.tpu.splash_attention import (
            splash_attention_mask as masks,
            splash_attention_mask_info as mask_info,
        )
        from tpu_rl.parallel.sequence import _next_computed

        mask = (masks.CausalMask((T, T)) if window is None
                else masks.LocalMask((T, T), (window - 1, 0), 0))
        heads = masks.MultiHeadMask([mask] * 2)
        fwd, _ = mask_info.process_mask(heads, (edge, edge), head_shards=1, q_seq_shards=1)
        dkv, _ = mask_info.process_mask_dkv(
            heads, (edge, edge), head_shards=1, q_seq_shards=1, shrink_grid=False)
        for info, walk in ((fwd, lambda x: x), (dkv, np.transpose)):
            block_mask, data_next = (walk(np.asarray(x)[0]) for x in (info.block_mask, info.data_next))
            got = np.asarray(_next_computed(jnp.asarray(block_mask), jnp.asarray(data_next)))
            np.testing.assert_array_equal(got, data_next)
            fewer = block_mask.copy().reshape(-1)
            computed = np.flatnonzero(fewer)
            fewer[computed[1::2]] = 0  # every other computed tile emptied
            got = np.asarray(_next_computed(
                jnp.asarray(fewer.reshape(block_mask.shape)), jnp.asarray(data_next))).reshape(-1)
            left = np.flatnonzero(fewer)
            for at in range(fewer.size):
                nxt = left[left >= at][0] if (left >= at).any() else left[0]
                assert got[at] == data_next.reshape(-1)[nxt], (at, nxt)

    @pytest.mark.parametrize("window", [None, 160], ids=["global", "window-160"])
    @pytest.mark.parametrize("case", [*SEAMS, "grouped-14:2"])
    def test_seam_skipping_equals_the_static_call_to_the_bit(self, rng, monkeypatch, case, window):
        """Forward and dq / dk / dv, T 512 in tiles of 128 (a 4 x 4 grid; the
        window's forward grid shrunk to 3 slots a row), two rows under the
        batch; 28:4-shaped heads (seven query heads a key head) in the grouped
        case. Against the static call: equal to the bit. Against
        ``full_attention``: to float tolerance."""
        from tpu_rl.parallel import sequence
        from tpu_rl.parallel.sequence import _splash_mha, _splash_block_sizes, seam_empty_tiles

        T, D = 512, 64
        H, n_kv = (14, 2) if case == "grouped-14:2" else (4, 2)
        q, k, v, pos, _ = _inputs(rng, T=T, H=H, D=D)
        k, v = k[:, :, :n_kv], v[:, :, :n_kv]
        seg = jnp.asarray(_seg_from_seams(T, SEAMS.get(case, SEAMS["two-rows-differ"])))
        pos = _segment_relative(seg)
        tiles = _tiles_of(_splash_block_sizes(T), 128)
        cot = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

        def splash(q, k, v, seg):
            out = _splash_mha(
                q, k, v, seg, causal=True, scale=float(1.0 / np.sqrt(D)),
                block_sizes=tiles, interpret=True, window=window,
            )
            return (out * cot).sum(), out

        def full(q, k, v, seg):
            kr, vr = (jnp.repeat(x, H // n_kv, axis=2) for x in (k, v))
            out = full_attention(q, kr, vr, pos, seg, causal=True, window=window)
            return (out * cot).sum(), out

        # seg an argument: the block masks are traced, as in the update program
        grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(q, k, v, seg)  # noqa: E731
        got_g, got = grad(splash)
        monkeypatch.setattr(sequence, "_SEAM_BLOCKS", 10 ** 9)
        static_g, static = grad(lambda *a: splash(*a))  # a new function: a new trace
        want_g, want = grad(full)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(static))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
        for name, g, s, w in zip(("dq", "dk", "dv"), got_g, static_g, want_g):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(s), err_msg=name)
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5, err_msg=name)
        skipped = int(np.asarray(seam_empty_tiles(seg, 128))[:, np.tril_indices(4)[0],
                                                              np.tril_indices(4)[1]].sum())
        assert (skipped == 0) == (case == "no-seam")
        if case == "every-few-steps":  # episodes shorter than a tile: a tile two blocks under
            assert skipped >= 2 * 3  # the diagonal is always empty, the one next to it hardly ever

    def test_without_a_causal_mask_the_tiles_ahead_are_skipped_too(self, rng, monkeypatch):
        """``causal=False``: a key block wholly in later episodes is as empty as
        one wholly in earlier ones (``lo[j] > hi[i]``)."""
        from tpu_rl.parallel import sequence
        from tpu_rl.parallel.sequence import _splash_mha, _splash_block_sizes

        T, D = 512, 64
        q, k, v, pos, _ = _inputs(rng, T=T, H=2, D=D)
        seg = jnp.asarray(_seg_from_seams(T, SEAMS["two-rows-differ"]))
        call = lambda: jax.jit(lambda q, k, v, seg: _splash_mha(  # noqa: E731
            q, k, v, seg, causal=False, scale=0.125,
            block_sizes=_tiles_of(_splash_block_sizes(T), 128), interpret=True))(q, k, v, seg)
        got = call()
        monkeypatch.setattr(sequence, "_SEAM_BLOCKS", 10 ** 9)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(call()))
        want = full_attention(q, k, v, pos, seg, causal=False)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("T,edge,traced", [
        (256, 128, False), (2048, 1024, False), (4096, 1024, False), (896, 128, False),
        (1024, 128, True), (16384, 1024, True)])
    def test_a_grid_under_the_gate_takes_the_static_call(self, monkeypatch, T, edge, traced):
        """Chosen by shape, at the production gate: under eight blocks an edge
        (``tf-longctx``: T 2,048 in tiles of 1,024; granite and nemotron: 4,096)
        the program is the static call's, with no mask info read from the
        segment ids; from eight on (smallthinker: 16) the block masks are traced."""
        from tpu_rl.parallel import sequence
        from tpu_rl.parallel.sequence import _splash_mha, _splash_block_sizes

        assert self.production_gate == 8
        monkeypatch.setattr(sequence, "_SEAM_BLOCKS", self.production_gate)
        assert _splash_block_sizes(T).block_q == edge or edge == 128
        tiles = _tiles_of(_splash_block_sizes(T), edge)
        shapes = [jax.ShapeDtypeStruct((2, T, 4, 64), jnp.float32)] * 3 + [
            jax.ShapeDtypeStruct((2, T), jnp.int32)]

        def program():
            def f(q, k, v, seg):
                return _splash_mha(q, k, v, seg, causal=True, scale=0.125, block_sizes=tiles,
                                   interpret=True).sum()
            return str(jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(*shapes))

        here = program()
        monkeypatch.setattr(sequence, "_SEAM_BLOCKS", 10 ** 9)
        static = program()
        assert "reduce_min" not in static
        assert (here != static) == traced and ("reduce_min" in here) == traced

    @pytest.mark.parametrize("window", [None, 160, 128], ids=["global", "window-160", "one-tile"])
    @pytest.mark.parametrize("case", list(SEAMS))
    def test_the_tile_counters_against_a_count(self, case, window):
        from tpu_rl.parallel.sequence import attention_tiles, band_tiles

        T, edge = 512, 128
        seg = _seg_from_seams(T, SEAMS[case])
        band = band_tiles(T, edge, window)
        want_run = sum(int((band & ~e).sum()) for e in _empty_twin(seg, edge))
        run, total, steps = jax.jit(lambda s: attention_tiles(s, window, edge))(jnp.asarray(seg))
        assert (float(run), float(total)) == (want_run, 2 * band.sum())
        assert float(steps) == float(total)  # the backward's grid is the band
        assert run.dtype == total.dtype == steps.dtype == jnp.float32
        if case == "no-seam":
            assert float(run) == float(total)
        if case == "two-rows-differ" and window is None:
            assert (float(run), float(total)) == (13.0, 20.0)

    @pytest.mark.parametrize("T,edge_in,blocks", [(2048, None, 2), (4096, None, 4), (16384, None, 16),
                                                  (256, 128, 2), (32, None, 1)])
    def test_the_tile_counters_follow_the_kernels_gate_and_edge(self, monkeypatch, T, edge_in, blocks):
        """The edge is the kernel's own (gcd(1024, T)); where the grid is too
        small for the traced masks every band tile runs, whatever the seams."""
        from tpu_rl.parallel import sequence
        from tpu_rl.parallel.sequence import attention_tiles, band_tiles

        monkeypatch.setattr(sequence, "_SEAM_BLOCKS", self.production_gate)
        edge = edge_in or T // blocks
        seg = _seg_from_seams(T, [(T // 2,)])  # empties the tile under the diagonal of a 2 x 2 grid
        run, total, steps = attention_tiles(jnp.asarray(seg), None, edge_in)
        band = band_tiles(T, edge)
        assert band.shape == (blocks, blocks) and float(total) == band.sum()
        static = blocks < self.production_gate
        # under the gate the library's fused backward steps over the rectangle
        assert float(steps) == (blocks * blocks if static else band.sum())
        want = band.sum() if static else (band & ~_empty_twin(seg, edge)[0]).sum()
        assert float(run) == want and (static or want < band.sum())


# ------------------------- the repo's own backward over the band's tiles (PR 40)
def _brute_force_steps(band, empty):
    """The walk ``band_steps`` should list, as loops: key block by key block,
    query blocks ascending, the tiles of ``band`` that ``empty`` leaves."""
    n_q, n_kv = band.shape
    kept = [(j, i) for j in range(n_kv) for i in range(n_q) if band[i, j] and not empty[i, j]]
    flags = []
    for at, (j, _) in enumerate(kept):
        first = at == 0 or kept[at - 1][0] != j
        last = at == len(kept) - 1 or kept[at + 1][0] != j
        flags.append(1 + 2 * first + 4 * last)
    return kept, flags


class TestBandBackward:
    """PR 40: where ``_splash_mha`` reads each row's block masks from its
    segment ids, the backward is ``ops/pallas_attn_bwd.py``'s: a grid as long
    as the static band, walked by a scalar-prefetched list of the tiles no seam
    emptied, one head's dq added in float32 in a resident block. Interpret mode
    on the CPU; the gate at three blocks an edge as in :class:`TestSeamSkipping`."""

    @pytest.fixture(autouse=True)
    def three_blocks_engage(self, monkeypatch):
        from tpu_rl.parallel import sequence

        self.production_gate = sequence._SEAM_BLOCKS
        monkeypatch.setattr(sequence, "_SEAM_BLOCKS", 3)

    @pytest.mark.parametrize("T,edge,window,seams", [
        (16384, 1024, None, (5000, 11000)), (16384, 1024, 4096, (5000, 11000)),
        (8192, 1024, None, (2048, 4096, 6000)), (16384, 1024, None, ()),
        (512, 128, 160, (200,)), (512, 128, None, (256,)), (512, 128, 128, (128, 384)),
        (1024, 128, 300, tuple(range(5, 1024, 7))), (1024, 128, 1, (130, 300)),
    ])
    def test_the_step_list_against_a_brute_force_walk(self, T, edge, window, seams):
        """Every tile of ``band_tiles & ~seam_empty_tiles`` once, in key-block
        order with query blocks ascending; the first / last flags where a key
        block's steps begin and end; then a tail that computes nothing and
        repeats the last computing step's indices."""
        from tpu_rl.ops.pallas_attn_bwd import band_steps
        from tpu_rl.parallel.sequence import band_tiles, seam_empty_tiles

        seg = _seg_from_seams(T, [seams])
        band = band_tiles(T, edge, window)
        empty = _empty_twin(seg, edge)[0] if T <= 1024 else np.asarray(seam_empty_tiles(seg, edge))[0]
        kv_of, q_of, flags = (np.asarray(x) for x in jax.jit(
            lambda e: band_steps(band, e))(jnp.asarray(empty)))
        assert kv_of.dtype == q_of.dtype == flags.dtype == np.int32
        assert kv_of.shape == q_of.shape == flags.shape == (band.sum(),)
        if (T, window) in ((16384, None), (16384, 4096), (8192, None)):
            assert band.sum() == {(16384, None): 136, (16384, 4096): 70, (8192, None): 36}[T, window]
        kept, want_flags = _brute_force_steps(band, empty)
        n = len(kept)
        assert n == (band & ~empty).sum() and (n < band.sum()) == bool(seams and (band & empty).any())
        assert list(zip(kv_of[:n], q_of[:n])) == kept and list(flags[:n]) == want_flags
        assert (flags[n:] == 0).all()  # the tail: no fetch, no write
        assert (kv_of[n:] == kept[-1][0]).all() and (q_of[n:] == kept[-1][1]).all()
        # a key block's steps are consecutive, and every key block has some: dk and dv
        # of every block are written, once
        assert sorted({j for j, _ in kept}) == list(range(T // edge))
        assert sum(f & 2 > 0 for f in flags) == sum(f & 4 > 0 for f in flags) == T // edge

    @pytest.mark.parametrize("D", [64, 128], ids=["head-64", "head-128"])
    @pytest.mark.parametrize("window", [None, 160], ids=["global", "window-160"])
    @pytest.mark.parametrize("case", [*SEAMS, "grouped-14:2", "equal-heads"])
    def test_no_farther_from_full_attention_than_the_librarys_backward(self, rng, case, window, D):
        """bf16 inputs, as the cells': dq / dk / dv of the new backward and of
        the library's fused one on the same traced masks, each against
        ``full_attention`` in float32 on the same inputs. out, dk and dv walk
        the tiles in the library's order and are its to the bit; dq is rounded
        once, not once a key block: no farther in the mean, within one bf16
        step of the library's."""
        from tpu_rl.parallel import sequence
        from tpu_rl.parallel.sequence import _splash_block_sizes

        T = 512
        H, n_kv = {"grouped-14:2": (14, 2), "equal-heads": (2, 2)}.get(case, (4, 2))
        q, k, v, _, _ = _inputs(rng, B=1, T=T, H=H, D=D)
        scale = float(1.0 / np.sqrt(D))
        q, k, v = ((x[0] * s).astype(jnp.bfloat16)  # one row, (T, H, D)
                   for x, s in ((q, scale), (k[:, :, :n_kv], 1.0), (v[:, :, :n_kv], 1.0)))
        seg = jnp.asarray(_seg_from_seams(T, SEAMS.get(case, SEAMS["two-rows-differ"]))[:1])
        pos = _segment_relative(seg)
        tiles = _tiles_of(_splash_block_sizes(T), 128)
        cot = jnp.asarray(rng.normal(size=(T, H, D)).astype(np.float32))

        def grads(attend):
            def loss(q, k, v, seg):
                return (attend(q, k, v, seg).astype(jnp.float32) * cot).sum()
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v, seg)

        def splash(row):
            def attend(q, k, v, seg):
                kernel = sequence._splash_kernel(
                    T, H, causal=True, window=window, block_sizes=tiles, interpret=True)
                empty = sequence.seam_empty_tiles(seg, 128)[0]
                return row((True, window, 0), kernel, q, k, v, seg[0], empty)
            return attend

        def full(q, k, v, seg):
            q, k, v = (x.astype(jnp.float32)[None] for x in (q, k, v))
            k, v = (jnp.repeat(x, H // n_kv, axis=2) for x in (k, v))
            return full_attention(q, k, v, pos, seg, causal=True, sm_scale=1.0, window=window)[0]

        own = grads(splash(sequence._seam_row))
        lib = grads(splash(sequence._seam_row_forward))
        want = grads(full)
        f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
        for name, g, t, w in zip(("dq", "dk", "dv"), own, lib, want):
            assert g.dtype == t.dtype == jnp.bfloat16 and g.shape == t.shape == w.shape, name
            far, far_lib = (np.abs(f32(x) - f32(w)) for x in (g, t))
            assert far.mean() <= far_lib.mean(), name
            assert far.max() <= 3e-2 * np.abs(f32(w)).max(), name
            if name != "dq":
                np.testing.assert_array_equal(f32(g), f32(t), err_msg=name)
        # the library rounds a partial a key block at the partial's size, which a sum
        # may fall under: one bf16 step at the size of dq, not of the element
        assert np.abs(f32(own[0]) - f32(lib[0])).max() <= 2.0 ** -7 * np.abs(f32(lib[0])).max()

    @pytest.mark.parametrize("heads", [(4, 2), (2, 2)], ids=["grouped-4:2", "equal-heads"])
    def test_inner_steps_of_fewer_keys_than_a_tile(self, rng, heads):
        """``block_kv_dkv_compute`` under ``block_kv_dkv`` (512 of 1,024 in the
        cells): a tile's keys in two inner steps, dk and dv gathered at their
        rows of the scratch, dq added twice. Against the library's backward at
        the same blocks (dk, dv to the bit) and ``full_attention``."""
        import dataclasses

        from tpu_rl.parallel.sequence import _splash_mha, _splash_block_sizes

        T, D = 1024, 64
        H, n_kv = heads
        q, k, v, _, _ = _inputs(rng, T=T, H=H, D=D)
        k, v = k[:, :, :n_kv], v[:, :, :n_kv]
        seg = jnp.asarray(_seg_from_seams(T, [(300, 700), (512,)]))
        pos = _segment_relative(seg)
        tiles = dataclasses.replace(
            _tiles_of(_splash_block_sizes(T), 256), block_kv_compute=128, block_kv_dkv_compute=128)
        cot = jnp.asarray(rng.normal(size=q.shape).astype(np.float32))

        def splash(q, k, v, seg):
            out = _splash_mha(q, k, v, seg, causal=True, scale=0.125, block_sizes=tiles,
                              interpret=True)
            return (out * cot).sum()

        def full(q, k, v, seg):
            kr, vr = (jnp.repeat(x, H // n_kv, axis=2) for x in (k, v))
            return (full_attention(q, kr, vr, pos, seg, causal=True) * cot).sum()

        grad = lambda f: jax.jit(jax.grad(f, argnums=(0, 1, 2)))(q, k, v, seg)  # noqa: E731
        own, want = grad(splash), grad(full)
        text = str(jax.make_jaxpr(jax.grad(splash, argnums=(0, 1, 2)))(q, k, v, seg))
        assert "attn_bwd_band" in text and "splash_mha_dkv" not in text
        for name, g, w in zip(("dq", "dk", "dv"), own, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-4, atol=1e-5,
                                       err_msg=name)

    @pytest.mark.parametrize("B", [2, 1], ids=["two-rows", "one-row"])
    @pytest.mark.parametrize("window", [None, 4096], ids=["global", "window-4096"])
    def test_no_dq_partials_and_no_sum_over_key_blocks(self, window, B):
        """The engaged program at a cell's shape (T 16,384 in tiles of 1,024,
        28 : 4 heads of 128, the production gate): the backward is one
        ``attn_bwd_band`` call a row whose grid is (heads, the band's tiles);
        no array has a leading axis of T / bkv = 16 key blocks over (H, T, D),
        and no ``reduce_sum`` runs over one. Where several rows are walked a
        call declares one more output of the partials' size that the kernel never
        writes and of which one element is read (``_splash_rows_skipping_seams``
        says why); a single row declares none."""
        from tpu_rl.parallel import sequence
        from tpu_rl.parallel.sequence import _splash_mha, _splash_block_sizes

        sequence._SEAM_BLOCKS = self.production_gate  # monkeypatch restores it
        T, H, n_kv, D = 16384, 28, 4, 128
        tiles = _splash_block_sizes(T)
        shapes = [jax.ShapeDtypeStruct((B, T, h, D), jnp.bfloat16) for h in (H, n_kv, n_kv)]
        shapes.append(jax.ShapeDtypeStruct((B, T), jnp.int32))

        def f(q, k, v, seg):
            out = _splash_mha(q, k, v, seg, causal=True, scale=D ** -0.5, block_sizes=tiles,
                              interpret=True, window=window)
            return out.astype(jnp.float32).sum()

        jaxpr = jax.make_jaxpr(jax.grad(f, argnums=(0, 1, 2)))(*shapes)
        blocks = T // tiles.block_kv_dkv
        steps = 136 if window is None else 70
        calls, partials, sums, own = [], [], [], []

        def walk(jaxpr):
            for eqn in jaxpr.eqns:
                for var in (*eqn.invars, *eqn.outvars):
                    shape = getattr(var.aval, "shape", ())
                    if len(shape) == 4 and shape[0] == blocks and shape[2] == T:
                        partials.append((eqn.primitive.name, shape))
                if eqn.primitive.name == "reduce_sum" and eqn.invars[0].aval.shape[:1] == (blocks,) \
                        and 0 in eqn.params["axes"] and eqn.invars[0].aval.ndim == 4:
                    sums.append(eqn)
                if eqn.primitive.name == "pallas_call":
                    calls.append((eqn.params["name"], eqn.params["grid_mapping"].grid))
                    if eqn.params["name"] == "attn_bwd_band":
                        own.append(eqn)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

        walk(jaxpr.jaxpr)
        assert not partials and not sums
        assert [grid for name, grid in calls if name == "attn_bwd_band"] == [(H, steps)] * B, calls
        assert not any("dkv" in name for name, _ in calls)  # the library's backward is gone
        assert sum("splash_mha_fwd" in name for name, _ in calls) == B
        for eqn in own:  # dq, dk, dv where they lie in (T, heads * D); then the ballast, dropped
            shapes_out = [v.aval.shape for v in eqn.outvars]
            assert shapes_out[:3] == [(T, H * D), (T, n_kv * D), (T, n_kv * D)]
            if B == 1:
                assert len(shapes_out) == 3
            else:
                assert shapes_out[3:] == [(blocks * H, T, D)]
