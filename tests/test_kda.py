"""The delta rule with a decay per key channel (``tpu_rl/ops/kda.py``) on the
CPU at small widths: the chunked form against the step recurrence, outputs,
the last state and all six gradients, with seams wherever a chunk and a
sub-block can take them; every gate at its bound for whole chunks (finite and
equal); the sub-blocks' factored pairs against the exact pairwise form; a decay
that is constant over the channels against ``gated_delta_chunked``; a window
that is no whole span; bf16 operands. Chunks of 8 steps in sub-blocks of 4 and
spans of 2, so a 32-step window is two spans of two chunks of two sub-blocks."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_rl.ops import gated_delta, kda

B, H, DK, DV = 2, 3, 8, 8
CHUNK, SUB = 8, 4
BOUND = -5.0


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    before = kda.SUB, gated_delta.SPAN_CHUNKS
    kda.SUB, gated_delta.SPAN_CHUNKS = SUB, 2
    yield
    kda.SUB, gated_delta.SPAN_CHUNKS = before


def step_by_step(q, k, v, g, beta, first, state0):
    """``kda_step`` over the window, the state zeroed where an episode starts."""
    def step(S, at):
        q_t, k_t, v_t, g_t, beta_t, first_t = at
        o, S = kda.kda_step(
            q_t, k_t, v_t, g_t, beta_t, jnp.where(first_t[:, None, None, None], 0.0, S))
        return S, o

    last, o = jax.lax.scan(
        step, state0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta, first)))
    return jnp.moveaxis(o, 0, 1), last


def rule_inputs(seed: int, steps: int, gate=None):
    """``gate``: every log decay, or None for the bounded gate on seeded inputs."""
    keys = jax.random.split(jax.random.key(seed), 6)
    q, k = (jax.random.normal(key, (B, steps, H, DK)) for key in keys[:2])
    v = jax.random.normal(keys[2], (B, steps, H, DV))
    g = BOUND * jax.nn.sigmoid(2.0 * jax.random.normal(keys[3], (B, steps, H, DK)))
    if gate is not None:
        g = jnp.full_like(g, gate)
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, steps, H)))
    return q, k, v, g, beta, jax.random.normal(keys[5], (B, H, DK, DV))


def windows(seams, steps):
    first = np.zeros((B, steps), bool)
    first[0, list(seams)] = True  # row 1 is one episode: it reads state0 to the end
    first = jnp.asarray(first)
    return first, jnp.cumsum(first, axis=1).astype(jnp.int32)


def close(got, want, tol, what=""):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.isfinite(got).all(), what
    err = np.abs(got - want).max() / (np.abs(want).max() + 1e-12)
    assert err <= tol, f"{what}: {err:.3g} > {tol}"


SEAMS = {
    "none": (), "a-chunks-first-step": (16,), "a-chunks-last-step": (15,),
    "a-sub-blocks-first-step": (20,), "inside-a-sub-block": (21,), "a-sub-blocks-last-step": (19,),
    "two-in-one-sub-block": (9, 10), "the-windows-first-step": (0,),
    "a-spans-first-step": (16, 17), "every-kind": (0, 3, 7, 8, 19, 21, 22, 31),
}


@pytest.mark.parametrize("seams", SEAMS.values(), ids=SEAMS.keys())
def test_the_chunked_rule_equals_the_step_recurrence(seams):
    T = 32
    q, k, v, g, beta, state0 = rule_inputs(1, T)
    first, seg = windows(seams, T)

    def chunked(q, k, v, g, beta, state0):
        return kda.kda_chunked(q, k, v, g, beta, seg, state0, CHUNK)

    def stepped(q, k, v, g, beta, state0):
        return step_by_step(q, k, v, g, beta, first, state0)

    args = (q, k, v, g, beta, state0)
    (o, last), (o_ref, last_ref) = jax.jit(chunked)(*args), jax.jit(stepped)(*args)
    close(o, o_ref, 2e-5, "o")
    close(last, last_ref, 2e-5, "the last state")

    w_o, w_s = jax.random.normal(jax.random.key(9), o.shape), jax.random.normal(
        jax.random.key(10), last.shape)
    loss = lambda f: lambda *a: (  # noqa: E731
        lambda out: jnp.sum(out[0] * w_o) + jnp.sum(out[1] * w_s))(f(*a))
    grads = jax.jit(jax.grad(loss(chunked), argnums=tuple(range(6))))(*args)
    wants = jax.jit(jax.grad(loss(stepped), argnums=tuple(range(6))))(*args)
    for name, got, want in zip(("q", "k", "v", "g", "beta", "state0"), grads, wants):
        close(got, want, 1e-4, f"d {name}")


@pytest.mark.parametrize("gate", [BOUND, 0.0], ids=["at-the-bound", "no-decay"])
def test_every_gate_at_one_value_for_the_whole_window(gate):
    """At the bound for four chunks on end the sub-block's right operand
    reaches ``e^(5 x 3)`` here (``e^75`` at sub-blocks of 16: finite in
    float32); the chunk-wide factoring would need ``e^(5 x 31)``, which is
    not."""
    T = 32
    q, k, v, g, beta, state0 = rule_inputs(2, T, gate)
    first, seg = windows((11,), T)
    o, last = jax.jit(lambda *a: kda.kda_chunked(*a, seg, state0, CHUNK))(q, k, v, g, beta)
    o_ref, last_ref = step_by_step(q, k, v, g, beta, first, state0)
    close(o, o_ref, 2e-5, "o")
    close(last, last_ref, 2e-5, "the last state")
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda.kda_chunked(*a, seg, state0, CHUNK)[0]), argnums=(0, 1, 2, 3, 4)
    ))(q, k, v, g, beta)
    wants = jax.grad(
        lambda *a: jnp.sum(step_by_step(*a, first, state0)[0]), argnums=(0, 1, 2, 3, 4)
    )(q, k, v, g, beta)
    for got, want in zip(grads, wants):
        close(got, want, 1e-4)


def test_the_bound_and_the_production_sub_block_stay_finite_in_float32_and_bf16(monkeypatch):
    """Sub-blocks of 16 in a chunk of 64 with every gate at -5: operands up to
    ``e^75``, outputs and gradients finite, equal to the recurrence."""
    monkeypatch.setattr(kda, "SUB", 16)
    T = 128
    q, k, v, g, beta, state0 = rule_inputs(3, T, BOUND)
    first, seg = windows((70,), T)
    run = lambda dtype: jax.jit(lambda *a: kda.kda_chunked(*a, seg, state0, 64, dtype))  # noqa: E731
    o, last = run(None)(q, k, v, g, beta)
    o_ref, last_ref = step_by_step(q, k, v, g, beta, first, state0)
    close(o, o_ref, 2e-5, "o")
    close(last, last_ref, 2e-5, "the last state")
    o16, last16 = run(jnp.bfloat16)(q, k, v, g, beta)
    assert o16.dtype == last16.dtype == jnp.float32
    close(o16, o_ref, 3e-2, "o at bf16 operands")
    grads = jax.jit(jax.grad(
        lambda *a: jnp.sum(kda.kda_chunked(*a, seg, state0, 64, jnp.bfloat16)[0] ** 2),
        argnums=(0, 1, 2, 3, 4)))(q, k, v, g, beta)
    assert all(np.isfinite(np.asarray(x)).all() for x in grads)


def test_the_sub_blocks_pairs_equal_the_exact_pairwise_form():
    """``_pairs`` against ``e^(Γ_i - Γ_j)`` on every pair with no factoring, on
    and under the diagonal (above it ``_pairs`` promises nothing)."""
    keys = jax.random.split(jax.random.key(4), 3)
    Q = 16
    left = jax.random.normal(keys[0], (B, H, 2, Q, DK))
    kn = jax.random.normal(keys[1], (B, H, Q, DK))
    gamma = jnp.cumsum(BOUND * jax.nn.sigmoid(3.0 * jax.random.normal(keys[2], (B, H, Q, DK))), -2)
    got = kda._pairs(left, kn, gamma, SUB, jnp.float32)
    decay = jnp.exp(jnp.minimum(gamma[..., :, None, :] - gamma[..., None, :, :], 0.0))
    want = jnp.einsum("bhnic,bhjc,bhijc->bhnij", left, kn, decay)
    under = np.tril(np.ones((Q, Q), bool))
    close(np.where(under, got, 0.0), np.where(under, want, 0.0), 1e-5)
    # a dropped reference point is seen: against the chunk's first step alone, 32 steps at
    # the bound need e^(5 x 31), which float32 does not hold; the sub-blocks stay exact
    Q = 32
    left, kn = (jax.random.normal(key, (B, H, 2, Q, DK)) for key in keys[:2])
    kn = kn[:, :, 0]
    gamma = jnp.cumsum(jnp.full((B, H, Q, DK), BOUND), -2)
    under = np.tril(np.ones((Q, Q), bool))
    decay = jnp.exp(jnp.minimum(gamma[..., :, None, :] - gamma[..., None, :, :], 0.0))
    want = jnp.einsum("bhnic,bhjc,bhijc->bhnij", left, kn, decay)
    close(np.where(under, kda._pairs(left, kn, gamma, SUB, jnp.float32), 0.0),
          np.where(under, want, 0.0), 1e-5)
    assert not np.isfinite(np.asarray(kda._pairs(left, kn, gamma, Q, jnp.float32))).all()


def test_bf16_operands_keep_the_decays_gradient_at_the_bound():
    """A step's pair with itself carries no decay and is summed exactly: taken
    through the factoring, its two halves' gradients by the decay cancel only
    to bf16 rounding, which at the bound (where every true term is ``e^-5`` of
    it or less) read 14% of the largest gradient here; 0.4% now."""
    T = 32
    q, k, v, g, beta, state0 = rule_inputs(11, T, BOUND)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    first, seg = windows((), T)
    w = jax.random.normal(jax.random.key(12), (B, T, H, DV))
    got = jax.jit(jax.grad(lambda g: jnp.sum(
        w * kda.kda_chunked(q, k, v, g, beta, seg, state0, CHUNK, jnp.bfloat16)[0])))(g)
    want = jax.grad(lambda g: jnp.sum(w * step_by_step(q, k, v, g, beta, first, state0)[0]))(g)
    close(got, want, 2e-2, "d g")


@pytest.mark.parametrize("seams", [(), (5, 19)], ids=["none", "two"])
def test_a_decay_constant_over_the_channels_is_the_gated_delta_rule(seams):
    T = 32
    q, k, v, g, beta, state0 = rule_inputs(5, T)
    scalar = g[..., 0]  # (B, T, H)
    _, seg = windows(seams, T)
    o, last = kda.kda_chunked(
        q, k, v, jnp.broadcast_to(scalar[..., None], g.shape), beta, seg, state0, CHUNK)
    o_ref, last_ref = gated_delta.gated_delta_chunked(
        q, k, v, scalar, beta, seg, state0, CHUNK, kernel=(None, False))
    close(o, o_ref, 2e-5, "o")
    close(last, last_ref, 2e-5, "the last state")


@pytest.mark.parametrize("steps", [5, 24, 37])
def test_the_chunked_rule_pads_a_window_that_is_no_whole_span(steps):
    q, k, v, g, beta, state0 = rule_inputs(6, steps)
    first, seg = windows((3,), steps)
    o, last = kda.kda_chunked(q, k, v, g, beta, seg, state0, CHUNK)
    o_ref, last_ref = step_by_step(q, k, v, g, beta, first, state0)
    assert o.shape == o_ref.shape
    close(o, o_ref, 2e-5, "o")
    close(last, last_ref, 2e-5, "the last state")


def test_stepping_over_the_window_is_the_unroll_and_hands_its_state_on():
    """``kda_step`` T times from the chunked form's last state of a first
    window equals the chunked form over both windows."""
    T = 32
    q, k, v, g, beta, state0 = rule_inputs(7, 2 * T)
    first, seg = windows((40,), 2 * T)
    o_all, last_all = kda.kda_chunked(q, k, v, g, beta, seg, state0, CHUNK)
    head = tuple(a[:, :T] for a in (q, k, v, g, beta))
    tail = tuple(a[:, T:] for a in (q, k, v, g, beta))
    _, mid = kda.kda_chunked(*head, seg[:, :T], state0, CHUNK)
    o_tail, last = step_by_step(*tail, first[:, T:], mid)
    close(o_tail, o_all[:, T:], 2e-5, "o")
    close(last, last_all, 2e-5, "the last state")


def test_a_chunk_that_is_no_whole_number_of_sub_blocks_is_refused():
    q, k, v, g, beta, state0 = rule_inputs(8, 12)
    _, seg = windows((), 12)
    with pytest.raises(AssertionError, match="sub-blocks"):
        kda.kda_chunked(q, k, v, g, beta, seg, state0, 6)
