"""The granite_hybrid family (``tpu_rl/models/granite_hybrid.py``) at tiny
widths on the CPU against the benchmark's plain reference
(``benchmarks/reference/granite_hybrid.py``: one ``lax.scan`` step per time
step, dense masked attention): outputs, the PPO loss and its gradients, the
episode seams of the chunked scan, a carried-in state, acting step by step,
and the shared attention entry point it added an argument to. The seam, carry
and rematerialisation tests run twice: on the ``jnp`` body of the scan and on
its Pallas kernels in the interpreter (``ops/pallas_ssd.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import granite_hybrid as reference
from benchmarks.reference import losses as ref_losses
from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.algos.ppo import make_train_step, policy_outputs
from tpu_rl.algos.registry import get_algo
from tpu_rl.config import Config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.models import cells
from tpu_rl.models.families import ModelFamily, build_family
from tpu_rl.models.granite_hybrid import GraniteHybridActorCritic
from tpu_rl.parallel.sequence import flash_attention_tpu, full_attention
from tpu_rl.types import Batch

ARCH = dict(
    hidden_size=64, layer_types=["mamba", "attention", "mamba"], rms_norm_eps=1e-5,
    intermediate_size=96, residual_multiplier=0.22, embedding_multiplier=12,
    logits_scaling=8, mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
    mamba_n_groups=2, mamba_d_conv=4, mamba_expand=2, mamba_chunk_size=8,
    mamba_conv_bias=True, mamba_proj_bias=False, num_attention_heads=4,
    num_key_value_heads=2, attention_multiplier=0.0625, attention_bias=False,
)
T, B, OBS, ACTIONS = 32, 2, 6, 3
PARAMS = dict(algo="PPO", model="granite_hybrid", arch=ARCH, obs_shape=(OBS,),
              action_space=ACTIONS, seq_len=T, batch_size=B)


def config(**kw) -> Config:
    return Config.from_dict({**PARAMS, **kw})


@pytest.fixture(params=["auto", "interpret"], ids=["jnp", "pallas"])
def scan_form(request, monkeypatch):
    """The form of the scan a test's programs are traced in (read while
    tracing: a test jits what it runs inside this fixture's scope)."""
    monkeypatch.setattr(cells, "_PALLAS_MODE", request.param)
    return request.param


@pytest.fixture(scope="module")
def family():
    return build_family(config())


@pytest.fixture(scope="module")
def actor(family):
    """Seeded weights with every leaf moved off its initial value, so that a
    scale of ones or a bias of zeros cannot hide a missing term."""
    def make(key):
        tree = family.init_params(key, seq_len=T)["actor"]
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(jax.random.key(1), len(leaves))
        return jax.tree.unflatten(treedef, [
            x + 0.1 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)
        ])

    return jax.jit(make)(jax.random.key(0))


def make_batch(seed: int, firsts=None, rows: int = B) -> dict:
    rng = np.random.default_rng(seed)
    fir = np.zeros((rows, T, 1), np.float32)
    if firsts is None:
        fir[:] = rng.random((rows, T, 1)) < 0.15
    else:
        fir[:, list(firsts)] = 1.0
    f32 = np.float32
    return {
        "obs": rng.standard_normal((rows, T, OBS)).astype(f32),
        "act": rng.integers(0, ACTIONS, (rows, T, 1)).astype(f32),
        "rew": (0.1 * rng.standard_normal((rows, T, 1))).astype(f32),
        "logits": np.full((rows, T, ACTIONS), -np.log(ACTIONS), f32),
        "log_prob": np.full((rows, T, 1), -np.log(ACTIONS), f32),
        "is_fir": fir,
        "hx": np.zeros((rows, T, 1), f32),
        "cx": np.zeros((rows, T, 1), f32),
    }


@pytest.fixture(scope="module")
def system(family):
    return jax.jit(lambda p, b: policy_outputs(family, {"actor": p}, Batch.from_mapping(b))[2:])


@pytest.fixture(scope="module")
def plain():
    return jax.jit(lambda p, b: reference.forward(p, b, PARAMS)[::-1])


def close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=tol)


def test_outputs_match_the_reference_in_float32(actor, system, plain):
    batch = make_batch(2)
    assert batch["is_fir"].sum() >= 4  # seams inside chunks of 8 steps
    (value, logits), (ref_value, ref_logits) = system(actor, batch), plain(actor, batch)
    close(logits, ref_logits, 1e-4)
    close(value, ref_value, 1e-4)


def test_outputs_match_the_reference_in_bfloat16(actor, plain):
    """bf16 operands into every matmul (2^-8 relative per operand) through
    three layers: 3e-2 of the largest output, against ~1e-6 in float32."""
    fam = build_family(config(compute_dtype="bfloat16"))
    batch = make_batch(3)
    value, logits = jax.jit(
        lambda p, b: policy_outputs(fam, {"actor": p}, Batch.from_mapping(b))[2:])(actor, batch)
    ref_value, ref_logits = plain(actor, batch)
    for got, want in ((logits, ref_logits), (value, ref_value)):
        close(got, want, 3e-2 * float(np.abs(want).max()))
        assert float(np.abs(np.asarray(got) - np.asarray(want)).max()) > 1e-6


def ref_ppo_loss(p, batch, cfg):
    """``benchmarks/reference/losses.ppo`` in jax.numpy, so it has a gradient."""
    logits, value = reference.forward(p, batch, PARAMS)
    g, lam, eps = cfg.gamma, cfg.lmbda, cfg.eps_clip
    log_prob = jnp.take_along_axis(logits, batch["act"].astype(jnp.int32), axis=-1)
    entropy = -(jnp.exp(logits) * logits).sum(-1, keepdims=True)
    v = jax.lax.stop_gradient(value)
    td_target = batch["rew"][:, :-1] + g * (1.0 - batch["is_fir"][:, 1:]) * v[:, 1:]
    delta = td_target - v[:, :-1]
    adv, run = [], jnp.zeros_like(delta[:, 0])
    for t in reversed(range(T - 1)):
        run = delta[:, t] + g * lam * run
        adv.append(run)
    adv = jnp.stack(adv[::-1], axis=1)
    ratio = jnp.exp(log_prob[:, :-1] - batch["log_prob"][:, :-1])
    policy = -jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - eps, 1 + eps) * adv).mean()
    d = jnp.abs(value[:, :-1] - td_target)
    value_loss = jnp.where(d < 1.0, 0.5 * d * d, d - 0.5).mean()
    return (cfg.policy_loss_coef * policy + cfg.value_loss_coef * value_loss
            - cfg.entropy_coef * entropy[:, :-1].mean())


def test_ppo_loss_and_gradients_match_the_reference(family, actor, plain):
    """The train step's own loss against the reference's NumPy loss, and the
    gradients of that loss (assembled from the train step's own pieces)
    against ``jax.grad`` of the reference forward under the reference loss."""
    cfg = config()
    batch = make_batch(4)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}

    def sys_loss(p):
        log_probs, entropy, value, _ = policy_outputs(family, {"actor": p}, Batch.from_mapping(jb))
        from tpu_rl.algos.ppo import td_target_and_gae
        from tpu_rl.ops.losses import smooth_l1

        td_target, adv = td_target_and_gae(cfg, Batch.from_mapping(jb), value)
        ratio = jnp.exp(log_probs[:, :-1] - jb["log_prob"][:, :-1])
        surr = jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - cfg.eps_clip, 1 + cfg.eps_clip) * adv)
        return (-cfg.policy_loss_coef * surr.mean()
                + cfg.value_loss_coef * smooth_l1(value[:, :-1], td_target)
                - cfg.entropy_coef * entropy[:, :-1].mean())

    loss, grads = jax.jit(jax.value_and_grad(sys_loss))(actor)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: ref_ppo_loss(p, jb, cfg)))(actor)
    ref_value, ref_logits = plain(actor, batch)
    numpy_loss = ref_losses.ppo(ref_logits, ref_value, batch, PARAMS)["loss"]
    assert abs(float(ref_loss) - numpy_loss) < 1e-5  # the two references agree
    assert abs(float(loss) - numpy_loss) < 1e-5
    # and the train step computes that loss
    params = {"actor": actor}
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=rmsprop(cfg).init(params))
    _, metrics = jax.jit(make_train_step(cfg, family))(state, Batch.from_mapping(jb), jax.random.key(1))
    assert abs(float(metrics["loss"]) - numpy_loss) < 1e-5
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(ref_grads))
    for (path, got), want in zip(jax.tree.leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        assert float(jnp.abs(got - want).max()) <= 2e-4 * scale, jax.tree_util.keystr(path)


@pytest.fixture(scope="module")
def weighted_by_form(family):
    """Sum of the outputs under per-step weights, and its gradient: one
    jitted program per form of the scan."""

    def f(p, batch, weights):
        _, _, value, logits = policy_outputs(family, {"actor": p}, Batch.from_mapping(batch))
        return jnp.sum(weights * (value + logits.sum(-1, keepdims=True))), (value, logits)

    return {form: jax.jit(jax.value_and_grad(lambda *a: f(*a), has_aux=True))
            for form in ("auto", "interpret")}


@pytest.fixture
def weighted(weighted_by_form, scan_form):
    return weighted_by_form[scan_form]


@pytest.mark.parametrize("seams", [(8,), (13,), (13, 14), (0, 19)],
                         ids=["chunk-edge", "inside-a-chunk", "two-in-a-row", "t0-and-later"])
def test_a_seam_cuts_state_taps_and_attention(actor, weighted, seams):
    """What follows the last seam equals a run of that suffix alone (placed at
    the start of a window; what comes after it cannot reach back), outputs
    and gradients; chunks are 8 steps."""
    s = seams[-1]
    full = make_batch(5, firsts=seams, rows=1)
    alone = {k: np.concatenate([v[:, s:], np.zeros_like(v[:, :s])], axis=1) for k, v in full.items()}
    w_full = np.zeros((1, T, 1), np.float32)
    w_full[:, s:] = np.random.default_rng(6).standard_normal((1, T - s, 1))
    w_alone = np.concatenate([w_full[:, s:], np.zeros_like(w_full[:, :s])], axis=1)
    (_, (value, logits)), grads = weighted(actor, full, w_full)
    (_, (value_a, logits_a)), grads_a = weighted(actor, alone, w_alone)
    close(value[:, s:], value_a[:, : T - s], 1e-5)
    close(logits[:, s:], logits_a[:, : T - s], 1e-5)
    assert np.isfinite(np.asarray(value)).all()
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads_a))
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_a)):
        assert np.isfinite(np.asarray(got)).all()
        close(got, want, 1e-4 * scale)


def mamba_carry(seed: int, rows: int):
    """One (state, tail) per Mamba layer, and the same flattened as ``h``."""
    rng = np.random.default_rng(seed)
    conv_ch = ARCH["mamba_n_heads"] * ARCH["mamba_d_head"] + 2 * ARCH["mamba_n_groups"] * ARCH["mamba_d_state"]
    pairs = [
        (rng.standard_normal((rows, ARCH["mamba_n_heads"], ARCH["mamba_d_head"],
                              ARCH["mamba_d_state"])).astype(np.float32),
         rng.standard_normal((rows, ARCH["mamba_d_conv"] - 1, conv_ch)).astype(np.float32))
        for _ in range(ARCH["layer_types"].count("mamba"))
    ]
    return pairs, np.concatenate([a.reshape(rows, -1) for pair in pairs for a in pair], axis=1)


def test_a_window_starts_from_the_state_it_is_handed(family, actor, plain, scan_form):
    """Non-zero ``carry0``: the system unrolls from the flattened acting
    carry, the reference from the same states; a seam at step 11 must drop
    both. The placeholder the stores hand over means zeros."""
    batch = make_batch(7, firsts=(11,))
    pairs, h = mamba_carry(8, B)
    assert h.shape[1] == family.carry_widths[0]
    c = jnp.zeros((B, family.carry_widths[1]))
    unroll = jax.jit(family.actor_unroll)
    obs, fir = jnp.asarray(batch["obs"]), jnp.asarray(batch["is_fir"])
    logits, value, (h_out, c_out) = unroll(actor, obs, (jnp.asarray(h), c), fir)
    ref = jax.jit(lambda p, b, carry0: reference.forward(p, b, PARAMS, carry0=carry0))
    ref_logits, ref_value = ref(actor, batch, pairs)
    close(logits, ref_logits, 1e-4)
    close(value, ref_value, 1e-4)
    zero_logits, _ = plain(actor, batch)[::-1]
    assert float(np.abs(np.asarray(zero_logits - ref_logits))[:, :11].max()) > 1e-3
    close(ref_logits[:, 11:], zero_logits[:, 11:], 1e-5)
    assert h_out.shape == h.shape and np.array_equal(c_out, c)
    held, _, _ = unroll(actor, obs, (jnp.ones((B, 1)), jnp.ones((B, 1))), fir)
    close(held, zero_logits, 1e-4)


def test_acting_step_by_step_equals_the_unroll(family, actor, system):
    """``family.act`` with the worker's zeroing at episode starts, across a
    seam inside a chunk; then unrolling the rest from the carry that acting
    reached equals unrolling it all (no seam after it, so attention alone is
    cut at the window's start: compare the Mamba-only prefix of the claim on
    a model without attention below)."""
    batch = make_batch(9, firsts=(0, 13))
    value, logits = system(actor, batch)
    h = jnp.zeros((B, family.carry_widths[0]))
    c = jnp.zeros((B, family.carry_widths[1]))
    act = jax.jit(family.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            h, c = jnp.zeros_like(h), jnp.zeros_like(c)
        _, step_logits, _, h, c = act({"actor": actor}, jnp.asarray(batch["obs"][:, t]), h, c,
                                      jax.random.key(t))
        close(step_logits, logits[:, t], 2e-5)
    assert float(c[0, -1]) == T - 13  # steps of the running episode in the ring


def test_the_carry_an_unroll_returns_continues_the_recurrence(actor, scan_form):
    """Mamba layers only: a window unrolled in two halves, the second from
    the carry the first returned, equals the window unrolled whole — with a
    seam in the first half, whose taps and state must not leak through."""
    arch = {**ARCH, "layer_types": ["mamba", "mamba"]}
    fam = build_family(config(arch=arch))
    params = jax.jit(lambda k: fam.init_params(k, seq_len=T))(jax.random.key(3))["actor"]
    batch = make_batch(10, firsts=(14,))
    obs, fir = jnp.asarray(batch["obs"]), jnp.asarray(batch["is_fir"])
    zero = (jnp.zeros((B, 1)), jnp.zeros((B, 1)))
    unroll = jax.jit(fam.actor_unroll)
    whole, _, _ = unroll(params, obs, zero, fir)
    first, _, carry = unroll(params, obs[:, :16], zero, fir[:, :16])
    second, _, _ = unroll(params, obs[:, 16:], carry, fir[:, 16:])
    close(jnp.concatenate([first, second], axis=1), whole, 1e-5)


def test_a_window_that_is_no_multiple_of_the_chunk(actor, system, plain):
    batch = make_batch(11)
    cut = {k: v[:, :27] for k, v in batch.items()}
    value, logits = system(actor, cut)
    ref_value, ref_logits = plain(actor, cut)
    close(logits, ref_logits, 1e-4)
    close(value, ref_value, 1e-4)


def test_rematerialisation_does_not_change_the_gradients(family, actor, scan_form):
    batch = make_batch(12)
    obs, fir = jnp.asarray(batch["obs"]), jnp.asarray(batch["is_fir"])
    carry = (jnp.zeros((B, 1)), jnp.zeros((B, 1)))
    plain_model = GraniteHybridActorCritic(
        n_actions=ACTIONS, arch=ARCH, act_ctx=T, remat=False)
    assert family.actor.remat  # no Config field turns it off

    def loss(model):
        def f(p):
            logits, value, _ = model.apply(p, obs, carry, fir)
            return jnp.sum(logits[..., 0] * value[..., 0])
        return jax.jit(jax.grad(f))

    with_remat, without = loss(family.actor)(actor), loss(plain_model)(actor)
    for got, want in zip(jax.tree.leaves(with_remat), jax.tree.leaves(without)):
        close(got, want, 1e-5 * (1.0 + float(jnp.abs(want).max())))


@pytest.mark.parametrize("algo", ["PPO", "IMPALA", "V-MPO"])
def test_each_on_policy_algorithm_runs_one_update(algo, monkeypatch):
    cfg = config(algo=algo, learn_diag=True, update_guard=True,
                 arch={**ARCH, "layer_types": ["mamba", "attention"]})
    # build() traces init_params op by op (12 s here); as one program, 4 s
    eager = ModelFamily.init_params
    monkeypatch.setattr(ModelFamily, "init_params", lambda self, key, seq_len=2: jax.jit(
        lambda k: eager(self, k, seq_len))(key))
    fam, state, step = get_algo(algo).build(cfg, jax.random.key(0))
    lay = BatchLayout.from_config(cfg)
    assert (lay.hx, lay.cx) == (1, 1) and not fam.store_carry
    before = jax.device_get(state.params["actor"])
    state, metrics = jax.jit(step)(state, Batch.from_mapping(make_batch(13)), jax.random.key(1))
    assert np.isfinite(float(metrics["loss"])) and float(metrics["nonfinite-updates"]) == 0
    moved = jax.tree.map(lambda a, b: float(np.abs(a - np.asarray(b)).max()),
                         before, state.params["actor"])
    assert all(v > 0 for v in jax.tree.leaves(moved))  # every leaf has a gradient


def test_what_the_family_refuses():
    with pytest.raises(AssertionError, match="on-policy"):
        config(algo="SAC")
    with pytest.raises(AssertionError, match="needs arch"):
        config(arch=None)
    with pytest.raises(AssertionError, match="lacks"):
        config(arch={k: v for k, v in ARCH.items() if k != "mamba_d_state"})
    with pytest.raises(AssertionError, match="expert"):
        config(arch={**ARCH, "num_local_experts": 8})
    with pytest.raises(AssertionError, match="granite_hybrid"):
        Config.from_dict({"model": "transformer", "arch": ARCH})
    from tpu_rl.checkpoint import resume_fingerprint

    other = config(arch={**ARCH, "mamba_d_state": 32})
    assert resume_fingerprint(other) != resume_fingerprint(config())


def test_the_update_program_names_its_paths(family, actor, monkeypatch):
    from tpu_rl.utils.platform import program_paths

    cfg = config()
    params = {"actor": actor}
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=rmsprop(cfg).init(params))
    def lower():
        return jax.jit(make_train_step(cfg, family)).lower(
            state, Batch.from_mapping(make_batch(14)), jax.random.key(1))

    lowered = lower()
    paths = set(program_paths(lowered)["paths"])
    assert {"ssd_scan", "attn_full"} <= paths and "ssd_pallas" not in paths  # a CPU: the jnp body
    text = lowered.as_text(debug_info=True)
    assert "ssd_conv" in text and "opt_update" in text
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    assert {"ssd_scan", "ssd_pallas", "attn_full"} <= set(program_paths(lower())["paths"])


# ------------------------------------------- the shared attention entry point
def qkv(n_kv: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((2, 16, 4, 8)), jnp.float32)
    k, v = (jnp.asarray(rng.standard_normal((2, 16, n_kv, 8)), jnp.float32) for _ in range(2))
    pos = jnp.broadcast_to(jnp.arange(16), (2, 16))
    seg = jnp.asarray(np.cumsum(rng.random((2, 16)) < 0.2, axis=1), jnp.int32)
    return q, k, v, pos, seg


@pytest.mark.parametrize("impl", [flash_attention_tpu, full_attention])
def test_the_default_softmax_scale_is_bit_identical(impl):
    q, k, v, pos, seg = qkv(4)
    default = impl(q, k, v, pos, seg)
    assert np.array_equal(default, impl(q, k, v, pos, seg, sm_scale=None))
    assert np.array_equal(default, impl(q, k, v, pos, seg, sm_scale=1.0 / np.sqrt(8)))
    assert not np.allclose(default, impl(q, k, v, pos, seg, sm_scale=0.015625))


@pytest.mark.parametrize("rep", [1, 2], ids=["unrepeated", "repeated"])
def test_repeated_key_value_heads_equal_grouped_attention(rep):
    """Key/value head g serves query heads 2g and 2g+1, whether the caller
    repeats the heads or hands them over as they are."""
    q, k, v, pos, seg = qkv(2, seed=1)
    scale = 0.3
    got = flash_attention_tpu(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
                              pos, seg, sm_scale=scale)
    grouped = q.reshape(2, 16, 2, 2, 8)
    scores = jnp.einsum("btgrd,bsgd->bgrts", grouped, k) * scale
    mask = (seg[:, :, None] == seg[:, None, :]) & (pos[:, :, None] >= pos[:, None, :])
    w = jax.nn.softmax(jnp.where(mask[:, None, None], scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bgrts,bsgd->btgrd", w, v).reshape(2, 16, 4, 8)
    close(got, want, 1e-6)
