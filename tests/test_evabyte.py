"""The evabyte family (``tpu_rl/models/evabyte.py``) at tiny widths on the CPU
against the benchmark's plain reference (``benchmarks/reference/evabyte.py``:
a summary row for every step, every query against ``[K ; all T rows]`` under
the mask from the definitions) and against a brute-force enumeration of the
sets ``E(t)`` and ``S(t)`` a query reads, step by step in NumPy: outputs, the
PPO loss and every gradient (the pooling vectors among them); one normaliser;
a chunk of the query's own block is never read as a summary; an episode moved
inside its window keeps its outputs; acting step by step — an exact ring that
restarts every block beside a summary store — against the unroll; the kernels'
construction in the interpreter against the ``jnp`` form; the counters; the
static bounds; the carry's widths; a family without experts through the routed
plumbing; what the config check refuses. Hidden 64, 4 heads of 16, blocks of
32 steps, chunks of 4, windows of 128: every ratio of the published model.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import evabyte as reference
from benchmarks.reference import losses as ref_losses
from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.algos.ppo import make_train_step, policy_outputs, policy_outputs_routed
from tpu_rl.algos.registry import get_algo
from tpu_rl.config import EVABYTE_ARCH_KEYS, Config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.models import evabyte
from tpu_rl.models.backbone import ring, state_widths
from tpu_rl.models.evabyte import EvaAttention, EvaByteActorCritic, episode_grid, pair_counts
from tpu_rl.models.families import ModelFamily, build_family
from tpu_rl.obs.learn import attention_scalars, route_scalars
from tpu_rl.parallel import sequence
from tpu_rl.types import Batch

ARCH = dict(
    hidden_size=64, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
    window_size=32, chunk_size=4, intermediate_size=96, rms_norm_eps=1e-5, rope_theta=100000,
    norm_add_unit_offset=True, init_std=0.05, attention_class="eva",
)
T, B, OBS, ACTIONS = 128, 2, 6, 3
HEADS, D, W, C = 4, 16, 32, 4
PARAMS = dict(algo="PPO", model="evabyte", arch=ARCH, obs_shape=(OBS,),
              action_space=ACTIONS, seq_len=T, batch_size=B)
# Row 0: a window that opens mid-episode (no first flag at 0), an episode that
# starts off every grid line of the window (37) and runs longer than three
# blocks (to the end: 91 steps). Row 1: an episode that ends inside a chunk
# (5 .. 46: 42 steps = 10 chunks and 2 steps), one that ends on a block's last
# step (47 .. 110: 64 steps), a short tail.
SEAMS = ((37,), (5, 47, 111))


def config(**kw) -> Config:
    return Config.from_dict({**PARAMS, **kw})


def make_batch(seed: int, seams=SEAMS, rows: int = B, steps: int = T) -> dict:
    rng = np.random.default_rng(seed)
    fir = np.zeros((rows, steps, 1), np.float32)
    for row, at in zip(fir, seams):
        row[list(at)] = 1.0
    f32 = np.float32
    return {
        "obs": rng.standard_normal((rows, steps, OBS)).astype(f32),
        "act": rng.integers(0, ACTIONS, (rows, steps, 1)).astype(f32),
        "rew": (0.1 * rng.standard_normal((rows, steps, 1))).astype(f32),
        "logits": np.full((rows, steps, ACTIONS), -np.log(ACTIONS), f32),
        "log_prob": np.full((rows, steps, 1), -np.log(ACTIONS), f32),
        "is_fir": fir,
        "hx": np.zeros((rows, steps, 1), f32),
        "cx": np.zeros((rows, steps, 1), f32),
    }


def close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=tol)


def moved(tree, seed: int = 1):
    """Every leaf moved off its initial value."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        x + 0.1 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def family():
    return build_family(config())


@pytest.fixture(scope="module")
def actor(family):
    return jax.jit(lambda key: moved(family.init_params(key, seq_len=T)["actor"]))(
        jax.random.key(0))


@pytest.fixture(scope="module")
def system(family):
    """(value, logits, records) of the system's unroll."""
    return jax.jit(
        lambda p, b: policy_outputs_routed(family, {"actor": p}, Batch.from_mapping(b))[2:])


@pytest.fixture(scope="module")
def plain():
    return jax.jit(lambda p, b: reference.forward(p, b, PARAMS)[::-1])


# ------------------------------------------------ the sets, by enumeration
def grid_by_hand(first_flags):
    """``first_flags`` (T,) -> per step its episode, its index in it, by a loop."""
    episode, pos, e, p = [], [], 0, -1
    for t, f in enumerate(first_flags):
        if f > 0:
            e += 1
        p = 0 if (f > 0 or t == 0) else p + 1
        episode.append(e)
        pos.append(p)
    return np.asarray(episode), np.asarray(pos)


def sets_by_hand(first_flags, block=W, chunk=C):
    """For every query t: the exact keys ``E(t)`` (steps) and the summaries
    ``S(t)`` (each a tuple of its chunk's member steps), straight from the
    definitions."""
    episode, pos = grid_by_hand(first_flags)
    n = len(pos)
    chunks = [tuple(range(r - chunk + 1, r + 1)) for r in range(n) if pos[r] % chunk == chunk - 1]
    E, S = [], []
    for t in range(n):
        E.append([m for m in range(t + 1)
                  if episode[m] == episode[t] and pos[m] // block == pos[t] // block])
        S.append([c for c in chunks
                  if episode[c[-1]] == episode[t] and pos[c[-1]] // block < pos[t] // block])
    return E, S


def rope_by_hand(x, pos, theta):
    """x (T, H, D) float64, rotate-half at ``pos`` (T,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half) / half)
    angle = pos[:, None, None] * inv
    a, b = x[..., :half], x[..., half:]
    return np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                           b * np.cos(angle) + a * np.sin(angle)], axis=-1)


def softmax(x):
    e = np.exp(x - x.max())
    return e / e.sum()


def eva_by_hand(p, u, first_flags, averaged=False):
    """One row (T, hidden) through the mixer, query by query, in float64.
    ``averaged``: the mean of two attentions (one over E, one over S) in place
    of one softmax over both: what the layer must *not* compute."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    u = np.asarray(u, np.float64)
    _, pos = grid_by_hand(first_flags)
    heads = lambda x: x.reshape(len(u), HEADS, D)  # noqa: E731
    q, k, v = (heads(u @ p[f"{n}_proj"]["kernel"]) for n in "qkv")
    q, k = rope_by_hand(q, pos, ARCH["rope_theta"]), rope_by_hand(k, pos, ARCH["rope_theta"])
    s = D ** -0.5
    E, S = sets_by_hand(first_flags)
    out = np.zeros_like(q)
    for t in range(len(u)):
        for h in range(HEADS):
            keys, values = [k[m, h] for m in E[t]], [v[m, h] for m in E[t]]
            pooled_k, pooled_v = [], []
            for c in S[t]:
                mk, mv = k[list(c), h], v[list(c), h]
                pooled_k.append(softmax(s * mk @ p["pool_k"][h]) @ mk)
                pooled_v.append(softmax(s * mk @ p["pool_v"][h]) @ mv)
            if averaged and pooled_k:
                parts = [softmax(s * np.asarray(ks) @ q[t, h]) @ np.asarray(vs)
                         for ks, vs in ((keys, values), (pooled_k, pooled_v))]
                out[t, h] = 0.5 * (parts[0] + parts[1])
            else:
                ks, vs = np.asarray(keys + pooled_k), np.asarray(values + pooled_v)
                out[t, h] = softmax(s * ks @ q[t, h]) @ vs
    return out.reshape(len(u), -1) @ p["o_proj"]["kernel"], E, S


@pytest.fixture(scope="module")
def mixer():
    module = EvaAttention(hidden=64, heads=HEADS, block=W, chunk=C, rope_theta=1e5, init_std=0.05)
    u = jnp.asarray(np.random.default_rng(5).standard_normal((B, T, 64)), jnp.float32)
    seg = jnp.cumsum(jnp.asarray(make_batch(0)["is_fir"][..., 0], jnp.int32), axis=1)
    params = moved(jax.jit(module.init)(jax.random.key(2), u, seg), seed=3)
    return module, params, u, seg


def test_the_mixer_reads_exactly_E_and_S_under_one_normaliser(mixer):
    module, params, u, seg = mixer
    got = jax.jit(module.apply)(params, u, seg)
    firsts = make_batch(0)["is_fir"][..., 0]
    for row in range(B):
        want, E, S = eva_by_hand(params["params"], u[row], firsts[row])
        close(got[row], want, 2e-5)
        two, _, _ = eva_by_hand(params["params"], u[row], firsts[row], averaged=True)
        reads = np.asarray([bool(s) for s in S])
        assert reads.any() and not reads.all()
        # the mean of two attentions is another function wherever a summary is read
        assert np.abs(two - want)[reads].max() > 1e-2
        np.testing.assert_allclose(two[~reads], want[~reads], atol=1e-12)
        # the window's cases: |E(t)| = (p mod W) + 1, |S(t)| = (W / C) b(t)
        _, pos = grid_by_hand(firsts[row])
        assert [len(e) for e in E] == list(pos % W + 1)
        assert [len(s) for s in S] == list(W // C * (pos // W))
    _, pos0 = grid_by_hand(firsts[0])
    assert pos0[36] == 36 and pos0[37] == 0 and pos0[-1] == 90 > 2 * W  # opens mid-episode; 3 blocks
    _, pos1 = grid_by_hand(firsts[1])
    assert pos1[46] == 41 and 41 % C != C - 1  # an episode that ends inside a chunk


def test_a_chunk_of_the_querys_own_block_is_never_read_as_a_summary(mixer):
    """The pooling vectors reach a query only through summaries: a query of its
    episode's first block is the same function whatever they are, to the bit;
    every later one moves."""
    module, params, u, seg = mixer
    other = jax.tree.map(lambda a: a, params)
    other["params"] = {**params["params"], "pool_k": params["params"]["pool_k"] + 1.0,
                       "pool_v": params["params"]["pool_v"] - 1.0}
    run = jax.jit(module.apply)
    a, b = np.asarray(run(params, u, seg)), np.asarray(run(other, u, seg))
    _, blk, _, _ = episode_grid(seg, W, C)
    first_block = np.asarray(blk) == 0
    np.testing.assert_array_equal(a[first_block], b[first_block])
    assert (np.abs(a - b).max(axis=-1)[~first_block] > 1e-6).all()


def test_an_episode_moved_inside_its_window_keeps_its_outputs(family, actor, system):
    """The grid and the rotation count from the episode's first step: the same
    70 steps placed at 13 and at 18 (neither a grid line of the window, and
    5 steps apart: not a chunk's multiple) give the same logits and values."""
    length, a, b = 70, 13, 18
    base = make_batch(7, seams=((a, a + length), (b, b + length)))
    episode = np.random.default_rng(8).standard_normal((length, OBS)).astype(np.float32)
    base["obs"][0, a:a + length] = episode
    base["obs"][1, b:b + length] = episode
    value, logits, _ = system(actor, base)
    close(logits[0, a:a + length], logits[1, b:b + length], 1e-5)
    close(value[0, a:a + length], value[1, b:b + length], 1e-5)
    assert np.abs(np.asarray(logits[0, :a]) - np.asarray(logits[1, :a])).max() > 1e-4


# ------------------------------------------------------- the family as a whole
def test_outputs_match_the_reference_in_float32(actor, system, plain):
    batch = make_batch(2)
    value, logits, records = system(actor, batch)
    ref_value, ref_logits = plain(actor, batch)
    close(logits, ref_logits, 1e-4)
    close(value, ref_value, 1e-4)
    assert len(records) == 2 and all("choice" not in r for r in records)


def test_bfloat16_stays_near_the_reference(actor, plain):
    fam = build_family(config(compute_dtype="bfloat16"))
    batch = make_batch(3)
    value, logits = jax.jit(
        lambda p, b: policy_outputs(fam, {"actor": p}, Batch.from_mapping(b))[2:])(actor, batch)
    ref_value, ref_logits = plain(actor, batch)
    for got, want in ((logits, ref_logits), (value, ref_value)):
        close(got, want, 5e-2 * float(np.abs(want).max()))
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


def test_ppo_loss_and_every_gradient_match_the_reference(family, actor, plain, monkeypatch):
    """The train step's own loss and ``jax.grad`` of it against the reference
    forward under the reference loss, leaf by leaf — with the queries scored
    against the summaries 32 at a time, so that the blocks' static bounds on
    the candidates are in the program."""
    monkeypatch.setattr(evabyte, "QUERY_BLOCK", 32)
    cfg = config()
    batch = make_batch(4)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    params = {"actor": actor}
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=rmsprop(cfg).init(params))
    step = make_train_step(config(learn_diag=True), family)
    _, metrics = jax.jit(step)(state, Batch.from_mapping(jb), jax.random.key(1))
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: ref_ppo_loss(p, jb, cfg)))(actor)
    ref_value, ref_logits = plain(actor, batch)
    numpy_loss = ref_losses.ppo(ref_logits, ref_value, batch, PARAMS)["loss"]
    assert abs(float(ref_loss) - numpy_loss) < 1e-5
    assert abs(float(metrics["loss"]) - numpy_loss) < 1e-5

    def sys_loss(p):
        from tpu_rl.algos.ppo import td_target_and_gae
        from tpu_rl.ops.losses import smooth_l1

        b = Batch.from_mapping(jb)
        log_probs, entropy, value, _ = policy_outputs(family, {"actor": p}, b)
        td_target, adv = td_target_and_gae(cfg, b, value)
        ratio = jnp.exp(log_probs[:, :-1] - jb["log_prob"][:, :-1])
        surr = jnp.minimum(ratio * adv, jnp.clip(ratio, 1 - cfg.eps_clip, 1 + cfg.eps_clip) * adv)
        return (-cfg.policy_loss_coef * surr.mean()
                + cfg.value_loss_coef * smooth_l1(value[:, :-1], td_target)
                - cfg.entropy_coef * entropy[:, :-1].mean())

    grads = jax.jit(jax.grad(sys_loss))(actor)
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(ref_grads))
    names = []
    for (path, got), want in zip(jax.tree.leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        names.append(name)
        assert float(jnp.abs(got - want).max()) <= 2e-4 * scale, name
        assert float(jnp.abs(got).max()) > 0, name
    for leaf in ("q_proj", "k_proj", "v_proj", "o_proj", "pool_k", "pool_v", "gate_proj",
                 "up_proj", "down_proj", "input_layernorm", "post_attention_layernorm"):
        assert sum(leaf in name for name in names) == 2, leaf  # in each of the two layers


@pytest.mark.parametrize("algo", ["PPO", "IMPALA", "V-MPO"])
def test_each_on_policy_algorithm_runs_one_update(algo, monkeypatch):
    """A family without experts whose layers hand records back: they pass
    through ``unroll_routed``, ``policy_outputs_routed`` and the two scalar
    folds, and every gauge is a finite number."""
    cfg = config(algo=algo, learn_diag=True, update_guard=True)
    eager = ModelFamily.init_params
    monkeypatch.setattr(ModelFamily, "init_params", lambda self, key, seq_len=2: jax.jit(
        lambda k: eager(self, k, seq_len))(key))
    fam, state, step = get_algo(algo).build(cfg, jax.random.key(0))
    lay = BatchLayout.from_config(cfg)
    assert (lay.hx, lay.cx) == (1, 1) and not fam.store_carry and fam.route_unroll is not None
    before = jax.device_get(state.params["actor"])
    state, metrics = jax.jit(step)(state, Batch.from_mapping(make_batch(13)), jax.random.key(1))
    assert np.isfinite(float(metrics["loss"])) and float(metrics["nonfinite-updates"]) == 0
    scalars = {k: float(v) for k, v in metrics["diag"]["scalars"].items()}
    assert all(np.isfinite(v) for v in scalars.values()) and not any(
        k.startswith("moe-") for k in scalars)
    assert scalars["attn-pairs-block"] > scalars["attn-pairs-summary"] > 0
    moved_by = jax.tree.map(lambda a, b: float(np.abs(a - np.asarray(b)).max()),
                            before, state.params["actor"])
    assert all(d > 0 for d in jax.tree.leaves(moved_by))


def test_records_without_experts_pass_the_scalar_folds(actor, system):
    _, _, records = system(actor, make_batch(2))
    assert route_scalars(records) == {} and route_scalars([]) == {}
    folded = {k: float(v) for k, v in attention_scalars(records).items()}
    one = {k: float(v) for k, v in attention_scalars(records[:1]).items()}
    assert set(folded) == {"attn-pairs-block", "attn-pairs-summary", "attn-tiles-run-block",
                           "attn-tiles-band-block", "attn-bwd-steps-block"}
    assert folded == {k: 2 * v for k, v in one.items()}  # summed over the two layers


# ----------------------------------------------------------------- the counters
def test_the_counters_against_the_enumeration_and_the_closed_forms():
    firsts = make_batch(0)["is_fir"][..., 0]
    seg = jnp.cumsum(jnp.asarray(firsts, jnp.int32), axis=1)
    counts = jax.jit(lambda s: pair_counts(s, W, C))(seg)
    exact = summary = 0
    for row in firsts:
        E, S = sets_by_hand(row)
        exact += sum(len(e) for e in E)
        summary += sum(len(s) for s in S)
    assert float(counts["attn-pairs"]["block"]) == exact
    assert float(counts["attn-pairs"]["summary"]) == summary
    # no seam: a query keeps (W + 1) / 2 exact pairs and (W / C) (T / W - 1) / 2 summaries in the mean
    whole = jax.jit(lambda s: pair_counts(s, W, C))(jnp.zeros((1, T), jnp.int32))
    assert float(whole["attn-pairs"]["block"]) == T * (W + 1) / 2
    assert float(whole["attn-pairs"]["summary"]) == T * (W // C) * (T // W - 1) / 2
    # the published grid at the cell's window: 1,024.5 + 448 = 1,472.5 a query
    big = jax.jit(lambda s: pair_counts(s, 2048, 16))(jnp.zeros((1, 16384), jnp.int32))
    pairs = big["attn-pairs"]
    assert (float(pairs["block"]) + float(pairs["summary"])) / 16384 == 1472.5
    assert float(pairs["summary"]) / 16384 == 448.0


def test_the_candidates_static_bounds_hold_at_the_worst_case(mixer):
    """One episode, the whole window: exactly T / C complete chunks, the j-th
    ending at step C j + C - 1 — the earliest any window can end its j-th —
    so a block of queries that stops at step e meets e / C candidates at most."""
    module, params, u, _ = mixer
    seg = jnp.zeros((1, T), jnp.int32)
    pos, blk, _, ends = episode_grid(seg, W, C)
    k = jnp.zeros((1, T, HEADS, D))
    _, _, seg_c, blk_c = module.apply(params, k, k, seg, blk, ends, method="summaries")
    assert seg_c.shape == (1, T // C) and (np.asarray(seg_c) == 0).all()  # none absent
    np.testing.assert_array_equal(np.asarray(blk_c)[0], np.arange(T // C) // (W // C))
    # with seams there are fewer, the absent ones carry an id no query has, and
    # the j-th still ends no earlier
    seg = jnp.cumsum(jnp.asarray(make_batch(0)["is_fir"][..., 0], jnp.int32), axis=1)
    pos, blk, _, ends = episode_grid(seg, W, C)
    k2 = jnp.zeros((B, T, HEADS, D))
    _, _, seg_c, _ = module.apply(params, k2, k2, seg, blk, ends, method="summaries")
    for row in range(B):
        last = np.flatnonzero(np.asarray(ends[row]))
        assert (last >= C * np.arange(len(last)) + C - 1).all()
        assert (np.asarray(seg_c[row])[: len(last)] >= 0).all()
        assert (np.asarray(seg_c[row])[len(last):] == -1).all() and len(last) < T // C


# The seams of each row of a window and the window's steps: what a wrong way back
# from the chunks to the steps gets wrong.
POOL_CASES = {
    "no seam": (((),), T),
    "a seam off the chunks' grid": (((37,),), T),
    "an episode shorter than a chunk": (((40, 42),), T),
    "an incomplete chunk before a seam and at the window's end": (((0, 38),), T),
    "the window opens inside an episode": (((5,),), T),
    # 0 + 1 + 30 complete chunks of 32, the last on steps 124 .. 127: the absent
    # candidate's clipped span lies on it
    "an absent candidate on the window's last chunk": (((3, 8),), T),
    "two rows, each with seams of its own": (SEAMS, T),
    "a window that is no whole number of chunks": (SEAMS, T - 2),
}


@pytest.mark.parametrize("case", list(POOL_CASES))
def test_the_summaries_and_their_gradients_against_the_shifted_products(mixer, case):
    """``summaries``' k~, v~ and the gradients of k, v, ``pool_k``, ``pool_v``
    in float32 against ``jax.grad`` of the reference's pooled rows read at the
    chunks' last steps: the members go to the chunks by a gather and their
    gradients come back by its inverse, no sum — every step gets the gradient
    of the one chunk it lies in, or none; an absent candidate is exactly zero,
    carries the id -1, and nothing of its cotangent reaches a step."""
    module, params, _, _ = mixer
    seams, steps = POOL_CASES[case]
    rows, n = len(seams), steps // C
    fir = make_batch(0, seams, rows, steps)["is_fir"][..., 0]
    seg = jnp.cumsum(jnp.asarray(fir, jnp.int32), axis=1)
    _, blk, _, ends = episode_grid(seg, W, C)
    last = [np.flatnonzero(np.asarray(e)) for e in ends]
    rng = np.random.default_rng(11)
    k, v = (jnp.asarray(rng.standard_normal((rows, steps, HEADS, D)), jnp.float32)
            for _ in range(2))
    w_k, w_v = (jnp.asarray(rng.standard_normal((rows, n, HEADS, D)), jnp.float32)
                for _ in range(2))

    def system(p, k, v):
        ks, vs, seg_c, _ = module.apply({"params": p}, k, v, seg, blk, ends, method="summaries")
        return (ks * w_k).sum() + (vs * w_v).sum(), (ks, vs, seg_c)

    def plain(p, k, v):
        pooled = [reference.pooled_rows(k, x, p[w], C, D ** -0.5)
                  for x, w in ((k, "pool_k"), (v, "pool_v"))]
        ks, vs = (jnp.stack([
            jnp.zeros((n, HEADS, D)).at[: len(at)].set(x[b][at]) for b, at in enumerate(last)])
            for x in pooled)
        return (ks * w_k).sum() + (vs * w_v).sum(), (ks, vs)

    pools = {name: params["params"][name] for name in ("pool_k", "pool_v")}
    (_, (ks, vs, seg_c)), got = jax.jit(
        jax.value_and_grad(system, argnums=(0, 1, 2), has_aux=True))(pools, k, v)
    (_, want_rows), want = jax.jit(
        jax.value_and_grad(plain, argnums=(0, 1, 2), has_aux=True))(pools, k, v)
    for b, at in enumerate(last):
        assert (np.asarray(seg_c[b])[len(at):] == -1).all()
        assert not np.asarray(ks[b, len(at):]).any() and not np.asarray(vs[b, len(at):]).any()
    assert any(len(at) < n for at in last) == (case != "no seam")
    jax.tree.map(lambda a, b: close(a, b, 2e-5), (ks, vs), want_rows)
    jax.tree.map(lambda a, b: close(a, b, 2e-5), got, want)
    # a step in no complete chunk gets nothing, every other one something
    inside = np.zeros((rows, steps), bool)
    for b, at in enumerate(last):
        for e in at:
            inside[b, e - C + 1: e + 1] = True
    for grad in got[1:]:
        moved_steps = np.abs(np.asarray(grad)).max(axis=(2, 3)) > 0
        np.testing.assert_array_equal(moved_steps, inside)


# ------------------------------------------------------------------- acting
def unroll_by_steps(fam, actor, batch, row, ctx=None):
    """``family.act``'s module method, step by step over one row, the carry
    zeroed at an episode's first step as the worker zeroes it."""
    hw, cw = fam.carry_widths
    act = jax.jit(lambda p, o, h, c: fam.actor.apply(p, o, h, c, method="act"))
    h, c = jnp.zeros((1, hw)), jnp.zeros((1, cw))
    logits, values = [], []
    for t in range(batch["obs"].shape[1]):
        if batch["is_fir"][row, t, 0] > 0:
            h, c = jnp.zeros((1, hw)), jnp.zeros((1, cw))
        lg, v, (h, c) = act(actor, jnp.asarray(batch["obs"][row:row + 1, t]), h, c)
        logits.append(np.asarray(lg[0]))
        values.append(np.asarray(v[0]))
    return np.stack(logits), np.stack(values)


@pytest.mark.parametrize("row", range(B))
def test_act_agrees_with_the_unroll(family, actor, system, row):
    """Across seams, block boundaries (the ring restarts: step 32 of an episode
    lands in slot 0), a chunk's last step and an episode that ends inside a
    chunk. Row 0's window opens mid-episode: its first step starts a fragment
    for both."""
    batch = make_batch(9)
    value, logits, _ = system(actor, batch)
    got_logits, got_value = unroll_by_steps(family, actor, batch, row)
    close(got_logits, logits[row], 2e-5)
    close(got_value, value[row], 2e-5)


def test_past_ctx_the_store_forgets_its_oldest_summaries(actor, system):
    """A store of 64 / 4 = 16 summaries holds two blocks' chunks: to the end of
    an episode's third block (96 steps: summaries 0-15 read, 16-23 written
    into the slots of the first block's, which block 2 still reads — from
    step 64 + ... on the oldest are gone) acting agrees with the unroll as
    long as no read summary was overwritten, and stays finite after."""
    fam = build_family(config(act_ctx=64))
    assert fam.carry_widths == (0, 2 * (2 * W + 2 * 64 // C) * HEADS * D + 1)
    batch = make_batch(10, seams=((), ()))
    value, logits, _ = system(actor, batch)
    got, _ = unroll_by_steps(fam, actor, batch, 0)
    # chunk 16 (steps 64-67) overwrites slot 0 at step 67, which block 2 reads
    close(got[:67], logits[0, :67], 2e-5)
    assert np.isfinite(got).all() and np.abs(got[68:96] - np.asarray(logits[0, 68:96])).max() > 1e-6


def test_acting_state_and_widths():
    state = EvaByteActorCritic.acting_state(ARCH, 128)
    exact, pooled = (W, HEADS, D), (128 // C, HEADS, D)
    assert state == [ring(exact, exact, pooled, pooled)] * 2
    assert state_widths(state) == (0, 2 * (2 * W + 2 * 128 // C) * HEADS * D + 1)
    # the published widths at a 16k context: 25.2M floats a layer against 134.2M of keys and values
    published = dict(ARCH, hidden_size=4096, num_attention_heads=32, window_size=2048, chunk_size=16)
    (_, shapes), *_ = EvaByteActorCritic.acting_state(published, 16384)
    assert sum(int(np.prod(s)) for s in shapes) == 25_165_824
    assert 2 * 16384 * 4096 == 134_217_728
    with pytest.raises(AssertionError, match="whole number"):
        EvaByteActorCritic.acting_state(ARCH, 130)


# ---------------------------------------- the kernels' construction, interpreted
@pytest.mark.parametrize("seams", [(), (100, 500)], ids=["no-seam", "seams"])
def test_the_kernel_construction_in_the_interpreter_matches_the_jnp_form(seams, monkeypatch):
    """Blocks of 384 steps in a window of 768 on tiles of gcd(1024, 768) = 256:
    the splash forward with each row's masks read from the (episode, block)
    ids, the repo's own backward with the logsumexp's cotangent folded into
    ``di``, merged with the summaries' part — against the ``jnp`` form
    (``attn_full``'s masked scores), forward and every gradient."""
    # three query tiles of 256, the first of which lies in the opening block and reads no
    # summary; 256 candidates: one key tile of the summaries' call
    steps, block, chunk = 768, 384, 3
    module = EvaAttention(hidden=512, heads=4, block=block, chunk=chunk, rope_theta=1e5,
                          init_std=0.05)
    rng = np.random.default_rng(11)
    u = jnp.asarray(rng.standard_normal((1, steps, 512)), jnp.float32)
    fir = np.zeros((1, steps), np.int32)
    fir[0, list(seams)] = 1
    seg = jnp.cumsum(jnp.asarray(fir), axis=1)
    params = moved(jax.jit(module.init)(jax.random.key(4), u, seg), seed=5)
    weight = jnp.asarray(rng.standard_normal((1, steps, 512)), jnp.float32)

    def loss(p, u, interpret):
        out = module.apply(p, u, seg, interpret)
        return jnp.sum(out * weight), out

    taken = []
    real = sequence.summary_attention_lse
    monkeypatch.setattr(evabyte, "summary_attention_lse", lambda *a, **k: taken.append(
        real(*a, **k)) or taken[-1])
    run = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True), static_argnums=2)
    (g_kernel, gu_kernel), out_kernel = run(params, u, True)
    (g_plain, gu_plain), out_plain = run(params, u, False)
    close(out_kernel, out_plain, 2e-4 * float(jnp.abs(out_plain).max()))
    for (path, got), want in zip(jax.tree.leaves_with_path(g_kernel), jax.tree.leaves(g_plain)):
        close(got, want, 5e-4 * float(jnp.abs(want).max()))
        assert float(jnp.abs(want).max()) > 0, jax.tree_util.keystr(path)
    close(gu_kernel, gu_plain, 5e-4 * float(jnp.abs(gu_plain).max()))
    assert [x is None for x in taken] == [False, True]  # the kernels read the summaries, then jnp


def _masked_softmax_read(q, ks, vs, keep, scale):
    """The plain form of a read over keys that are no steps: (B, T, H, D) and
    the logsumexp (B, H, T) over the kept, -inf where none is."""
    s = scale * jnp.einsum("bqhd,bnhd->bhqn", q, ks)
    s = jnp.where(keep[:, None], s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.where(keep[:, None], jnp.exp(s - jnp.where(jnp.isfinite(lse), lse, 0.0)[..., None]), 0.0)
    return jnp.einsum("bhqn,bnhd->bqhd", p, vs), lse


@pytest.mark.parametrize("late", [20, 60], ids=["every-query-tile-reads", "a-query-tile-reads-none"])
@pytest.mark.parametrize("keys", [128, 256], ids=["one-key-tile", "two-key-tiles"])
def test_the_summaries_call_reads_its_segment_up_to_each_querys_reach(keys, late):
    """``summary_attention_lse`` in the interpreter on a (384, keys) rectangle
    in tiles of 128: key-side ids of its own with an absent tail (-1), a reach
    a query that runs from -1 (reads nothing: a hugely negative logsumexp, no
    weight in a merge) to past a key tile's edge, never past the query's own
    index (the library's static causal structure on the rectangle stands); the
    output, the logsumexp and the gradients of a loss on both against the
    plain masked softmax."""
    steps, heads, width = 384, 2, 128
    rng = np.random.default_rng(keys)
    q, ks, vs = (jnp.asarray(rng.standard_normal(shape), jnp.float32) * 0.5
                 for shape in ((1, steps, heads, width), (1, keys, heads, width),
                               (1, keys, heads, width)))
    seg = jnp.asarray(np.repeat([1, 2, 3], [150, 150, 84])[None], jnp.int32)
    present = keys - 40  # the last 40 candidates are absent
    seg_k = jnp.asarray(np.where(
        np.arange(keys) < present, np.repeat([1, 2, 3], [keys // 4, keys // 2, keys // 4]),
        -1)[None], jnp.int32)
    # late 60: no query of the first tile of 128 reaches a key (the window's opening block)
    reach = jnp.asarray(np.clip(
        np.arange(steps) * keys // steps - late * keys // 128, -1, None)[None], jnp.int32)
    assert bool((reach[0, :128] < 0).all()) == (late == 60)
    keep = (seg[:, :, None] == seg_k[:, None, :]) & (
        jnp.arange(keys)[None, None, :] <= reach[:, :, None])
    assert bool(keep.any(-1).any()) and not bool(keep.any(-1).all())
    assert bool((reach <= jnp.arange(steps)).all())
    w_o = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    w_l = jnp.asarray(rng.standard_normal((1, heads, steps)), jnp.float32)

    def loss(read, q, ks, vs):
        o, lse = read(q, ks, vs)
        reads = jnp.isfinite(lse) & (lse > -1e30)  # where nothing is read the caller gives no weight
        return jnp.sum(o * w_o * reads.transpose(0, 2, 1)[..., None]) + jnp.sum(
            jnp.where(reads, lse, 0.0) * w_l), (o, lse)

    kernel = lambda q, ks, vs: sequence.summary_attention_lse(  # noqa: E731
        q, ks, vs, seg, seg_k, reach, width ** -0.5, interpret=True)
    plain = lambda q, ks, vs: _masked_softmax_read(q, ks, vs, keep, width ** -0.5)  # noqa: E731
    grad = lambda read: jax.jit(jax.grad(  # noqa: E731
        lambda *a: loss(read, *a), argnums=(0, 1, 2), has_aux=True))(q, ks, vs)
    got, (o, lse) = grad(kernel)
    want, (o_ref, lse_ref) = grad(plain)
    reads = np.asarray(keep.any(-1))[0]
    close(np.asarray(o)[0][reads], np.asarray(o_ref)[0][reads], 1e-5)
    close(np.asarray(lse)[0][:, reads], np.asarray(lse_ref)[0][:, reads], 1e-5)
    assert (np.asarray(lse)[0][:, ~reads] < -1e30).all()
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(lse)).all()
    for a, b in zip(got, want):
        close(a, b, 1e-4 * float(jnp.abs(b).max()))
    # a candidate count the kernels cannot tile, or a CPU program: the caller's jnp form
    assert sequence.summary_attention_lse(
        q, ks[:, :96], vs[:, :96], seg, seg_k[:, :96], reach, 1.0, interpret=True) is None
    assert sequence.summary_attention_lse(q, ks, vs, seg, seg_k, reach, 1.0) is None


def test_the_logsumexp_of_the_exact_half_is_an_output_with_a_gradient():
    """``flash_attention_lse``: the rows' walk in the interpreter (four tiles of
    128, the repo's own backward with the cotangent folded into ``di``) against
    the ``jnp`` form, on a loss that reads the logsumexp alone and one that
    reads both outputs."""
    steps, heads, width = 512, 2, 128
    rng = np.random.default_rng(3)
    q, k, v = (jnp.asarray(rng.standard_normal((2, steps, heads, width)), jnp.float32) * 0.5
               for _ in range(3))
    seg = jnp.asarray(np.stack([np.repeat([0, 1, 2], [100, 300, 112]), np.zeros(steps)]), jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(steps), (2, steps))
    w_o = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)
    w_l = jnp.asarray(rng.standard_normal((2, heads, steps)), jnp.float32)
    for both in (0.0, 1.0):
        def loss(q, k, v, interpret):
            o, lse = sequence.flash_attention_lse(q, k, v, pos, seg, interpret=interpret)
            return both * jnp.sum(o * w_o) + jnp.sum(lse * w_l), (o, lse)

        run = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True), static_argnums=3)
        got, (o, lse) = run(q, k, v, True)
        want, (o_ref, lse_ref) = run(q, k, v, False)
        close(o, o_ref, 1e-5)
        close(lse, lse_ref, 1e-5)
        for a, b in zip(got, want):
            close(a, b, 1e-4 * float(jnp.abs(b).max()))


# ------------------------------------------------------------- the config check
REFUSED = {
    "window-not-chunks": (dict(window_size=30), "whole number of chunk_size"),
    "grouped-heads": (dict(num_key_value_heads=2), "multi-head"),
    "head-size": (dict(hidden_size=66), "head size"),
    "odd-head": (dict(hidden_size=60), "rotate-half"),
    "other-attention": (dict(attention_class="mha"), "attention_class"),
    "plain-norm": (dict(norm_add_unit_offset=False), r"1 \+ w"),
    "rope-scaling": (dict(rope_scaling={"type": "linear"}), "rotary scaling"),
    "bias": (dict(attention_bias=True), "no bias"),
    "activation": (dict(hidden_act="gelu"), "gelu"),
    "no-layer": (dict(num_hidden_layers=0), "0"),
}


@pytest.mark.parametrize("change, message", REFUSED.values(), ids=REFUSED.keys())
def test_what_the_family_refuses(change, message):
    with pytest.raises(AssertionError, match=message):
        config(arch={**ARCH, **change})


@pytest.mark.parametrize("key", EVABYTE_ARCH_KEYS)
def test_a_missing_key_is_named(key):
    with pytest.raises(AssertionError, match=key):
        config(arch={k: v for k, v in ARCH.items() if k != key})


def test_arch_is_needed_and_the_algorithm_is_on_policy():
    with pytest.raises(AssertionError, match="needs arch"):
        Config.from_dict({**PARAMS, "arch": None})
    with pytest.raises(AssertionError, match="on-policy"):
        config(algo="SAC")
