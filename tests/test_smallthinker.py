"""The smallthinker family (``tpu_rl/models/smallthinker.py``) at tiny widths
on the CPU against the benchmark's plain reference
(``benchmarks/reference/smallthinker.py``: dense masked attention a block of
queries at a time, positions counted from the episode's start, the held
experts as a loop under a mask): outputs, the PPO loss and every gradient with
identical choices asserted; the four ranks' parts of a layer adding up to the
uncut one; the window mask, the rotation, the softmax router and the gated
walk each against its plain form; the relu2 walk against the parent's, bit
for bit; acting against the unroll past the window; the pair counters against
a count. One global NoPE layer and three 8-step-window RoPE layers, 16 routed
experts over 4 ranks (rank 1 holds experts 4-7), 3 chosen per token."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import losses as ref_losses
from benchmarks.reference import smallthinker as reference
from test_granite_hybrid import close, make_batch
from test_nemotron_h import ref_ppo_loss, same_choices
from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.algos.ppo import make_train_step, policy_outputs, policy_outputs_routed
from tpu_rl.algos.registry import get_algo
from tpu_rl.config import Config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.models import cells
from tpu_rl.models.families import ModelFamily, build_family
from tpu_rl.models.layers import kept_pairs, rope
from tpu_rl.models.smallthinker import SmallThinkerLayer
from tpu_rl.ops import moe
from tpu_rl.parallel.sequence import full_attention
from tpu_rl.types import Batch

SHARE = dict(published_n_routed_experts=16, chips=4, rank=1)
WINDOW = 8
ARCH = dict(
    hidden_size=64, num_hidden_layers=4, rms_norm_eps=1e-6, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, rope_theta=1500000, rope_scaling=None,
    rope_layout=[0, 1, 1, 1], sliding_window_layout=[0, 1, 1, 1], sliding_window_size=WINDOW,
    moe_ffn_hidden_size=48, moe_num_primary_experts=4, moe_num_active_primary_experts=3,
    moe_primary_router_apply_softmax=True, norm_topk_prob=True, expert_parallel=SHARE,
)
T, B, OBS, ACTIONS = 32, 2, 6, 3
PARAMS = dict(algo="PPO", model="smallthinker", arch=ARCH, obs_shape=(OBS,),
              action_space=ACTIONS, seq_len=T, batch_size=B)


def config(**kw) -> Config:
    return Config.from_dict({**PARAMS, **kw})


@pytest.fixture(params=["auto", "interpret"], ids=["jnp", "pallas"])
def kernel_form(request, monkeypatch):
    monkeypatch.setattr(cells, "_PALLAS_MODE", request.param)
    return request.param


@pytest.fixture(scope="module")
def family():
    return build_family(config())


@pytest.fixture(scope="module")
def actor(family):
    """Seeded weights with every leaf moved off its initial value."""
    def make(key):
        tree = family.init_params(key, seq_len=T)["actor"]
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(jax.random.key(1), len(leaves))
        return jax.tree.unflatten(treedef, [
            x + 0.1 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)
        ])

    return jax.jit(make)(jax.random.key(0))


@pytest.fixture(scope="module")
def system(family):
    """(value, logits, routes) of the system's unroll."""
    return jax.jit(lambda p, b: policy_outputs_routed(family, {"actor": p}, Batch.from_mapping(b))[2:])


@pytest.fixture(scope="module")
def plain():
    def run(p, b, choices=None):
        logits, value, routes = reference.forward_routed(p, b, PARAMS, choices)
        return value, logits, routes

    return jax.jit(run)


# ------------------------------------------------------- the family as a whole
def test_outputs_and_choices_match_the_reference_in_float32(actor, system, plain):
    batch = make_batch(2)
    assert batch["is_fir"].sum() >= 4
    value, logits, routes = system(actor, batch)
    ref_value, ref_logits, ref_routes = plain(actor, batch)
    assert len(routes) == 4 and routes[0]["choice"].shape == (B, T, 3)
    assert same_choices(routes, ref_routes)
    close(logits, ref_logits, 1e-4)
    close(value, ref_value, 1e-4)
    held = float(routes[0]["stats"]["held-share"])
    assert 0.05 < held < 0.6 and float(routes[0]["stats"]["rows"]) == round(held * B * T * 3)


def test_bfloat16_matches_the_reference_on_the_systems_choices(actor, plain):
    fam = build_family(config(compute_dtype="bfloat16"))
    batch = make_batch(3)
    value, logits, routes = jax.jit(
        lambda p, b: policy_outputs_routed(fam, {"actor": p}, Batch.from_mapping(b))[2:])(actor, batch)
    ref_value, ref_logits, ref_routes = plain(actor, batch, [r["choice"] for r in routes])
    for got, want in ((logits, ref_logits), (value, ref_value)):
        close(got, want, 3e-2 * float(np.abs(want).max()))
        assert float(np.abs(np.asarray(got) - np.asarray(want)).max()) > 1e-6
    for mine, theirs in zip(routes, ref_routes):
        differ = (np.sort(mine["choice"], -1) != np.sort(theirs["choice"], -1)).any(-1)
        assert differ.mean() < 0.2
        assert (np.asarray(theirs["margin"])[differ] < 0.1).all()


def test_ppo_loss_and_every_gradient_match_the_reference(family, actor, system, plain, kernel_form):
    """The train step's own loss and ``jax.grad`` of it against the reference
    forward under the reference loss, leaf by leaf; the router's weights get
    their gradient through the softmax over the chosen logits."""
    cfg = config()
    batch = make_batch(4)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    assert same_choices(system(actor, batch)[2], plain(actor, batch)[2])
    params = {"actor": actor}
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=rmsprop(cfg).init(params))
    step = make_train_step(config(learn_diag=True), family)
    _, metrics = jax.jit(step)(state, Batch.from_mapping(jb), jax.random.key(1))
    forward = lambda p, b: reference.forward(p, b, PARAMS)  # noqa: E731
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(
        lambda p: ref_ppo_loss(p, jb, cfg, forward)))(actor)
    ref_value, ref_logits, _ = plain(actor, batch)
    numpy_loss = ref_losses.ppo(ref_logits, ref_value, batch, PARAMS)["loss"]
    assert abs(float(ref_loss) - numpy_loss) < 1e-5
    assert abs(float(metrics["loss"]) - numpy_loss) < 1e-5
    scalars = metrics["diag"]["scalars"]
    assert float(scalars["moe-rows"]) == sum(
        float(r["stats"]["rows"]) for r in system(actor, batch)[2])
    assert float(scalars["moe-chunks"]) == 1.0

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
    for leaf in ("router", "w_gate", "w_in", "w_out", "q_proj", "input_norm", "post_norm"):
        assert sum(leaf in name for name in names) == 4, leaf  # in each of the four layers


def layer_of(rank: int, index: int, chips: int = 4) -> SmallThinkerLayer:
    arch = {**ARCH, "moe_num_primary_experts": 16 // chips,
            "expert_parallel": dict(published_n_routed_experts=16, chips=chips, rank=rank)}
    return SmallThinkerLayer(arch, index)


@pytest.mark.parametrize("index", [0, 1], ids=["global", "window"])
def test_the_four_ranks_parts_add_up_to_the_uncut_layer(kernel_form, index):
    """Each rank computes the whole attention and its own four experts' part
    of the routed sum. The parts, with the attention residual (what every
    rank computes alike) counted once, equal the uncut reference's layer."""
    rng = np.random.default_rng(20)
    x = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    seam = np.zeros((B, T), bool)
    seam[:, 11] = True
    seg = jnp.cumsum(jnp.asarray(seam, jnp.int32), axis=1)
    whole = jax.jit(lambda k: layer_of(0, index, chips=1).init(k, x, seg))(jax.random.key(2))["params"]
    whole = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), whole)
    uncut = {k: v for k, v in ARCH.items() if k != "expert_parallel"} | {"moe_num_primary_experts": 16}
    a = reference.rms_norm(x, whole["input_norm"]["scale"], 1e-6)
    after = x + reference.attention(a, jnp.asarray(seam), whole["attention"], uncut, index)
    h = reference.rms_norm(after, whole["post_norm"]["scale"], 1e-6)
    mixed, ref_route = reference.experts(h, a, whole["experts"], uncut)
    total, rows = jnp.zeros_like(x), 0.0
    for rank in range(4):
        held = slice(4 * rank, 4 * rank + 4)
        mine = {**whole, "experts": {
            k: (v if k == "router" else v[held]) for k, v in whole["experts"].items()}}
        out, route = jax.jit(lambda p, r=rank: layer_of(r, index).apply({"params": p}, x, seg))(mine)
        assert np.array_equal(np.sort(route["choice"], -1), np.sort(ref_route["choice"], -1))
        total = total + (out - after)
        rows += float(route["stats"]["rows"])
    close(after + total, after + mixed, 2e-4)
    assert float(jnp.abs(total).max()) > 0.1 and rows == B * T * 3
    all_held, _ = layer_of(0, index, chips=1).apply({"params": whole}, x, seg)
    close(all_held, after + mixed, 2e-4)


def test_acting_step_by_step_equals_the_unroll_past_the_window(family, actor, system):
    """``family.act`` over per-layer rings (the global layer's of ``act_ctx``
    slots, a window layer's of 8 whatever ``act_ctx`` is, rotated keys in
    them) with the worker's zeroing at episode starts: an episode of 21 steps,
    more than two windows, after one of 11."""
    batch = make_batch(9, firsts=(0, 11))
    _, logits, _ = system(actor, batch)
    assert family.carry_widths == (0, (T + 3 * WINDOW) * 2 * 2 * 32 + 1)
    h = jnp.zeros((B, 0))
    c = jnp.zeros((B, family.carry_widths[1]))
    act = jax.jit(family.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            c = jnp.zeros_like(c)
        _, step_logits, _, h, c = act({"actor": actor}, jnp.asarray(batch["obs"][:, t]), h, c,
                                      jax.random.key(t))
        close(step_logits, logits[:, t], 2e-5)
    assert float(c[0, -1]) == T - 11 and h.shape == (B, 0)


@pytest.mark.parametrize("algo", ["PPO", "IMPALA", "V-MPO"])
def test_each_on_policy_algorithm_runs_one_update(algo, monkeypatch):
    cfg = config(algo=algo, learn_diag=True, update_guard=True)
    eager = ModelFamily.init_params
    monkeypatch.setattr(ModelFamily, "init_params", lambda self, key, seq_len=2: jax.jit(
        lambda k: eager(self, k, seq_len))(key))
    fam, state, step = get_algo(algo).build(cfg, jax.random.key(0))
    lay = BatchLayout.from_config(cfg)
    assert (lay.hx, lay.cx) == (1, 1) and not fam.store_carry
    before = jax.device_get(state.params["actor"])
    state, metrics = jax.jit(step)(state, Batch.from_mapping(make_batch(13)), jax.random.key(1))
    assert np.isfinite(float(metrics["loss"])) and float(metrics["nonfinite-updates"]) == 0
    if algo == "PPO":  # the algorithm whose diagnostics carry the families' counters
        assert float(metrics["diag"]["scalars"]["moe-rows"]) > 0
    moved = jax.tree.map(lambda a, b: float(np.abs(a - np.asarray(b)).max()),
                         before, state.params["actor"])
    assert all(delta > 0 for delta in jax.tree.leaves(moved))


def test_what_the_family_refuses():
    with pytest.raises(AssertionError, match="on-policy"):
        config(algo="SAC")
    with pytest.raises(AssertionError, match="needs arch"):
        config(arch=None)
    with pytest.raises(AssertionError, match="lacks"):
        config(arch={k: v for k, v in ARCH.items() if k != "sliding_window_size"})
    with pytest.raises(AssertionError, match="rope_layout"):
        config(arch={**ARCH, "rope_layout": [0, 1, 1]})  # three entries for four layers
    with pytest.raises(AssertionError, match="sliding_window_layout"):
        config(arch={**ARCH, "sliding_window_layout": [0, 2, 1, 1]})
    with pytest.raises(AssertionError, match="published 16"):
        config(arch={**ARCH, "moe_num_primary_experts": 8})
    with pytest.raises(AssertionError, match="rank"):
        config(arch={**ARCH, "expert_parallel": {**SHARE, "rank": 4}})
    with pytest.raises(AssertionError, match="scaling"):
        config(arch={**ARCH, "rope_scaling": {"type": "yarn"}})
    with pytest.raises(AssertionError, match="softmax"):
        config(arch={**ARCH, "moe_primary_router_apply_softmax": False})
    with pytest.raises(AssertionError, match="smallthinker"):
        Config.from_dict({"model": "lstm", "arch": ARCH})
    with pytest.raises(AssertionError, match="sequence-parallel"):
        config(mesh_seq=2)


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
    assert {"attn_full", "attn_window", "attn_global", "attn_rope", "moe_experts"} <= paths
    assert "moe_gmm_pallas" not in paths  # a CPU: ragged_dot
    text = lowered.as_text(debug_info=True)
    for scope in ("/moe/", "moe_route/", "moe_dispatch/", "moe_combine/", "opt_update",
                  "attn_window/attention/attn_rope", "attn_global/attention"):
        assert scope in text, scope
    assert "moe_shared" not in text and "attn_global/attention/attn_rope" not in text
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    assert {"moe_experts", "moe_gmm_pallas"} <= set(program_paths(lower())["paths"])


# ------------------------------------------------------------ the window mask
def brute_mask(fir, window):
    """(T, T) bool by the definition, a pair at a time."""
    n = len(fir)
    episode = np.cumsum(fir)
    return np.array([[episode[q] == episode[k] and 0 <= q - k < window for k in range(n)]
                     for q in range(n)])


@pytest.mark.parametrize("seams", [(), (5,), (3, 20), (9, 10, 30)],
                         ids=["none", "inside-the-first-window", "inside-and-outside", "in-a-row"])
def test_the_window_mask_against_a_mask_made_pair_by_pair(seams):
    rng = np.random.default_rng(31)
    q, k, v = (jnp.asarray(rng.standard_normal((1, T, 2, 16)), jnp.float32) for _ in range(3))
    fir = np.zeros(T, bool)
    fir[list(seams)] = True
    seg = jnp.cumsum(jnp.asarray(fir, jnp.int32))[None]
    pos = jnp.arange(T, dtype=jnp.int32)[None]
    got = full_attention(q, k, v, pos, seg, causal=True, window=WINDOW)
    mask = brute_mask(fir, WINDOW)
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    scores = np.where(mask, scores, -np.inf)
    w = np.exp(scores - scores.max(-1, keepdims=True))
    want = np.einsum("bhqk,bkhd->bqhd", w / w.sum(-1, keepdims=True), v)
    close(got, want, 1e-5)
    assert mask.sum() < brute_mask(fir, T).sum()  # the window does cut something
    whole = full_attention(q, k, v, pos, seg, causal=True)
    close(full_attention(q, k, v, pos, seg, causal=True, window=T), whole, 0)


@pytest.mark.parametrize("window", [None, WINDOW], ids=["global", "window"])
def test_the_pair_counters_against_a_count(window):
    fir = np.zeros((2, T), bool)
    fir[0, [0, 5, 6, 29]] = True
    fir[1, [17]] = True
    seg = jnp.cumsum(jnp.asarray(fir, jnp.int32), axis=1)
    want = sum(brute_mask(row, window or T).sum() for row in fir)
    assert float(kept_pairs(seg, window)) == want


def test_the_pair_counters_reach_the_diagnostics(actor, system):
    from tpu_rl.obs import learn

    batch = make_batch(16, firsts=(13,))
    routes = system(actor, batch)[2]
    scalars = learn.attention_scalars(routes)
    fir = batch["is_fir"][..., 0] > 0
    per_global = sum(brute_mask(row, T).sum() for row in fir)
    per_window = sum(brute_mask(row, WINDOW).sum() for row in fir)
    assert float(scalars["attn-pairs-global"]) == per_global  # one global layer
    assert float(scalars["attn-pairs-window"]) == 3 * per_window  # three window layers
    assert 0.5 < per_window / per_global < 0.9  # the window cuts pairs on this batch
    # beside the routing, not in it: the routing's counters are the expert block's alone
    assert set(scalars) == {
        f"attn-{what}-{kind}" for what in ("pairs", "tiles-run", "tiles-band", "bwd-steps")
        for kind in ("global", "window")}
    assert not any(key.startswith("attn") for key in learn.route_scalars(routes))
    assert learn.attention_scalars([{"stats": {}}]) == {}  # a family that counts no pairs


@pytest.mark.parametrize("kind,layers", [("global", 1), ("window", 3)])
def test_the_tile_counters_reach_the_diagnostics(actor, system, kind, layers):
    """At T 32 the kernels' grid is one tile (edge gcd(1024, 32)): under the
    blocks the traced masks are taken from, so every band tile runs and the layer
    hands back rows x 1 for both counters, summed over the layers of its kind."""
    from tpu_rl.obs import learn

    routes = system(actor, make_batch(16, firsts=(13,)))[2]
    assert [set(r["attn-tiles-run"]) for r in routes] == [{"global"}, *[{"window"}] * 3]
    scalars = learn.attention_scalars(routes)
    assert float(scalars[f"attn-tiles-run-{kind}"]) == layers * B
    assert float(scalars[f"attn-tiles-band-{kind}"]) == layers * B
    assert float(scalars[f"attn-bwd-steps-{kind}"]) == layers * B  # a grid of one tile


@pytest.mark.parametrize("window", [None, 12], ids=["global", "window"])
def test_the_tile_counters_fold_like_the_layers_own_counts(window):
    """``attention_scalars`` over four layers' records against the numpy count
    of one layer's tiles at an edge small enough for a 8 x 8 grid: the sum
    over the layers of a kind, run and band apart."""
    from tpu_rl.obs import learn
    from tpu_rl.parallel.sequence import attention_tiles, band_tiles, seam_empty_tiles

    edge = 4
    fir = np.zeros((2, T), np.int32)
    fir[:, 0] = 1
    fir[0, [9, 20]] = 1
    fir[1, [16]] = 1
    seg = np.cumsum(fir, axis=1).astype(np.int32)
    band = band_tiles(T, edge, window)
    want_run = sum(int((band & ~e).sum()) for e in seam_empty_tiles(seg, edge))
    run, total, steps = attention_tiles(jnp.asarray(seg), window, edge)
    assert (float(run), float(total)) == (want_run, 2 * band.sum()) and want_run < 2 * band.sum()
    assert float(steps) == 2 * band.sum()  # an 8 x 8 grid: the backward walks the band
    kind = "global" if window is None else "window"
    layer = {"attn-pairs": {kind: 1.0}, "attn-tiles-run": {kind: run}, "attn-tiles-band": {kind: total},
             "attn-bwd-steps": {kind: steps}}
    other = {"attn-tiles-run": {"other": jnp.float32(5)}, "attn-tiles-band": {"other": jnp.float32(7)}}
    scalars = learn.attention_scalars([layer, other, layer, layer])
    assert float(scalars[f"attn-tiles-run-{kind}"]) == 3 * want_run
    assert float(scalars[f"attn-tiles-band-{kind}"]) == 3 * 2 * band.sum()
    assert float(scalars[f"attn-bwd-steps-{kind}"]) == 3 * 2 * band.sum()
    assert (float(scalars["attn-tiles-run-other"]), float(scalars["attn-tiles-band-other"])) == (5, 7)
    assert float(scalars[f"attn-pairs-{kind}"]) == 3.0


# --------------------------------------------------------------- the rotation
def test_shifting_an_episodes_positions_changes_no_score():
    """RoPE enters a score through ``q_pos - k_pos`` alone: with every
    position of an episode moved by a constant (the step's index in the
    window against its index in the episode) attention's output is the same;
    and the rotation is the published rotate-half one."""
    rng = np.random.default_rng(40)
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, 2, 32)), jnp.float32) for _ in range(3))
    fir = np.zeros((B, T), bool)
    fir[:, 13] = True
    seg = jnp.cumsum(jnp.asarray(fir, jnp.int32), axis=1)
    index = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    in_episode = jnp.where(index >= 13, index - 13, index)
    theta = 1.5e6

    def attend(pos):
        return full_attention(rope(q, pos, theta), rope(k, pos, theta), v, index, seg,
                              causal=True, window=WINDOW)

    close(attend(index), attend(in_episode), 1e-5)
    assert float(jnp.abs(attend(index) - full_attention(q, k, v, index, seg, window=WINDOW)).max()) > 0.05
    close(rope(q, index, theta), reference.rotary(q, index, theta), 1e-5)
    close(rope(q, jnp.zeros_like(index), theta), q, 0)


# ----------------------------------------------------------------- the router
def test_the_softmax_router_is_the_softmax_over_all_renormalised_over_the_chosen():
    rng = np.random.default_rng(50)
    u = jnp.asarray(rng.standard_normal((40, 64)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32) / 8
    choice, weight = moe.route(u, kernel, None, 6, 1.0, "softmax")
    every = np.asarray(jax.nn.softmax(jnp.dot(u, kernel, precision="highest"), axis=-1))
    assert np.array_equal(np.sort(choice, -1), np.sort(np.argsort(-every, -1)[:, :6], -1))
    chosen = np.take_along_axis(every, np.asarray(choice), -1)
    close(weight, chosen / chosen.sum(-1, keepdims=True), 1e-6)
    close(weight.sum(-1), np.ones(40), 1e-6)
    # the choice carries no gradient, the chosen logits do
    grad = jax.grad(lambda w: jnp.sum(moe.route(u, w, None, 6, 1.0, "softmax")[1][:, 0]))(kernel)
    assert float(jnp.abs(grad).max()) > 0


# ------------------------------------------------------------- the gated walk
N, D, F, HELD, FIRST, K = 700, 64, 48, 4, 4, 3


def gated_case(seed: int):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    choice = jnp.asarray(np.stack([rng.permutation(16)[:K] for _ in range(N)]), jnp.int32)
    weight = jax.nn.softmax(jnp.asarray(rng.standard_normal((N, K)), jnp.float32), axis=-1)
    leaves = [jnp.asarray(rng.standard_normal(s), jnp.float32) / 8
              for s in ((HELD, D, F), (HELD, D, F), (HELD, F, D))]
    return u, choice, weight, leaves


@pytest.mark.parametrize("kernel", [(False, False), (True, True)], ids=["ragged_dot", "pallas"])
@pytest.mark.parametrize("chunk", [None, 256], ids=["one-trip", "several-trips"])
def test_the_gated_walk_against_the_dense_form(kernel, chunk):
    """Output and all five gradients: the tokens', the weights' and the three
    leaves' of ``W_out (relu(W_gate u) * W_in u)``."""
    u, choice, weight, (w_gate, w_in, w_out) = gated_case(60)
    probe = jnp.asarray(np.random.default_rng(61).standard_normal((N, D)), jnp.float32)
    held_rows = int(((choice >= FIRST) & (choice < FIRST + HELD)).sum())
    trips = -(-held_rows // (chunk or moe.chunk_rows(N, K, HELD, HELD)))
    assert (trips == 1) if chunk is None else (trips >= 2)

    def walked(u, weight, w_gate, w_in, w_out):
        return jnp.sum(probe * moe.routed_experts(
            u, choice, weight, w_in, w_out, FIRST, kernel=kernel, chunk=chunk, w_gate=w_gate,
            form="reglu"))

    def dense(u, weight, w_gate, w_in, w_out):
        return jnp.sum(probe * moe.routed_experts_dense(
            u, choice, weight, w_in, w_out, FIRST, w_gate=w_gate, form="reglu"))

    args = (u, weight, w_gate, w_in, w_out)
    got, got_grads = jax.jit(jax.value_and_grad(walked, argnums=range(5)))(*args)
    want, want_grads = jax.jit(jax.value_and_grad(dense, argnums=range(5)))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for name, g, w in zip(("u", "weight", "w_gate", "w_in", "w_out"), got_grads, want_grads):
        assert float(jnp.abs(w).max()) > 0, name
        close(g, w, 1e-4 * (1.0 + float(jnp.abs(w).max())))
    # by the definition, an assignment at a time, at a few tokens
    y = moe.routed_experts(u, choice, weight, w_in, w_out, FIRST, kernel=kernel, chunk=chunk,
                           w_gate=w_gate, form="reglu")
    for n in (0, 7, N - 1):
        want_row = sum(
            float(weight[n, j]) * (np.maximum(u[n] @ w_gate[e - FIRST], 0) * (u[n] @ w_in[e - FIRST]))
            @ w_out[e - FIRST]
            for j, e in enumerate(np.asarray(choice[n])) if FIRST <= e < FIRST + HELD)
        close(y[n], want_row + np.zeros(D), 1e-4)


# -------------------------------------- the relu2 walk, as the parent had it
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def parent_walk(u, weight, w_in, w_out, order, sizes, chunk, kernel):
    """``ops/moe._walk`` as PR 31 left it (one expert form, two leaves),
    statement for statement: the oracle of bit-identity below."""
    n, k = weight.shape
    x, flat = u.astype(w_in.dtype), weight.reshape(-1)

    def trip(c, y):
        at, part, live = moe._trip(c, order, sizes, chunk)
        tok = at // k
        rows = x[tok]
        hidden = jnp.square(jax.nn.relu(moe.grouped_matmul(rows, w_in, part, kernel)))
        out = moe.grouped_matmul(hidden.astype(x.dtype), w_out, part, kernel)
        add = jnp.where(live[:, None], flat[at][:, None] * out.astype(jnp.float32), 0.0)
        return y.at[jnp.where(live, tok, n)].add(add, mode="drop")

    return jax.lax.fori_loop(
        0, moe._trips(sizes, chunk), trip, jnp.zeros((n, u.shape[1]), jnp.float32))


def parent_walk_fwd(u, weight, w_in, w_out, order, sizes, chunk, kernel):
    return parent_walk(u, weight, w_in, w_out, order, sizes, chunk, kernel), (
        u, weight, w_in, w_out, order, sizes)


def parent_walk_bwd(chunk, kernel, residual, dy):
    u, weight, w_in, w_out, order, sizes = residual
    n, k = weight.shape
    x, g, flat = u.astype(w_in.dtype), dy.astype(w_in.dtype), weight.reshape(-1)

    def trip(c, carry):
        d_x, d_flat, d_in, d_out = carry
        at, part, live = moe._trip(c, order, sizes, chunk)
        tok = at // k
        rows = x[tok]
        dy_rows, wt = g[tok], flat[at][:, None]
        pre = moe.grouped_matmul(rows, w_in, part, kernel)
        hidden = jnp.square(jax.nn.relu(pre))
        t, d_out = moe.grouped_grads(
            (wt * hidden).astype(x.dtype), w_out, part, dy_rows, d_out, kernel)
        t = t.astype(jnp.float32)
        p = (wt * t * 2.0 * jax.nn.relu(pre)).astype(x.dtype)
        d_rows, d_in = moe.grouped_grads(rows, w_in, part, p, d_in, kernel)
        d_wt = jnp.sum(hidden.astype(jnp.float32) * t, axis=-1)
        d_flat = d_flat.at[jnp.where(live, at, n * k + jnp.arange(chunk))].set(
            d_wt, mode="drop", unique_indices=True)
        d_x = d_x.at[jnp.where(live, tok, n)].add(
            jnp.where(live[:, None], d_rows.astype(jnp.float32), 0.0), mode="drop")
        return d_x, d_flat, d_in, d_out

    d_x, d_flat, d_in, d_out = jax.lax.fori_loop(0, moe._trips(sizes, chunk), trip, (
        jnp.zeros(u.shape, jnp.float32), jnp.zeros(n * k, jnp.float32),
        jnp.zeros(w_in.shape, jnp.float32), jnp.zeros(w_out.shape, jnp.float32)))
    return (d_x.astype(u.dtype), d_flat.reshape(n, k).astype(weight.dtype),
            d_in.astype(w_in.dtype), d_out.astype(w_out.dtype), None, None)


parent_walk.defvjp(parent_walk_fwd, parent_walk_bwd)


@pytest.mark.parametrize("kernel", [(False, False), (True, True)], ids=["ragged_dot", "pallas"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_the_relu2_walk_is_the_parents_bit_for_bit(kernel, dtype):
    """The walk that now takes its expert form as a parameter, at the form
    nemotron_h runs, against the parent's walk: the same bits in the output
    and in all four gradients, over several trips."""
    u, choice, weight, (_, w_in, w_out) = gated_case(70)
    chunk = 256
    probe = jnp.asarray(np.random.default_rng(71).standard_normal((N, D)), jnp.float32)

    def now(u, weight, w_in, w_out):
        y = moe.routed_experts(u, choice, weight, w_in, w_out, FIRST, dtype, kernel, chunk)
        return jnp.sum(probe * y), y

    def then(u, weight, w_in, w_out):
        local = (choice - FIRST).reshape(-1)
        key = jnp.where((local >= 0) & (local < HELD), local, HELD)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        sizes = jnp.sum(key[:, None] == jnp.arange(HELD), axis=0).astype(jnp.int32)
        order = jnp.pad(order, (0, (-N * K) % chunk))
        y = parent_walk(u, weight, w_in.astype(dtype), w_out.astype(dtype), order, sizes, chunk, kernel)
        return jnp.sum(probe * y), y

    args = (u, weight, w_in, w_out)
    (_, y), grads = jax.jit(jax.value_and_grad(now, argnums=range(4), has_aux=True))(*args)
    (_, y0), grads0 = jax.jit(jax.value_and_grad(then, argnums=range(4), has_aux=True))(*args)
    assert float(jnp.abs(y).max()) > 0.1 and np.array_equal(y, y0)
    for g, g0 in zip(grads, grads0):
        assert float(jnp.abs(g0).max()) > 0 and np.array_equal(g, g0)
