"""The nemotron_h family (``tpu_rl/models/nemotron_h.py``) at tiny widths on the
CPU against the benchmark's plain reference
(``benchmarks/reference/nemotron_h.py``: the recurrence step by step, dense
masked attention, the held experts as a loop under a mask): outputs, the PPO
loss and every gradient with identical choices asserted; bfloat16 through the
routed comparison; the shares of a small deployment adding up to the uncut
layer; forced imbalance; the correction bias; seams, acting, the carry,
rematerialisation; one update of each on-policy algorithm; what the family
refuses; the paths the update program names. 16 routed experts over 4 ranks
(rank 1 holds experts 4-7), 3 chosen per token, 2 B/C groups, chunks of 8."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import losses as ref_losses
from benchmarks.reference import nemotron_h as reference
from test_granite_hybrid import close, make_batch
from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.algos.ppo import make_train_step, policy_outputs, policy_outputs_routed
from tpu_rl.algos.registry import get_algo
from tpu_rl.config import Config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.models import cells
from tpu_rl.models.families import ModelFamily, build_family
from tpu_rl.models.layers import ExpertBlock
from tpu_rl.models.nemotron_h import NemotronHActorCritic
from tpu_rl.types import Batch

SHARE = dict(published_n_routed_experts=16, chips=4, rank=1)
ARCH = dict(
    hidden_size=64, hybrid_override_pattern="MEM*E", layer_norm_epsilon=1e-5,
    mamba_num_heads=8, mamba_head_dim=16, ssm_state_size=16, n_groups=2, conv_kernel=4,
    chunk_size=8, use_conv_bias=True, mamba_proj_bias=False, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, attention_bias=False, n_routed_experts=4,
    num_experts_per_tok=3, moe_intermediate_size=48, moe_shared_expert_intermediate_size=96,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=2.5, expert_parallel=SHARE,
)
T, B, OBS, ACTIONS = 32, 2, 6, 3
PARAMS = dict(algo="PPO", model="nemotron_h", arch=ARCH, obs_shape=(OBS,),
              action_space=ACTIONS, seq_len=T, batch_size=B)


def config(**kw) -> Config:
    return Config.from_dict({**PARAMS, **kw})


@pytest.fixture(params=["auto", "interpret"], ids=["jnp", "pallas"])
def kernel_form(request, monkeypatch):
    """The form of the scan and of the grouped matmul a test's programs are
    traced in (read while tracing)."""
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
    """(value, logits, routes) of the reference, on its own choice or on
    the choices it is handed."""
    def run(p, b, choices=None):
        logits, value, routes = reference.forward_routed(p, b, PARAMS, choices)
        return value, logits, routes

    return jax.jit(run)


def same_choices(routes, ref_routes) -> bool:
    return all(np.array_equal(np.sort(a["choice"], -1), np.sort(b["choice"], -1))
               for a, b in zip(routes, ref_routes))


def test_outputs_and_choices_match_the_reference_in_float32(actor, system, plain):
    batch = make_batch(2)
    assert batch["is_fir"].sum() >= 4
    value, logits, routes = system(actor, batch)
    ref_value, ref_logits, ref_routes = plain(actor, batch)
    assert len(routes) == 2 and routes[0]["choice"].shape == (B, T, 3)
    assert same_choices(routes, ref_routes)
    close(logits, ref_logits, 1e-4)
    close(value, ref_value, 1e-4)
    held = float(routes[0]["stats"]["held-share"])
    assert 0.05 < held < 0.6 and float(routes[0]["stats"]["rows"]) == round(held * B * T * 3)


def test_bfloat16_matches_the_reference_on_the_systems_choices(actor, plain):
    """The routed comparison: bf16 states differ from the reference's by
    rounding, so near a tie the two may choose differently, and a different
    expert is an O(1) change. Given the system's choices, the arithmetic
    agrees to bf16 rounding; and wherever the system's set is not the
    reference's own, the reference's margin at that token is small."""
    fam = build_family(config(compute_dtype="bfloat16"))
    batch = make_batch(3)
    value, logits, routes = jax.jit(
        lambda p, b: policy_outputs_routed(fam, {"actor": p}, Batch.from_mapping(b))[2:])(actor, batch)
    choices = [r["choice"] for r in routes]
    ref_value, ref_logits, ref_routes = plain(actor, batch, choices)
    for got, want in ((logits, ref_logits), (value, ref_value)):
        close(got, want, 3e-2 * float(np.abs(want).max()))
        assert float(np.abs(np.asarray(got) - np.asarray(want)).max()) > 1e-6
    for mine, theirs in zip(routes, ref_routes):
        differ = (np.sort(mine["choice"], -1) != np.sort(theirs["choice"], -1)).any(-1)
        assert differ.mean() < 0.2
        assert (np.asarray(theirs["margin"])[differ] < 2e-2).all()


def ref_ppo_loss(p, batch, cfg, forward=lambda p, b: reference.forward(p, b, PARAMS)):
    """``benchmarks/reference/losses.ppo`` in jax.numpy, so it has a gradient."""
    logits, value = forward(p, batch)
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


def test_ppo_loss_and_every_gradient_match_the_reference(family, actor, system, plain, kernel_form):
    """The train step's own loss and ``jax.grad`` of it against the reference
    forward under the reference loss, leaf by leaf: the router's weights get a
    gradient through the chosen scores, its correction bias gets none, on
    either side."""
    cfg = config()
    batch = make_batch(4)
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    assert same_choices(system(actor, batch)[2], plain(actor, batch)[2])
    params = {"actor": actor}
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=rmsprop(cfg).init(params))
    step = make_train_step(config(learn_diag=True), family)
    _, metrics = jax.jit(step)(state, Batch.from_mapping(jb), jax.random.key(1))
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(lambda p: ref_ppo_loss(p, jb, cfg)))(actor)
    ref_value, ref_logits, _ = plain(actor, batch)
    numpy_loss = ref_losses.ppo(ref_logits, ref_value, batch, PARAMS)["loss"]
    assert abs(float(ref_loss) - numpy_loss) < 1e-5
    assert abs(float(metrics["loss"]) - numpy_loss) < 1e-5
    scalars = metrics["diag"]["scalars"]
    assert float(scalars["moe-rows"]) == sum(
        float(r["stats"]["rows"]) for r in system(actor, batch)[2])
    assert 1.0 <= float(scalars["moe-rows-max-over-mean"]) <= 4.0
    assert 0.0 < float(scalars["moe-held-share"]) < 1.0 > float(scalars["moe-no-held-share"])
    assert float(scalars["moe-chunks"]) == 1.0  # every layer's held rows fit one chunk

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
    for (path, got), want in zip(jax.tree.leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(got - want).max()) <= 2e-4 * scale, name
        if "router_bias" in name:
            assert not np.asarray(got).any() and not np.asarray(want).any()
        elif "router" in name:
            assert float(jnp.abs(got).max()) > 0


def block(rank: int, chips: int = 4, total: int = 16) -> ExpertBlock:
    held = total // chips
    return ExpertBlock(hidden=64, n_experts=total, held=held, first=rank * held, top_k=3,
                       expert_width=48, shared_width=96, scale=2.5)


def test_the_shares_add_up_to_the_uncut_layer(kernel_form):
    """Over all four ranks of the deployment, the routed parts plus the shared
    expert counted once equal the uncut reference's layer: every rank routes
    over all 16 experts with the same router and computes its own four."""
    rng = np.random.default_rng(20)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    whole = jax.jit(lambda k: block(0, chips=1).init(k, u))(jax.random.key(2))["params"]
    whole = jax.tree.map(lambda x: x + 0.1 * jnp.asarray(rng.standard_normal(x.shape), x.dtype), whole)
    uncut_arch = {**ARCH, "n_routed_experts": 16, "expert_parallel": None}
    uncut_arch.pop("expert_parallel")
    want, ref_route = reference.experts(u, whole, uncut_arch)
    shared = reference.matmul(reference.relu2(reference.matmul(u, whole["shared_in"]["kernel"])),
                              whole["shared_out"]["kernel"])
    total, rows = jnp.zeros_like(want), 0.0
    for rank in range(4):
        mine = {**whole, "w_in": whole["w_in"][4 * rank: 4 * rank + 4],
                "w_out": whole["w_out"][4 * rank: 4 * rank + 4]}
        part, route = jax.jit(lambda p, r=rank: block(r).apply({"params": p}, u))(mine)
        assert np.array_equal(np.sort(route["choice"], -1), np.sort(ref_route["choice"], -1))
        # the reference, given the same share, computes the same part
        shared_arch = {**ARCH, "expert_parallel": {**SHARE, "rank": rank}}
        close(part, reference.experts(u, mine, shared_arch)[0], 1e-4)
        total = total + (part - shared)
        rows += float(route["stats"]["rows"])
    close(total + shared, want, 2e-4)
    assert rows == B * T * 3  # every assignment is computed by exactly one rank
    all_held, _ = block(0, chips=1).apply({"params": whole}, u)
    close(all_held, want, 1e-4)


@pytest.mark.parametrize("bias,expect", [(+10.0, "every token on one held expert"),
                                         (-10.0, "no token on any held expert")],
                         ids=["all-on-one", "none-held"])
def test_forced_imbalance_drops_nothing(actor, system, plain, bias, expect):
    """The correction bias moves the choice: +10 on held expert 5 sends every
    token there, -10 on experts 4-7 sends none to this rank. The model's
    outputs still equal the reference's, which loops densely."""
    tilted = jax.tree_util.tree_map_with_path(
        lambda path, x: (x.at[5].set(bias) if bias > 0 else x.at[4:8].set(bias))
        if "router_bias" in jax.tree_util.keystr(path) else x, actor)
    batch = make_batch(21)
    value, logits, routes = system(tilted, batch)
    ref_value, ref_logits, ref_routes = plain(tilted, batch)
    assert same_choices(routes, ref_routes)
    close(logits, ref_logits, 1e-4)
    close(value, ref_value, 1e-4)
    for r in routes:
        stats = {k: float(v) for k, v in r["stats"].items()}
        if bias > 0:
            assert (np.asarray(r["choice"]) == 5).any(-1).all(), expect
            assert stats["rows-max"] == B * T and stats["no-held-share"] == 0.0
        else:
            assert stats["rows"] == 0.0 and stats["no-held-share"] == 1.0, expect
    untilted = system(actor, batch)
    assert float(np.abs(np.asarray(untilted[1]) - np.asarray(logits)).max()) > 1e-4


@pytest.fixture(scope="module")
def weighted_by_form(family):
    def f(p, batch, weights):
        _, _, value, logits = policy_outputs(family, {"actor": p}, Batch.from_mapping(batch))
        return jnp.sum(weights * (value + logits.sum(-1, keepdims=True))), (value, logits)

    return {form: jax.jit(jax.value_and_grad(lambda *a: f(*a), has_aux=True))
            for form in ("auto", "interpret")}


@pytest.mark.parametrize("seams", [(8,), (13, 14), (0, 19)],
                         ids=["chunk-edge", "two-in-a-row", "t0-and-later"])
def test_a_seam_cuts_state_taps_and_attention(actor, weighted_by_form, kernel_form, seams):
    """What follows the last seam equals a run of that suffix alone, outputs
    and gradients: the expert layers are per token and carry nothing across."""
    weighted = weighted_by_form[kernel_form]
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
    scale = max(float(jnp.abs(g).max()) for g in jax.tree.leaves(grads_a))
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_a)):
        assert np.isfinite(np.asarray(got)).all()
        close(got, want, 1e-4 * scale)


def test_acting_step_by_step_equals_the_unroll(family, actor, system):
    """``family.act`` (the held experts densely under a mask) with the
    worker's zeroing at episode starts, across a seam inside a chunk."""
    batch = make_batch(9, firsts=(0, 13))
    _, logits, _ = system(actor, batch)
    h = jnp.zeros((B, family.carry_widths[0]))
    c = jnp.zeros((B, family.carry_widths[1]))
    act = jax.jit(family.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            h, c = jnp.zeros_like(h), jnp.zeros_like(c)
        _, step_logits, _, h, c = act({"actor": actor}, jnp.asarray(batch["obs"][:, t]), h, c,
                                      jax.random.key(t))
        close(step_logits, logits[:, t], 2e-5)
    assert float(c[0, -1]) == T - 13
    assert family.carry_widths == (2 * (8 * 16 * 16 + 3 * (128 + 2 * 2 * 16)), 2 * T * 2 * 32 + 1)


def test_a_window_starts_from_the_state_it_is_handed(family, actor, kernel_form):
    """A window unrolled in two halves, the second from the carry the first
    returned (attention layers left out: their context is not carried),
    equals the window unrolled whole; and the reference from the same carry."""
    arch = {**ARCH, "hybrid_override_pattern": "MEME"}
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
    per = carry[0].reshape(B, 2, -1)
    n_state = 8 * 16 * 16
    pairs = [(per[:, i, :n_state].reshape(B, 8, 16, 16), per[:, i, n_state:].reshape(B, 3, -1))
             for i in range(2)]
    half = {k: v[:, 16:] for k, v in batch.items()}
    ref_second, _ = reference.forward(params, half, {**PARAMS, "arch": arch}, carry0=pairs)
    close(second, ref_second, 1e-4)


def test_rematerialisation_does_not_change_the_gradients(family, actor, kernel_form):
    batch = make_batch(12)
    obs, fir = jnp.asarray(batch["obs"]), jnp.asarray(batch["is_fir"])
    carry = (jnp.zeros((B, 1)), jnp.zeros((B, 1)))
    plain_model = NemotronHActorCritic(n_actions=ACTIONS, arch=ARCH, act_ctx=T, remat=False)
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
                 arch={**ARCH, "hybrid_override_pattern": "ME*"})
    eager = ModelFamily.init_params
    monkeypatch.setattr(ModelFamily, "init_params", lambda self, key, seq_len=2: jax.jit(
        lambda k: eager(self, k, seq_len))(key))
    fam, state, step = get_algo(algo).build(cfg, jax.random.key(0))
    lay = BatchLayout.from_config(cfg)
    assert (lay.hx, lay.cx) == (1, 1) and not fam.store_carry
    before = jax.device_get(state.params["actor"])
    state, metrics = jax.jit(step)(state, Batch.from_mapping(make_batch(13)), jax.random.key(1))
    assert np.isfinite(float(metrics["loss"])) and float(metrics["nonfinite-updates"]) == 0
    assert float(metrics["diag"]["scalars"]["moe-rows"]) > 0
    assert float(metrics["diag"]["scalars"]["moe-chunks"]) == 1.0
    moved = jax.tree_util.tree_map_with_path(
        lambda path, a, b: (jax.tree_util.keystr(path), float(np.abs(a - np.asarray(b)).max())),
        before, state.params["actor"])
    for name, delta in jax.tree.leaves(moved, is_leaf=lambda x: isinstance(x, tuple)):
        # every leaf has a gradient but the correction bias, which the optimizer leaves
        assert (delta == 0) if "router_bias" in name else (delta > 0), name


def test_the_diagnostics_publish_the_routing_counters():
    """``diag["scalars"]`` through the accumulator, ``derive``, ``publish`` and
    ``learn_record``: the per-update means that ``learn.jsonl`` and the
    ``learner-diag-moe-*`` gauges carry, the walk's trips among them."""
    from tpu_rl.obs import learn
    from tpu_rl.obs.registry import MetricsRegistry

    routes = [{"stats": {"rows": jnp.float32(60), "rows-max": jnp.float32(30),
                         "rows-mean": jnp.float32(15), "held-share": jnp.float32(0.25),
                         "no-held-share": jnp.float32(0.5), "chunks": jnp.float32(2)}},
              {"stats": {"rows": jnp.float32(40), "rows-max": jnp.float32(10),
                         "rows-mean": jnp.float32(10), "held-share": jnp.float32(0.15),
                         "no-held-share": jnp.float32(0.7), "chunks": jnp.float32(1)}}]
    assert learn.route_scalars([]) == {}  # a family without expert layers: no key at all
    assert float(learn.route_scalars(routes)["moe-chunks"]) == 1.5  # the mean per expert layer
    diag = {"rows": {"ent": jnp.ones((2,))}, "scalars": learn.route_scalars(routes)}
    acc = learn.DiagAccumulator()
    acc.add(diag, jnp.zeros((2,)))
    acc.add(diag, jnp.zeros((2,)))
    doc = acc.drain(4)
    glob = doc["global"]
    assert glob["moe-rows"] == 100.0 and glob["moe-rows-max-over-mean"] == pytest.approx(1.5)
    assert glob["moe-held-share"] == pytest.approx(0.2) and glob["moe-no-held-share"] == pytest.approx(0.6)
    assert glob["moe-chunks"] == pytest.approx(1.5)
    record = learn.learn_record(4, {"n_updates": 2, "global": glob, "buckets": {}})
    assert record["moe-rows"] == 100.0 and record["moe-chunks"] == pytest.approx(1.5)
    reg = MetricsRegistry(role="learner", pid=0, host="h")
    learn.publish(reg, doc)
    gauges = {name: value for name, _, value in reg.snapshot()["gauges"]}
    assert gauges["learner-diag-moe-chunks"] == pytest.approx(1.5)
    assert gauges["learner-diag-moe-rows"] == 100.0


@pytest.mark.parametrize("model", ["nemotron_h", "lstm"])
def test_the_trips_are_in_the_diagnostics_of_a_family_with_expert_layers_only(model, monkeypatch):
    """One update's ``diag["scalars"]`` and the ``learn.jsonl`` record made of
    it: ``moe-chunks`` beside ``moe-rows`` where the family routes, neither
    where it does not."""
    from tpu_rl.obs import learn

    if model == "nemotron_h":
        cfg = config(learn_diag=True, arch={**ARCH, "hybrid_override_pattern": "ME"})
        eager = ModelFamily.init_params
        monkeypatch.setattr(ModelFamily, "init_params", lambda self, key, seq_len=2: jax.jit(
            lambda k: eager(self, k, seq_len))(key))
    else:
        cfg = Config.from_dict(dict(
            algo="PPO", obs_shape=(OBS,), action_space=ACTIONS, batch_size=B, seq_len=T,
            hidden_size=16, learn_diag=True))
    _, state, step = get_algo("PPO").build(cfg, jax.random.key(0))
    lay = BatchLayout.from_config(cfg)
    batch = make_batch(15)
    batch["hx"] = np.zeros((B, T, lay.hx), np.float32)
    batch["cx"] = np.zeros((B, T, lay.cx), np.float32)
    _, metrics = jax.jit(step)(state, Batch.from_mapping(batch), jax.random.key(1))
    acc = learn.DiagAccumulator()
    acc.add(metrics["diag"], jnp.zeros((B,)))
    record = learn.learn_record(1, acc.drain(1))
    routed = model == "nemotron_h"
    assert ("moe-chunks" in metrics["diag"]["scalars"]) == routed
    assert ("moe-chunks" in record) == ("moe-rows" in record) == routed
    if routed:
        assert record["moe-chunks"] == 1.0 and record["moe-rows"] > 0


def test_what_the_family_refuses():
    with pytest.raises(AssertionError, match="on-policy"):
        config(algo="SAC")
    with pytest.raises(AssertionError, match="needs arch"):
        config(arch=None)
    with pytest.raises(AssertionError, match="lacks"):
        config(arch={k: v for k, v in ARCH.items() if k != "ssm_state_size"})
    with pytest.raises(AssertionError, match="only M"):
        config(arch={**ARCH, "hybrid_override_pattern": "ME-*"})  # a dense MLP layer
    with pytest.raises(AssertionError, match="published 16"):
        config(arch={**ARCH, "n_routed_experts": 8})  # 4 chips x 8 is not 16
    with pytest.raises(AssertionError, match="rank"):
        config(arch={**ARCH, "expert_parallel": {**SHARE, "rank": 4}})
    with pytest.raises(AssertionError, match="group stage"):
        config(arch={**ARCH, "n_group": 2})
    with pytest.raises(AssertionError, match="relu2"):
        config(arch={**ARCH, "mlp_hidden_act": "silu"})
    with pytest.raises(AssertionError, match="normalised"):
        config(arch={**ARCH, "norm_topk_prob": False})
    with pytest.raises(AssertionError, match="nemotron_h"):
        Config.from_dict({"model": "lstm", "arch": ARCH})
    with pytest.raises(AssertionError, match="sequence-parallel"):
        config(mesh_seq=2)
    from tpu_rl.checkpoint import resume_fingerprint

    other = config(arch={**ARCH, "expert_parallel": {**SHARE, "rank": 2}})
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
    assert {"ssd_scan", "attn_full", "moe_experts"} <= paths
    assert not {"ssd_pallas", "moe_gmm_pallas"} & paths  # a CPU: the jnp bodies
    text = lowered.as_text(debug_info=True)
    for scope in ("/moe/", "moe_route/", "moe_dispatch/", "moe_combine/", "moe_shared/", "opt_update"):
        assert scope in text, scope
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    assert {"ssd_pallas", "moe_experts", "moe_gmm_pallas"} <= set(program_paths(lower())["paths"])
