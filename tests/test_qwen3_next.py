"""The qwen3_next family (``tpu_rl/models/qwen3_next.py``) at tiny widths on
the CPU against the benchmark's plain reference
(``benchmarks/reference/qwen3_next.py``: the delta rule step by step, dense
masked attention, the held experts as a loop under a mask): the chunked rule
against the step recurrence with seams wherever a chunk can take them;
outputs, the PPO loss and every gradient with identical choices asserted; the
ranks' parts of a layer adding up to the uncut one; partial RoPE, the q/k norm,
the output gate, the zero-centred norm and the gated shared expert each
against a hand-written line; the ``swiglu`` walk against the dense form;
acting against the unroll. Three linear layers and one full layer, chunks of 8
steps in spans of 2, 16 routed experts over 4 ranks (rank 1 holds experts
4-7), 3 chosen per token."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import losses as ref_losses
from benchmarks.reference import qwen3_next as reference
from test_granite_hybrid import close, make_batch
from test_nemotron_h import ref_ppo_loss, same_choices
from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.algos.ppo import make_train_step, policy_outputs, policy_outputs_routed
from tpu_rl.algos.registry import get_algo
from tpu_rl.config import Config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.models import cells, qwen3_next
from tpu_rl.models.families import ModelFamily, build_family
from tpu_rl.models.layers import ExpertBlock, GQAttention, RMSNorm, rope
from tpu_rl.models.qwen3_next import Qwen3NextLayer
from tpu_rl.ops import gated_delta, moe
from tpu_rl.parallel.sequence import full_attention
from tpu_rl.types import Batch

SHARE = dict(published_n_routed_experts=16, chips=4, rank=1)
ARCH = dict(
    hidden_size=64, num_hidden_layers=4, full_attention_interval=4, rms_norm_eps=1e-6,
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=32, rope_theta=10000000, rope_scaling=None,
    partial_rotary_factor=0.25, moe_intermediate_size=48, shared_expert_intermediate_size=40,
    num_experts=4, num_experts_per_tok=3, norm_topk_prob=True, decoder_sparse_step=1,
    mlp_only_layers=[], expert_parallel=SHARE,
)
T, B, OBS, ACTIONS = 32, 2, 6, 3
CHUNK = 8
PARAMS = dict(algo="PPO", model="qwen3_next", arch=ARCH, obs_shape=(OBS,),
              action_space=ACTIONS, seq_len=T, batch_size=B)


def config(**kw) -> Config:
    return Config.from_dict({**PARAMS, **kw})


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    """Chunks of 8 steps in spans of 2: a 32-step window is two spans of two
    chunks, so every seam of a test batch falls inside some chunk."""
    before = qwen3_next.CHUNK, gated_delta.SPAN_CHUNKS
    qwen3_next.CHUNK, gated_delta.SPAN_CHUNKS = CHUNK, 2
    yield
    qwen3_next.CHUNK, gated_delta.SPAN_CHUNKS = before


@pytest.fixture(params=["auto", "interpret"], ids=["jnp", "pallas"])
def kernel_form(request, monkeypatch):
    """The form of the delta rule's scan and of the experts' products a test's
    programs are traced in (read while tracing: a test jits what it runs
    inside this fixture's scope, under a function of its own)."""
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


# ------------------------------------------- the chunked rule against the steps
def step_by_step(q, k, v, g, beta, first, state0):
    """``gated_delta_step`` over the window, the state zeroed where an episode
    starts."""
    def step(S, at):
        q_t, k_t, v_t, g_t, beta_t, first_t = at
        o, S = gated_delta.gated_delta_step(
            q_t, k_t, v_t, g_t, beta_t, jnp.where(first_t[:, None, None, None], 0.0, S))
        return S, o

    last, o = jax.lax.scan(
        step, state0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta, first)))
    return jnp.moveaxis(o, 0, 1), last


def rule_inputs(seed: int, steps: int):
    keys = jax.random.split(jax.random.key(seed), 6)
    q, k = (jax.random.normal(key, (B, steps, 2, 8)) for key in keys[:2])
    v = jax.random.normal(keys[2], (B, steps, 4, 8))
    g = -jax.nn.softplus(jax.random.normal(keys[3], (B, steps, 4)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (B, steps, 4)))
    return q, k, v, g, beta, jax.random.normal(keys[5], (B, 4, 8, 8))


SEAMS = {
    "none": (), "a-chunks-first-step": (16,), "a-chunks-last-step": (15,), "mid-chunk": (19,),
    "two-in-one-chunk": (9, 13), "the-windows-first-step": (0,), "a-spans-first-step": (16, 17),
    "every-kind": (0, 7, 8, 19, 21, 31),
}


@pytest.mark.parametrize("seams", SEAMS.values(), ids=SEAMS.keys())
def test_the_chunked_rule_equals_the_step_recurrence(seams, kernel_form):
    """Outputs, the last state and all six gradients, float32 at ``highest``:
    chunks of 8 in spans of 2 over 32 steps, the second row's seams shifted by
    three steps so that the rows differ."""
    q, k, v, g, beta, state0 = rule_inputs(3, T)
    first = np.zeros((B, T), bool)
    first[0, list(seams)] = True
    first[1, [(s + 3) % T for s in seams]] = True
    first = jnp.asarray(first)
    seg = jnp.cumsum(first.astype(jnp.int32), axis=1)
    probe = jax.random.normal(jax.random.key(9), (B, T, 4, 8))

    def loss(run):
        def f(q, k, v, g, beta, state0):
            o, last = run(q, k, v, g, beta, state0)
            return jnp.sum(probe * o) + jnp.sum(jnp.sin(last)), (o, last)
        return jax.jit(jax.value_and_grad(f, argnums=tuple(range(6)), has_aux=True))

    with jax.default_matmul_precision("highest"):
        (_, (o, last)), grads = loss(
            lambda *a: gated_delta.gated_delta_chunked(*a[:5], seg, a[5], CHUNK))(
                q, k, v, g, beta, state0)
        (_, (want_o, want_last)), want_grads = loss(
            lambda *a: step_by_step(*a[:5], first, a[5]))(q, k, v, g, beta, state0)
    close(o, want_o, 2e-6)
    close(last, want_last, 2e-6)
    for name, got, want in zip("q k v g beta state0".split(), grads, want_grads):
        scale = float(jnp.abs(want).max())
        assert float(jnp.abs(got - want).max()) <= 1e-5 * max(scale, 1.0), name
        if name != "state0" or not first[:, 0].all():
            assert scale > 0, name


@pytest.mark.parametrize("steps", [5, 21, 40], ids=["under-a-chunk", "a-ragged-span", "a-ragged-window"])
def test_the_chunked_rule_pads_a_window_that_is_no_whole_span(steps, kernel_form):
    q, k, v, g, beta, state0 = rule_inputs(4, steps)
    first = jnp.zeros((B, steps), bool).at[:, steps // 2].set(True)
    seg = jnp.cumsum(first.astype(jnp.int32), axis=1)
    with jax.default_matmul_precision("highest"):
        o, last = gated_delta.gated_delta_chunked(q, k, v, g, beta, seg, state0, CHUNK)
        want_o, want_last = step_by_step(q, k, v, g, beta, first, state0)
    assert o.shape == (B, steps, 4, 8)
    close(o, want_o, 2e-6)
    close(last, want_last, 2e-6)


def test_bf16_operands_keep_the_state_and_the_decays_in_float32(kernel_form):
    q, k, v, g, beta, state0 = rule_inputs(5, T)
    seg = jnp.zeros((B, T), jnp.int32)
    o, last = gated_delta.gated_delta_chunked(q, k, v, g, beta, seg, state0, CHUNK, jnp.bfloat16)
    want_o, want_last = gated_delta.gated_delta_chunked(q, k, v, g, beta, seg, state0, CHUNK)
    assert o.dtype == last.dtype == jnp.float32
    for got, want in ((o, want_o), (last, want_last)):
        err = float(jnp.abs(got - want).max())
        assert 1e-5 < err < 3e-2 * float(jnp.abs(want).max())


@pytest.mark.parametrize("size", [1, 8, 64])
def test_the_triangles_inverse_and_its_transpose_rule(size):
    rng = np.random.default_rng(size)
    n = jnp.tril(jnp.asarray(rng.standard_normal((3, size, size)), jnp.float32), -1) / 4
    eye = jnp.eye(size)
    with jax.default_matmul_precision("highest"):
        inverse = gated_delta._unit_lower_inverse(n)
        close(inverse @ (eye + n), jnp.broadcast_to(eye, n.shape), 2e-5)
        probe = jnp.asarray(rng.standard_normal(n.shape), jnp.float32)
        got = jax.grad(lambda n: jnp.sum(probe * gated_delta._unit_lower_inverse(n)))(n)
        want = jax.grad(lambda n: jnp.sum(probe * jnp.linalg.inv(eye + jnp.tril(n, -1))))(n)
    close(got, want, 1e-4 * max(1.0, float(jnp.abs(want).max())))


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
        # 16-wide heads and every leaf moved by 0.1: a bf16 step is a larger share here
        close(got, want, 5e-2 * float(np.abs(want).max()))
        assert float(np.abs(np.asarray(got) - np.asarray(want)).max()) > 1e-6
    worst = 0.0
    for mine, theirs in zip(routes, ref_routes):
        differ = (np.sort(mine["choice"], -1) != np.sort(theirs["choice"], -1)).any(-1)
        assert differ.mean() < 0.2
        worst = max(worst, float(np.asarray(theirs["margin"])[differ].max(initial=0.0)))
    assert worst < 0.3  # 0.185 here; 16-wide heads, every leaf moved by 0.1


def test_ppo_loss_and_every_gradient_match_the_reference(family, actor, system, plain, kernel_form):
    """The train step's own loss and ``jax.grad`` of it against the reference
    forward under the reference loss, leaf by leaf."""
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
    for leaf in ("router", "w_gate", "w_in", "w_out", "shared_gate", "shared_weight",
                 "input_norm", "post_norm"):
        assert sum(leaf in name for name in names) == 4, leaf  # in each of the four layers
    for leaf, layers in (("in_proj_qkvz", 3), ("in_proj_ba", 3), ("conv_weight", 3), ("A_log", 3),
                         ("dt_bias", 3), ("norm_scale", 3), ("q_norm", 1), ("k_norm", 1),
                         ("q_proj", 1)):
        assert sum(leaf in name for name in names) == layers, leaf


def layer_of(rank: int, kind: str, chips: int) -> Qwen3NextLayer:
    arch = {**ARCH, "num_experts": 16 // chips,
            "expert_parallel": dict(published_n_routed_experts=16, chips=chips, rank=rank)}
    return Qwen3NextLayer(arch, kind)


@pytest.mark.parametrize("kind, chips, form", [
    ("linear", 16, "auto"), ("attention", 16, "auto"), ("linear", 4, "interpret"),
    ("attention", 4, "interpret"),
], ids=["linear-sixteen-jnp", "attention-sixteen-jnp", "linear-four-pallas", "attention-four-pallas"])
def test_the_ranks_parts_add_up_to_the_uncut_layer(monkeypatch, kind, chips, form):
    """Each rank computes the whole mixer, the whole gated shared expert and
    its own experts' part of the routed sum. The routed parts of all the ranks
    (sixteen holding one expert each, or four holding four), with the mixer's
    residual and the shared expert (what every rank computes alike) counted
    once, equal the uncut reference's layer."""
    monkeypatch.setattr(cells, "_PALLAS_MODE", form)
    rng = np.random.default_rng(20)
    x = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    seam = np.zeros((B, T), bool)
    seam[:, 11] = True
    seg = jnp.cumsum(jnp.asarray(seam, jnp.int32), axis=1)
    carry = (jnp.zeros((B, 4, 16, 16)), jnp.zeros((B, 3, 128))) if kind == "linear" else ()
    whole = jax.jit(lambda k: layer_of(0, kind, chips=1).init(k, x, seg, *carry))(
        jax.random.key(2))["params"]
    whole = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), whole)
    uncut = {k: v for k, v in ARCH.items() if k != "expert_parallel"} | {"num_experts": 16}
    u = reference.norm(x, whole["input_norm"]["scale"], 1e-6)
    if kind == "linear":
        after = x + reference.linear_attention(u, jnp.asarray(seam), whole["linear_attn"], uncut)
    else:
        after = x + reference.attention(u, jnp.asarray(seam), whole["attention"], uncut)
    h = reference.norm(after, whole["post_norm"]["scale"], 1e-6)
    mixed, ref_route = reference.experts(h, whole["experts"], uncut)
    per_expert = ("w_gate", "w_in", "w_out")
    no_experts = {**whole["experts"], **{k: whole["experts"][k][:0] for k in per_expert}}
    shared, _ = reference.experts(h, no_experts, uncut | {"num_experts": 0})
    routed_parts, rows = jnp.zeros_like(x), 0.0
    for rank in range(chips):
        held = slice(16 // chips * rank, 16 // chips * (rank + 1))
        mine = {**whole, "experts": {
            k: (v[held] if k in per_expert else v) for k, v in whole["experts"].items()}}
        out, *_, route = jax.jit(
            lambda p, r=rank: layer_of(r, kind, chips).apply({"params": p}, x, seg, *carry))(mine)
        assert np.array_equal(np.sort(route["choice"], -1), np.sort(ref_route["choice"], -1))
        routed_parts = routed_parts + (out - after - shared)
        rows += float(route["stats"]["rows"])
    close(after + shared + routed_parts, after + mixed, 3e-4)
    assert float(jnp.abs(routed_parts).max()) > 0.1 and float(jnp.abs(shared).max()) > 0.01
    assert rows == B * T * 3
    all_held, *_ = layer_of(0, kind, chips=1).apply({"params": whole}, x, seg, *carry)
    close(all_held, after + mixed, 3e-4)


def test_acting_step_by_step_equals_the_unroll(family, actor, kernel_form):
    """``family.act`` over the linear layers' states and convolution tails and
    the full layer's K/V ring, with the worker's zeroing at episode starts: an
    episode of 21 steps after one of 11, across chunks and spans."""
    batch = make_batch(9, firsts=(0, 11))
    logits = jax.jit(lambda p, b: policy_outputs_routed(
        family, {"actor": p}, Batch.from_mapping(b))[3])(actor, batch)
    assert family.carry_widths == (3 * (4 * 16 * 16 + 3 * 128), 2 * T * 2 * 32 + 1)
    h = jnp.zeros((B, family.carry_widths[0]))
    c = jnp.zeros((B, family.carry_widths[1]))
    act = jax.jit(family.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            h, c = jnp.zeros_like(h), jnp.zeros_like(c)
        _, step_logits, _, h, c = act({"actor": actor}, jnp.asarray(batch["obs"][:, t]), h, c,
                                      jax.random.key(t))
        close(step_logits, logits[:, t], 3e-5)
    assert float(c[0, -1]) == T - 11 and float(jnp.abs(h).max()) > 0


def test_the_unroll_hands_back_the_carry_acting_would_reach(family, actor, kernel_form):
    """The state and the convolution tail after the window's last step, from
    the unroll, against the acting loop's: what the next window would start
    from."""
    batch = make_batch(10, firsts=(5,))
    obs, firsts = jnp.asarray(batch["obs"]), jnp.asarray(batch["is_fir"])
    carry0 = (jnp.zeros((B, 1)), jnp.zeros((B, 1)))
    _, _, (h_unroll, _) = jax.jit(lambda *a: family.actor_unroll(*a))(actor, obs, carry0, firsts)
    h = jnp.zeros((B, family.carry_widths[0]))
    c = jnp.zeros((B, family.carry_widths[1]))
    act = jax.jit(family.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            h, c = jnp.zeros_like(h), jnp.zeros_like(c)
        *_, h, c = act({"actor": actor}, obs[:, t], h, c, jax.random.key(t))
    close(h_unroll, h, 3e-5)


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


REFUSED = {
    "an off-policy algorithm": (dict(algo="SAC"), "on-policy"),
    "no arch": (dict(arch=None), "needs arch"),
    "a key left out": (dict(arch={k: v for k, v in ARCH.items() if k != "linear_key_head_dim"}),
                       "lacks"),
    "dense MLP layers": (dict(arch={**ARCH, "mlp_only_layers": [0]}), "dense MLP"),
    "a sparse step of two": (dict(arch={**ARCH, "decoder_sparse_step": 2}), "expert block"),
    "a sliding window": (dict(arch={**ARCH, "use_sliding_window": True}), "no window"),
    "rotary scaling": (dict(arch={**ARCH, "rope_scaling": {"type": "yarn"}}), "rotary scaling"),
    "an odd rotary width": (dict(arch={**ARCH, "partial_rotary_factor": 0.3}), "rotate-half"),
    "unnormalised weights": (dict(arch={**ARCH, "norm_topk_prob": False}), "chosen logits"),
    "a share that does not add up": (dict(arch={**ARCH, "num_experts": 8}), "is not the published"),
    "a sequence mesh": (dict(mesh_seq=2, attention_impl="ring"), "sequence-parallel"),
}


@pytest.mark.parametrize("change, message", REFUSED.values(), ids=REFUSED.keys())
def test_what_the_family_refuses(change, message):
    with pytest.raises(AssertionError, match=message):
        config(**change)


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
    assert {"gdn_scan", "attn_full", "attn_global", "attn_rope", "moe_experts"} <= paths
    # a CPU: ragged_dot, and the delta rule's jax.numpy body
    assert not {"moe_gmm_pallas", "gdn_pallas", "ssd_scan", "attn_window"} & paths
    text = lowered.as_text(debug_info=True)
    for scope in ("/gdn/linear_attn/", "gdn_conv", "gdn/linear_attn/gdn_scan", "/moe/",
                  "moe_route/", "moe_dispatch/", "moe_combine/", "experts._add_shared/moe_shared",
                  "opt_update", "attn_global/attention/attn_rope"):
        assert scope in text, scope
    assert "ssd_conv" not in text
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    lowered = lower()
    assert {"moe_experts", "moe_gmm_pallas", "gdn_scan", "gdn_pallas"} <= set(
        program_paths(lowered)["paths"])
    assert "gdn/linear_attn/gdn_scan/gdn_pallas" in lowered.as_text(debug_info=True)


def test_the_attention_counters_reach_the_diagnostics_under_the_span_global(actor, system):
    from tpu_rl.obs import learn

    batch = make_batch(16, firsts=(13,))
    routes = system(actor, batch)[2]
    assert [set(r.get("attn-pairs", {})) for r in routes] == [set(), set(), set(), {"global"}]
    scalars = learn.attention_scalars(routes)
    assert set(scalars) == {
        f"attn-{what}-global" for what in ("pairs", "tiles-run", "tiles-band", "bwd-steps")}
    fir = batch["is_fir"][..., 0] > 0
    episode = np.cumsum(fir, axis=1)
    kept = sum(int(((e[:, None] == e[None, :]) & np.tri(T, dtype=bool)).sum()) for e in episode)
    assert float(scalars["attn-pairs-global"]) == kept
    assert float(scalars["attn-tiles-run-global"]) == float(scalars["attn-tiles-band-global"]) == B
    assert float(scalars["attn-bwd-steps-global"]) == B  # one full layer, a grid of one tile


# --------------------------------------- each new field against a line by hand
def test_partial_rope_turns_the_heads_first_features_and_passes_the_rest():
    rng = np.random.default_rng(40)
    x = jnp.asarray(rng.standard_normal((B, T, 2, 32)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    theta, width = 1e7, 8
    got = rope(x, pos, theta, width)
    inv = theta ** (-np.arange(0, width, 2) / width)  # four frequencies
    angle = np.asarray(pos)[..., None, None] * inv
    a, b = np.asarray(x[..., :4]), np.asarray(x[..., 4:8])
    want = np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                           b * np.cos(angle) + a * np.sin(angle), np.asarray(x[..., 8:])], -1)
    close(got, want, 1e-5)
    close(got, reference.rotary(x, pos, theta, width), 1e-5)
    close(rope(x, pos, theta, 32), rope(x, pos, theta), 0)  # the whole head: the old rotation
    assert float(jnp.abs(got[..., :8] - x[..., :8]).max()) > 0.1


def attention_layer(**fields) -> GQAttention:
    return GQAttention(hidden=64, n_q=4, n_kv=2, head_dim=32, scale=32 ** -0.5, **fields)


def attention_case(layer: GQAttention, seed: int):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    seg = jnp.cumsum(jnp.zeros((B, T), jnp.int32).at[:, 13].set(1), axis=1)
    p = layer.init(jax.random.key(seed), u, seg)["params"]
    p = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), p)
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    return u, seg, pos, p


def test_the_qk_norm_is_the_zero_centred_norm_over_each_head():
    layer = attention_layer(qk_norm=1e-6)
    u, seg, pos, p = attention_case(layer, 41)
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (32,)

    def normed(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1.0 + w)

    q = normed((u @ p["q_proj"]["kernel"]).reshape(B, T, 4, 32), p["q_norm"]["scale"])
    k = normed((u @ p["k_proj"]["kernel"]).reshape(B, T, 2, 32), p["k_norm"]["scale"])
    v = (u @ p["v_proj"]["kernel"]).reshape(B, T, 2, 32)
    o = full_attention(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), pos, seg, sm_scale=32 ** -0.5)
    close(layer.apply({"params": p}, u, seg), o.reshape(B, T, -1) @ p["o_proj"]["kernel"], 1e-4)
    fresh = layer.init(jax.random.key(0), u, seg)["params"]
    assert float(jnp.abs(fresh["q_norm"]["scale"]).max()) == 0  # starts at 0: scales by 1


def test_the_output_gate_is_the_second_half_of_each_heads_query_columns():
    layer = attention_layer(gated=True)
    u, seg, pos, p = attention_case(layer, 42)
    assert p["q_proj"]["kernel"].shape == (64, 2 * 4 * 32)
    per_head = (u @ p["q_proj"]["kernel"]).reshape(B, T, 4, 64)
    q, gate = per_head[..., :32], per_head[..., 32:]
    k, v = ((u @ p[name]["kernel"]).reshape(B, T, 2, 32) for name in ("k_proj", "v_proj"))
    o = full_attention(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), pos, seg, sm_scale=32 ** -0.5)
    want = (o * jax.nn.sigmoid(gate)).reshape(B, T, -1) @ p["o_proj"]["kernel"]
    close(layer.apply({"params": p}, u, seg), want, 1e-4)


def test_the_three_fields_default_to_the_layer_the_other_families_build():
    plain_layer, u = attention_layer(), jnp.zeros((B, T, 64))
    seg = jnp.zeros((B, T), jnp.int32)
    tree = jax.eval_shape(lambda: plain_layer.init(jax.random.key(0), u, seg))["params"]
    assert set(tree) == {"q_proj", "k_proj", "v_proj", "o_proj"}
    assert tree["q_proj"]["kernel"].shape == (64, 4 * 32)


def test_the_zero_centred_norm_scales_by_one_plus_its_leaf():
    x = jnp.asarray(np.random.default_rng(43).standard_normal((5, 16)), jnp.float32)
    norm = RMSNorm(1e-6, zero_centered=True)
    p = norm.init(jax.random.key(0), x)
    assert float(jnp.abs(p["params"]["scale"]).max()) == 0
    w = jnp.linspace(-0.5, 0.5, 16)
    want = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1.0 + w)
    close(norm.apply({"params": {"scale": w}}, x), want, 1e-6)
    close(norm.apply({"params": {"scale": w}}, x), reference.norm(x, w, 1e-6), 1e-6)
    close(RMSNorm(1e-6).apply({"params": {"scale": 1.0 + w}}, x), want, 1e-6)


def test_the_gated_shared_expert_is_swiglu_times_a_sigmoid_of_one_logit():
    block = ExpertBlock(hidden=64, n_experts=16, held=4, first=4, top_k=3, expert_width=48,
                        shared_width=40, scale=1.0, form="swiglu", score="softmax",
                        shared_gated=True)
    rng = np.random.default_rng(44)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    p = block.init(jax.random.key(3), u)["params"]
    p = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), p)
    assert p["shared_weight"]["kernel"].shape == (64, 1) and "router_bias" not in p
    out, route = block.apply({"params": p}, u)
    rows = u.reshape(-1, 64)
    choice, weight = moe.route(rows, p["router"], None, 3, 1.0, "softmax")
    routed = moe.routed_experts_dense(
        rows, choice, weight, p["w_in"], p["w_out"], 4, w_gate=p["w_gate"], form="swiglu")
    shared = (jax.nn.silu(u @ p["shared_gate"]["kernel"]) * (u @ p["shared_in"]["kernel"])
              ) @ p["shared_out"]["kernel"]
    want = jax.nn.sigmoid(u @ p["shared_weight"]["kernel"]) * shared + routed.reshape(u.shape)
    close(out, want, 1e-4)
    close(block.apply({"params": p}, u.reshape(-1, 64), method="step"), want.reshape(-1, 64), 1e-4)
    assert np.array_equal(route["choice"].reshape(-1, 3), choice)


# ------------------------------------------------------------ the swiglu walk
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
def test_the_swiglu_walk_against_the_dense_form(kernel, chunk):
    """Output and all five gradients: the tokens', the weights' and the three
    leaves', the walk's hand-written backward against autodiff of the dense
    form; and the form itself against a line by hand."""
    u, choice, weight, (w_gate, w_in, w_out) = gated_case(61)
    probe = jnp.asarray(np.random.default_rng(62).standard_normal((N, D)), jnp.float32)

    def walked(u, weight, w_gate, w_in, w_out):
        return jnp.sum(probe * moe.routed_experts(
            u, choice, weight, w_in, w_out, FIRST, kernel=kernel, chunk=chunk, w_gate=w_gate,
            form="swiglu"))

    def dense(u, weight, w_gate, w_in, w_out):
        return jnp.sum(probe * moe.routed_experts_dense(
            u, choice, weight, w_in, w_out, FIRST, w_gate=w_gate, form="swiglu"))

    args = (u, weight, w_gate, w_in, w_out)
    got, got_grads = jax.jit(jax.value_and_grad(walked, argnums=(0, 1, 2, 3, 4)))(*args)
    want, want_grads = jax.jit(jax.value_and_grad(dense, argnums=(0, 1, 2, 3, 4)))(*args)
    assert abs(float(got) - float(want)) < 1e-3
    for name, g, w in zip(("u", "weight", "w_gate", "w_in", "w_out"), got_grads, want_grads):
        assert float(jnp.abs(g - w).max()) <= 2e-4 * float(jnp.abs(w).max()), name
    y = moe.routed_experts(u, choice, weight, w_in, w_out, FIRST, kernel=kernel, chunk=chunk,
                           w_gate=w_gate, form="swiglu")
    n = 5
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    by_hand = sum(
        float(weight[n, j]) * (silu(u[n] @ w_gate[e - FIRST]) * (u[n] @ w_in[e - FIRST]))
        @ w_out[e - FIRST]
        for j, e in enumerate(np.asarray(choice[n])) if FIRST <= e < FIRST + HELD)
    close(y[n], by_hand, 1e-4)


@pytest.mark.parametrize("form, gated", [("relu2", True), ("swiglu", False), ("geglu", True)])
def test_a_form_is_taken_by_name_and_held_to_its_leaves(form, gated):
    u, choice, weight, (w_gate, w_in, w_out) = gated_case(63)
    with pytest.raises(AssertionError):
        moe.routed_experts_dense(
            u, choice, weight, w_in, w_out, FIRST, w_gate=w_gate if gated else None, form=form)
