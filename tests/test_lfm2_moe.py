"""The lfm2_moe family (``tpu_rl/models/lfm2_moe.py``) at tiny widths on the CPU
against the benchmark's plain reference (``benchmarks/reference/lfm2_moe.py``:
the convolution as three shifted, seam-masked products; attention dense and
masked; the held experts as a loop under a mask): outputs, the PPO loss and
every gradient with identical choices asserted; the gated short convolution
alone against a line by hand with seams at every kind of place; acting step by
step — a two-row tail as a convolution layer's whole carry, a K/V ring past a
wrap — against the unroll; the tail is of ``b * x~``; the plain per-head norms
and the rotation over the whole head; the ranks' parts of a layer adding up to
the uncut one with the dense layer counted once; the counters; the carry's
widths; what the config check refuses. A dense convolution layer, an attention
layer and two convolution layers with experts (``D F C C``), 16 routed experts
over 2 ranks (rank 1 holds experts 8-15), 4 chosen per token; 4 : 2 heads of 16.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe as reference
from benchmarks.reference import losses as ref_losses
from test_granite_hybrid import close, make_batch
from test_nemotron_h import ref_ppo_loss, same_choices
from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.algos.ppo import make_train_step, policy_outputs, policy_outputs_routed
from tpu_rl.algos.registry import get_algo
from tpu_rl.config import LFM2_MOE_ARCH_KEYS, Config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.models import cells
from tpu_rl.models.backbone import NOTHING, recurrent, ring, state_widths, tail
from tpu_rl.models.families import ModelFamily, build_family
from tpu_rl.models.layers import GQAttention, rope
from tpu_rl.models.lfm2_moe import Lfm2MoeActorCritic, Lfm2MoeLayer, ShortConv
from tpu_rl.parallel.sequence import full_attention
from tpu_rl.types import Batch

SHARE = dict(published_n_routed_experts=16, chips=2, rank=1)
ARCH = dict(
    hidden_size=64, num_hidden_layers=4, layer_types=["conv", "full_attention", "conv", "conv"],
    num_dense_layers=1, norm_eps=1e-5, conv_L_cache=3, conv_bias=False, num_attention_heads=4,
    num_key_value_heads=2, rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    intermediate_size=160, moe_intermediate_size=48, num_experts=8, num_experts_per_tok=4,
    norm_topk_prob=True, routed_scaling_factor=1, use_expert_bias=True, expert_parallel=SHARE,
)
T, B, OBS, ACTIONS = 32, 2, 6, 3
HEADS, KV, D, K = 4, 2, 16, 3
PARAMS = dict(algo="PPO", model="lfm2_moe", arch=ARCH, obs_shape=(OBS,),
              action_space=ACTIONS, seq_len=T, batch_size=B)


def config(**kw) -> Config:
    return Config.from_dict({**PARAMS, **kw})


@pytest.fixture(params=["auto", "interpret"], ids=["jnp", "pallas"])
def kernel_form(request, monkeypatch):
    """The form of the experts' products a test's programs are traced in (read
    while tracing: a test jits what it runs inside this fixture's scope)."""
    monkeypatch.setattr(cells, "_PALLAS_MODE", request.param)
    return request.param


@pytest.fixture(scope="module")
def family():
    return build_family(config())


def moved(tree, seed: int = 1):
    """Every leaf moved off its initial value."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        x + 0.1 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def actor(family):
    return jax.jit(lambda key: moved(family.init_params(key, seq_len=T)["actor"]))(
        jax.random.key(0))


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
    assert len(routes) == len(ref_routes) == 3  # the expert layers of D F C C
    assert routes[0]["choice"].shape == (B, T, 4)
    assert same_choices(routes, ref_routes)
    close(logits, ref_logits, 1e-4)
    close(value, ref_value, 1e-4)
    held = float(routes[0]["stats"]["held-share"])
    assert 0.2 < held < 0.8 and float(routes[0]["stats"]["rows"]) == round(held * B * T * 4)


def test_bfloat16_matches_the_reference_on_the_systems_choices(actor, plain):
    fam = build_family(config(compute_dtype="bfloat16"))
    batch = make_batch(3)
    value, logits, routes = jax.jit(
        lambda p, b: policy_outputs_routed(fam, {"actor": p}, Batch.from_mapping(b))[2:])(actor, batch)
    ref_value, ref_logits, ref_routes = plain(actor, batch, [r["choice"] for r in routes])
    for got, want in ((logits, ref_logits), (value, ref_value)):
        close(got, want, 5e-2 * float(np.abs(want).max()))
        assert float(np.abs(np.asarray(got) - np.asarray(want)).max()) > 1e-6
    for mine, theirs in zip(routes, ref_routes):
        differ = (np.sort(mine["choice"], -1) != np.sort(theirs["choice"], -1)).any(-1)
        assert differ.mean() < 0.2
        assert float(np.asarray(theirs["margin"])[differ].max(initial=0.0)) < 0.1


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
        # the expert bias enters the choice alone: no gradient reaches it
        assert (float(jnp.abs(got).max()) > 0) == ("router_bias" not in name), name
    for leaf in ("operator_norm", "ffn_norm"):
        assert sum(leaf in name for name in names) == 4, leaf  # in each of the four layers
    for leaf in ("in_proj", "conv_weight", "out_proj"):
        assert sum(leaf in name for name in names) == 3, leaf  # the three convolution layers
    for leaf in ("'router'", "router_bias", "w_gate", "w_in", "w_out"):
        assert sum(leaf in name for name in names) == 3, leaf  # the three expert layers
    for leaf in ("q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm", "w1", "w2", "w3"):
        assert sum(leaf in name for name in names) == 1, leaf  # one attention, one dense layer
    assert not any("shared" in name for name in names)


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
    moved_by = jax.tree.map(lambda a, b: float(np.abs(a - np.asarray(b)).max()),
                            before, state.params["actor"])
    still = [jax.tree_util.keystr(p) for p, d in jax.tree.leaves_with_path(moved_by) if d == 0]
    assert all("router_bias" in name for name in still) and len(still) == 3, still


# ----------------------------------------------------------------- the counters
def test_the_routing_counters_count_expert_layers_only(family, actor, system):
    """Four layers, three of them expert layers: the routing counters sum and
    average over three records; the attention counters over the one attention
    layer; the dense layer is a convolution layer and counts nothing."""
    from tpu_rl.obs import learn

    batch = make_batch(16, firsts=(13,))
    routes = system(actor, batch)[2]
    assert len(routes) == 3 and all("stats" in r and "choice" in r for r in routes)
    assert ["attn-pairs" in r for r in routes] == [True, False, False]
    scalars = learn.route_scalars(routes)
    assert float(scalars["moe-rows"]) == sum(float(r["stats"]["rows"]) for r in routes)
    assert float(scalars["moe-chunks"]) == 1.0
    assert float(scalars["moe-held-share"]) == pytest.approx(
        np.mean([float(r["stats"]["held-share"]) for r in routes]))
    attn = learn.attention_scalars(routes)
    assert set(attn) == {
        f"attn-{what}-global" for what in ("pairs", "tiles-run", "tiles-band", "bwd-steps")}
    fir = batch["is_fir"][..., 0] > 0
    episode = np.cumsum(fir, axis=1)
    kept = sum(int(((e[:, None] == e[None, :]) & np.tri(T, dtype=bool)).sum()) for e in episode)
    assert float(attn["attn-pairs-global"]) == kept
    assert float(attn["attn-tiles-run-global"]) == float(attn["attn-tiles-band-global"]) == B
    assert float(attn["attn-bwd-steps-global"]) == B  # one layer, a grid of one tile
    step = make_train_step(config(learn_diag=True), family)
    params = {"actor": actor}
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=rmsprop(config()).init(params))
    _, metrics = jax.jit(step)(state, Batch.from_mapping(batch), jax.random.key(1))
    diag = metrics["diag"]["scalars"]
    assert float(diag["moe-rows"]) == float(scalars["moe-rows"])
    assert float(diag["attn-pairs-global"]) == kept


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
    assert {"shortconv", "attn_global", "attn_full", "attn_rope", "moe_experts"} <= paths
    # a CPU: ragged_dot, and no other family's mixer
    assert not {"moe_gmm_pallas", "mla", "attn_window", "gdn_scan", "ssd_scan"} & paths
    text = lowered.as_text(debug_info=True)
    for scope in ("layer0/shortconv/conv/shortconv_in", "/shortconv/conv/shortconv_gate",
                  "shortconv_gate/checkpoint/shortconv_conv", "/shortconv/conv/shortconv_out",
                  "layer1/attn_global/attention/attn_rope", "layer1/attn_global/attention/attn_full",
                  "layer0/mlp/", "layer1/moe/", "layer3/moe/", "moe_route/", "moe_dispatch/",
                  "moe_combine/", "opt_update"):
        assert scope in text, scope
    for absent in ("layer0/moe/", "layer1/mlp/", "layer1/shortconv", "layer2/attn_global",
                   "moe_shared"):
        assert absent not in text, absent
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    assert {"shortconv", "moe_experts", "moe_gmm_pallas"} <= set(program_paths(lower())["paths"])


# ------------------------------------------------- the short convolution alone
def conv_case(seed: int, firsts=()):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    first = np.zeros((B, T), bool)
    first[:, list(firsts)] = True
    seg = jnp.cumsum(jnp.asarray(first, jnp.int32), axis=1)
    tail0 = jnp.zeros((B, K - 1, 64))
    p = ShortConv(64, K).init(jax.random.key(seed), u, seg, tail0)["params"]
    p = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), p)
    return u, jnp.asarray(first), seg, p


def three_shifted_products(u, first, p, tail0=None):
    """``W_out (c * (w_0 z_{t-2} + w_1 z_{t-1} + w_2 z_t))`` a line at a time:
    a step's earlier taps only from its own episode; before the window, the
    tail it was handed (none where the window opens an episode)."""
    b, c, x = np.split(np.asarray(u @ p["in_proj"]["kernel"], np.float64), 3, axis=-1)
    z, w = b * x, np.asarray(p["conv_weight"], np.float64)
    first = np.asarray(first)
    began = np.zeros((B,), int) - (0 if tail0 is None else K - 1)  # where each row's episode began
    before = np.zeros((B, K - 1, 64)) if tail0 is None else np.asarray(tail0, np.float64)
    zp = np.concatenate([before, z], axis=1)  # step t lies at t + K - 1
    h = np.zeros_like(z)
    for t in range(T):
        began = np.where(first[:, t], t, began)
        for j in range(K):  # the tap j steps back
            ok = t - j >= began
            h[:, t] += np.where(ok[:, None], zp[:, t - j + K - 1], 0.0) * w[K - 1 - j]
    return (c * h) @ np.asarray(p["out_proj"]["kernel"], np.float64), z


SEAMS = {
    "no-seam": (), "at-the-query": (13,), "one-step-before": (12, 20), "two-steps-before": (11,),
    "on-each-of-the-last-two-steps": (12, 13), "at-the-windows-first-step": (0,),
    "at-the-windows-second-step": (1,), "at-both-first-steps": (0, 1),
    "at-the-last-step": (T - 1,), "at-the-last-two-steps": (T - 2, T - 1),
}


@pytest.mark.parametrize("firsts", SEAMS.values(), ids=SEAMS.keys())
def test_the_short_convolution_is_three_shifted_products(firsts):
    u, first, seg, p = conv_case(30, firsts)
    assert p["in_proj"]["kernel"].shape == (64, 3 * 64) and p["conv_weight"].shape == (K, 64)
    assert set(p) == {"in_proj", "conv_weight", "out_proj"} and set(p["in_proj"]) == {"kernel"}
    got, tail = ShortConv(64, K).apply({"params": p}, u, seg, jnp.zeros((B, K - 1, 64)))
    want, z = three_shifted_products(u, first, p)
    close(got, want, 1e-4)
    close(got, reference.short_conv(u, first, p, ARCH), 1e-4)
    # the tail: the last two z, those of the last step's episode
    last_two = np.asarray(seg[:, -2:] == seg[:, -1:])
    assert tail.shape == (B, K - 1, 64) and tail.dtype == jnp.float32
    close(tail, np.where(last_two[..., None], z[:, -2:], 0.0), 1e-5)
    if T - 1 in firsts:
        assert float(jnp.abs(tail[:, 0]).max()) == 0 and float(jnp.abs(tail[:, 1]).max()) > 0


@pytest.mark.parametrize("firsts", [(), (0,), (1,), (5,)], ids=["runs-on", "opens", "second", "later"])
def test_a_window_starts_from_the_tail_it_is_handed(firsts):
    """An episode that runs on from the window before reads its last two z at
    the window's first two steps; a window that opens an episode reads none."""
    u, first, seg, p = conv_case(31, firsts)
    rng = np.random.default_rng(32)
    tail0 = jnp.asarray(rng.standard_normal((B, K - 1, 64)), jnp.float32)
    got, _ = ShortConv(64, K).apply({"params": p}, u, seg, tail0)
    want, _ = three_shifted_products(u, first, p, tail0)
    close(got, want, 1e-4)
    empty, _ = ShortConv(64, K).apply({"params": p}, u, seg, jnp.zeros_like(tail0))
    moved_steps = np.nonzero(np.abs(np.asarray(got - empty)).max(axis=(0, 2)) > 1e-6)[0]
    assert list(moved_steps) == ([] if 0 in firsts else [0] if 1 in firsts else [0, 1])


def test_the_convolution_has_no_activation_and_no_bias():
    """Doubling ``c``'s columns doubles the output; doubling ``b``'s too: the
    operator is bilinear in its gates and linear in the taps."""
    u, _, seg, p = conv_case(33, (9,))
    run = lambda p: ShortConv(64, K).apply({"params": p}, u, seg, jnp.zeros((B, K - 1, 64)))[0]  # noqa: E731
    base = run(p)
    w_in = p["in_proj"]["kernel"]
    for chunk in (0, 1, 2):  # b, c, x~
        doubled = w_in.at[:, chunk * 64:(chunk + 1) * 64].multiply(2.0)
        close(run({**p, "in_proj": {"kernel": doubled}}), 2 * base, 2e-4)
    close(run({**p, "conv_weight": 2 * p["conv_weight"]}), 2 * base, 2e-4)
    zero = ShortConv(64, K).apply({"params": p}, jnp.zeros_like(u), seg, jnp.zeros((B, K - 1, 64)))[0]
    assert float(jnp.abs(zero).max()) == 0


def conv_stepped(layer, p, u, first):
    """``layer.step`` over the window with the worker's zeroing at episode
    starts; every step's tail too."""
    tail = jnp.zeros((B, K - 1, 64))
    step = jax.jit(lambda p, u, tail: layer.apply({"params": p}, u, tail, method="step"))
    outs, tails = [], []
    for t in range(T):
        if bool(first[0, t]):
            tail = jnp.zeros_like(tail)
        out, tail = step(p, u[:, t], tail)
        outs.append(out)
        tails.append(tail)
    return jnp.stack(outs, axis=1), tails


@pytest.mark.parametrize("firsts", [(13,), (), (12, 13), (0, 1, 2), (T - 1,)],
                         ids=["a-seam", "one-episode", "two-seams-in-a-row", "three-at-the-start",
                              "a-seam-at-the-end"])
def test_stepping_the_convolution_equals_its_unroll(firsts):
    """A step's carry is the last two ``z``: exactly the unroll's numbers,
    and the tail after the last step is the unroll's."""
    u, first, seg, p = conv_case(34, firsts)
    want, tail = ShortConv(64, K).apply({"params": p}, u, seg, jnp.zeros((B, K - 1, 64)))
    got, tails = conv_stepped(ShortConv(64, K), p, u, first)
    close(got, want, 1e-5)
    close(tails[-1], tail, 1e-6)
    assert tails[-1].shape == (B, 2, 64)


def test_the_tail_is_of_the_gated_input_not_of_the_output_gate():
    """Perturbing ``c`` at step t - 1 (``c``'s columns of ``W_in`` see another
    input there) moves the output at t - 1 and nothing at t or after; the same
    change to ``b`` or ``x~`` reaches t and t + 1 through the tail."""
    u, first, _, p = conv_case(35)
    t = 17
    w_in = p["in_proj"]["kernel"]
    nudged = u.at[:, t - 1].add(1.0)

    def run(part: int):
        """The output with chunk ``part`` of ``[b ; c ; x~]`` computed from
        ``nudged`` and the other two from ``u``."""
        layer = ShortConv(64, K)
        tail = jnp.zeros((B, K - 1, 64))
        outs = []
        for s in range(T):
            mixed = jnp.where(
                (jnp.arange(3 * 64) // 64 == part)[None], nudged[:, s] @ w_in, u[:, s] @ w_in)
            # a step on pre-mixed chunks: in_proj as the identity
            out, tail = layer.apply(
                {"params": {**p, "in_proj": {"kernel": jnp.eye(3 * 64)}}}, mixed, tail,
                method="step")
            outs.append(out)
        return jnp.stack(outs, axis=1)

    base = conv_stepped(ShortConv(64, K), p, u, first)[0]
    moved_at = lambda out: list(np.nonzero(  # noqa: E731
        np.abs(np.asarray(out - base)).max(axis=(0, 2)) > 1e-5)[0])
    assert moved_at(run(1)) == [t - 1]  # c: the step's own output alone
    assert moved_at(run(0)) == [t - 1, t, t + 1]  # b: carried in z for two more steps
    assert moved_at(run(2)) == [t - 1, t, t + 1]  # x~: likewise


def test_bfloat16_keeps_the_gates_product_and_the_taps_sum_in_float32():
    u, first, seg, p = conv_case(36, (13,))
    got, tail = ShortConv(64, K, dtype=jnp.bfloat16).apply(
        {"params": p}, u, seg, jnp.zeros((B, K - 1, 64)))
    want, _ = three_shifted_products(u, first, p)
    assert got.dtype == jnp.bfloat16 and tail.dtype == jnp.float32
    close(got, want, 4e-2 * float(np.abs(want).max()))
    # the tail is the exact float32 product of the two rounded chunks
    chunks = (u.astype(jnp.bfloat16) @ p["in_proj"]["kernel"].astype(jnp.bfloat16))
    b, _, x = jnp.split(chunks, 3, axis=-1)
    close(tail, (b.astype(jnp.float32) * x.astype(jnp.float32))[:, -2:], 0)
    stepped, tails = conv_stepped(ShortConv(64, K, dtype=jnp.bfloat16), p, u, first)
    close(stepped.astype(jnp.float32), got.astype(jnp.float32), 2e-2 * float(np.abs(want).max()))
    close(tails[-1], tail, 0)


# ------------------------------------------------------------ attention's fields
def attention(**fields) -> GQAttention:
    return GQAttention(
        hidden=64, n_q=HEADS, n_kv=KV, head_dim=D, scale=D ** -0.5, rope_theta=1e6, qk_norm=1e-5,
        qk_norm_zero_centered=False, **fields)


def attention_case(seed: int, seam: int | None = 13):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    first = jnp.zeros((B, T), bool)
    if seam is not None:
        first = first.at[:, seam].set(True)
    seg = jnp.cumsum(first.astype(jnp.int32), axis=1)
    p = attention().init(jax.random.key(seed), u, seg)["params"]
    fresh = p
    p = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), p)
    return u, first, seg, p, fresh


def attention_by_hand(u, seg, p, window=None):
    def normed(x, w):  # plain: the weight as it is, not 1 + w
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * w

    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    q = normed((u @ p["q_proj"]["kernel"]).reshape(B, T, HEADS, D), p["q_norm"]["scale"])
    k = normed((u @ p["k_proj"]["kernel"]).reshape(B, T, KV, D), p["k_norm"]["scale"])
    v = (u @ p["v_proj"]["kernel"]).reshape(B, T, KV, D)
    q, k = rope(q, pos, 1e6), rope(k, pos, 1e6)
    o = full_attention(q, jnp.repeat(k, HEADS // KV, 2), jnp.repeat(v, HEADS // KV, 2), pos, seg,
                       sm_scale=D ** -0.5, window=window)
    return o.reshape(B, T, -1) @ p["o_proj"]["kernel"]


def test_the_per_head_norms_are_plain_and_the_rotation_is_over_the_whole_head():
    u, first, seg, p, fresh = attention_case(40)
    assert p["q_norm"]["scale"].shape == p["k_norm"]["scale"].shape == (D,)  # one for all heads
    assert float(jnp.abs(fresh["q_norm"]["scale"] - 1).max()) == 0  # plain: starts at 1
    assert float(jnp.abs(fresh["k_norm"]["scale"] - 1).max()) == 0
    got = attention().apply({"params": p}, u, seg)
    close(got, attention_by_hand(u, seg, p), 1e-4)
    close(got, reference.attention(u, first, p, ARCH), 1e-4)
    # the zero-centred form (another family's) reads the same leaf as 1 + w
    centred = GQAttention(hidden=64, n_q=HEADS, n_kv=KV, head_dim=D, scale=D ** -0.5,
                          rope_theta=1e6, qk_norm=1e-5)
    lowered = {**p, **{n: {"scale": p[n]["scale"] - 1} for n in ("q_norm", "k_norm")}}
    close(centred.apply({"params": lowered}, u, seg), got, 1e-5)
    assert float(jnp.abs(centred.apply({"params": p}, u, seg) - got).max()) > 1e-3
    zeros = centred.init(jax.random.key(0), u, seg)["params"]["q_norm"]["scale"]
    assert float(jnp.abs(zeros).max()) == 0


def test_every_feature_of_a_head_is_rotated():
    """The same episode at steps 0-15 and 16-31: equal outputs (a score moves
    with the distance alone); one episode over the window: every pair of
    features ``(i, i + 8)`` of a head turns with the position."""
    u, _, _, p, _ = attention_case(41, seam=None)
    u = u.at[:, 16:].set(u[:, :16])
    seg = jnp.cumsum(jnp.zeros((B, T), jnp.int32).at[:, 16].set(1), axis=1)
    out = attention().apply({"params": p}, u, seg)
    close(out[:, 16:], out[:, :16], 2e-5)
    x = jnp.ones((1, 2, 1, D))
    turned = rope(x, jnp.asarray([[0, 5]]), 1e6)
    assert float(jnp.abs(turned[0, 0] - 1).max()) == 0
    assert bool((jnp.abs(turned[0, 1, 0] - 1) > 0).all())  # no feature passes unrotated


def attention_stepped(layer, p, u, first, ctx: int):
    k_ring = jnp.zeros((B, ctx, KV, D))
    v_ring, count = jnp.zeros_like(k_ring), jnp.zeros((B,), jnp.int32)
    step = jax.jit(lambda p, u, k, v, n: layer.apply({"params": p}, u, k, v, n, method="step"))
    outs = []
    for t in range(T):
        if bool(first[0, t]):
            k_ring, v_ring, count = jnp.zeros_like(k_ring), jnp.zeros_like(v_ring), 0 * count
        out, k_ring, v_ring = step(p, u[:, t], k_ring, v_ring, count)
        count = count + 1
        outs.append(out)
    return jnp.stack(outs, axis=1), k_ring


@pytest.mark.parametrize("ctx, seam", [(T, 13), (T, None), (8, None), (8, 5)],
                         ids=["a-seam", "one-episode", "a-wrapped-ring", "wrapped-after-a-seam"])
def test_stepping_attention_over_its_ring_equals_the_unroll(ctx, seam):
    """Keys are stored normed (plain) and rotated at their own step; a ring
    of 8 slots is an exact window of 8 steps."""
    u, first, seg, p, _ = attention_case(42, seam)
    got, k_ring = attention_stepped(attention(), p, u, first, ctx)
    whole = ctx if ctx < T else T
    close(got[:, :whole], attention().apply({"params": p}, u, seg)[:, :whole], 1e-4)
    close(got, attention_by_hand(u, seg, p, window=ctx if ctx < T else None), 1e-4)
    since = T - 1 - (seam or 0)
    last_k = (u[:, -1] @ p["k_proj"]["kernel"]).reshape(B, 1, KV, D)
    last_k = last_k * jax.lax.rsqrt(jnp.mean(last_k * last_k, -1, keepdims=True) + 1e-5)
    close(k_ring[:, since % ctx],
          rope(last_k * p["k_norm"]["scale"], jnp.full((B, 1), since), 1e6)[:, 0], 1e-5)


# ------------------------------------------------------------- acting as a whole
def test_acting_step_by_step_equals_the_unroll(family, actor):
    """``family.act`` over a two-row tail a convolution layer and one K/V ring
    for the attention layer, with the worker's zeroing at episode starts: an
    episode of 21 steps after one of 11."""
    batch = make_batch(9, firsts=(0, 11))
    logits = jax.jit(lambda p, b: policy_outputs_routed(
        family, {"actor": p}, Batch.from_mapping(b))[3])(actor, batch)
    widths = state_widths(Lfm2MoeActorCritic.acting_state(ARCH, T))
    assert family.carry_widths == widths == (3 * 2 * 64, 2 * T * KV * D + 1)
    h = jnp.zeros((B, family.carry_widths[0]))
    c = jnp.zeros((B, family.carry_widths[1]))
    act = jax.jit(family.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            h, c = jnp.zeros_like(h), jnp.zeros_like(c)
        _, step_logits, _, h, c = act({"actor": actor}, jnp.asarray(batch["obs"][:, t]), h, c,
                                      jax.random.key(t))
        close(step_logits, logits[:, t], 3e-5)
    assert float(c[0, -1]) == T - 11 and h.shape == (B, 384)
    assert float(jnp.abs(h).min()) > 0  # three tails of two rows, every number a z


@pytest.mark.parametrize("firsts", [(0, 6, 12, 19, 25), (0, 1, 2, 3, 30, 31)],
                         ids=["short-episodes", "seams-in-a-row"])
def test_a_short_acting_context_is_a_window_of_that_many_steps(actor, firsts):
    """``act_ctx`` 8: the ring holds 8 slots — the tails are what they were —
    and acting agrees with the unroll while an episode is shorter than that."""
    fam = build_family(config(act_ctx=8))
    assert fam.carry_widths == (3 * 2 * 64, 2 * 8 * KV * D + 1)
    batch = make_batch(10, firsts=firsts)
    logits = jax.jit(lambda p, b: policy_outputs_routed(
        fam, {"actor": p}, Batch.from_mapping(b))[3])(actor, batch)
    h, c = (jnp.zeros((B, w)) for w in fam.carry_widths)
    act = jax.jit(fam.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            h, c = jnp.zeros_like(h), jnp.zeros_like(c)
        _, step_logits, _, h, c = act({"actor": actor}, jnp.asarray(batch["obs"][:, t]), h, c,
                                      jax.random.key(t))
        since = t - max(f for f in firsts if f <= t)
        if since < 8:
            close(step_logits, logits[:, t], 3e-5)


def test_a_window_handed_the_acting_tails_runs_on_from_them(family, actor):
    """The unroll's ``h`` after a window is what ``act`` carries after the same
    steps, and a second window started from it continues the convolutions (the
    attention context starts empty: the truncation the trunk documents)."""
    batch = make_batch(11, firsts=(0, 20))
    h_after = jax.jit(lambda p, b: family.actor.apply(
        p, b["obs"], (jnp.zeros((B, 1)), jnp.zeros((B, 1))), b["is_fir"])[2][0])(
        actor, {k: jnp.asarray(v) for k, v in batch.items()})
    h, c = (jnp.zeros((B, w)) for w in family.carry_widths)
    act = jax.jit(family.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            h, c = jnp.zeros_like(h), jnp.zeros_like(c)
        *_, h, c = act({"actor": actor}, jnp.asarray(batch["obs"][:, t]), h, c, jax.random.key(t))
    assert h_after.shape == (B, 3 * 2 * 64)
    close(h_after, h, 1e-5)


# ------------------------------------------------------- the carry's statement
def test_a_convolution_layers_carry_is_a_tail_and_no_state():
    """``tail(shape)`` states one array; the widths follow from it, with no
    one-float state (``math.prod(())`` is 1) beside it."""
    assert tail((2, 64)) == ("h", ((2, 64),))
    assert state_widths([tail((2, 64))]) == (128, 1)
    assert state_widths([recurrent((), (2, 64))]) == (129, 1)  # what a dummy state would carry
    assert state_widths([NOTHING, tail((2, 64)), ring((8, 2, 16), (8, 2, 16))]) == (128, 513)
    carried = Lfm2MoeActorCritic.acting_state(ARCH, T)
    assert carried == [tail((2, 64)), ring((T, KV, D), (T, KV, D)), tail((2, 64)), tail((2, 64))]


def test_the_carrys_widths_at_the_published_sizes():
    """Layers 1-5 of the published 40 (``D F C C C``) at a context of 8,192:
    ``h`` is four tails of 2 x 2,048, ``c`` one ring of keys and values at 8
    heads of 64 and the counter."""
    published = {**ARCH, "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 8,
                 "num_hidden_layers": 5,
                 "layer_types": ["conv", "full_attention", "conv", "conv", "conv"]}
    h, c = state_widths(Lfm2MoeActorCritic.acting_state(published, 8192))
    assert h == 4 * 4096 and c == 2 * 8192 * 8 * 64 + 1


# ------------------------------------------------------------- the ranks' parts
def layer_of(rank: int, kind: tuple, chips: int) -> Lfm2MoeLayer:
    arch = {**ARCH, "num_experts": 16 // chips,
            "expert_parallel": dict(published_n_routed_experts=16, chips=chips, rank=rank)}
    return Lfm2MoeLayer(arch, kind)


def mixer_of_reference(kind: str, u, first, p, arch):
    if kind == "conv":
        return reference.short_conv(u, first, p["conv"], arch)
    return reference.attention(u, first, p["attention"], arch)


@pytest.mark.parametrize("kind", ["conv", "full_attention"])
@pytest.mark.parametrize("chips, form", [(2, "auto"), (16, "auto"), (2, "interpret")],
                         ids=["two-jnp", "sixteen-jnp", "two-pallas"])
def test_the_ranks_parts_add_up_to_the_uncut_layer(monkeypatch, chips, form, kind):
    """Each rank computes the mixer whole and its own experts' part of the
    routed sum. The routed parts of all the ranks (two holding eight experts
    each, or sixteen holding one), with the mixer's residual (what every rank
    computes alike) counted once, equal the uncut reference's layer."""
    monkeypatch.setattr(cells, "_PALLAS_MODE", form)
    rng = np.random.default_rng(20)
    x = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    seam = np.zeros((B, T), bool)
    seam[:, 11] = True
    seg = jnp.cumsum(jnp.asarray(seam, jnp.int32), axis=1)
    carry = (jnp.zeros((B, K - 1, 64)),) if kind == "conv" else ()
    whole = jax.jit(lambda k: layer_of(0, (kind, False), chips=1).init(k, x, seg, *carry))(
        jax.random.key(2))["params"]
    whole = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), whole)
    uncut = {k: v for k, v in ARCH.items() if k != "expert_parallel"} | {"num_experts": 16}
    u = reference.norm(x, whole["operator_norm"]["scale"], 1e-5)
    after = x + mixer_of_reference(kind, u, jnp.asarray(seam), whole, uncut)
    h = reference.norm(after, whole["ffn_norm"]["scale"], 1e-5)
    mixed, ref_route = reference.experts(h, whole["experts"], uncut)
    per_expert = ("w_gate", "w_in", "w_out")
    routed_parts, rows = jnp.zeros_like(x), 0.0
    for rank in range(chips):
        held = slice(16 // chips * rank, 16 // chips * (rank + 1))
        mine = {**whole, "experts": {
            k: (v[held] if k in per_expert else v) for k, v in whole["experts"].items()}}
        out, *_, route = jax.jit(
            lambda p, r=rank: layer_of(r, (kind, False), chips).apply({"params": p}, x, seg, *carry))(
            mine)
        assert np.array_equal(np.sort(route["choice"], -1), np.sort(ref_route["choice"], -1))
        assert ("attn-pairs" in route) == (kind == "full_attention")
        routed_parts = routed_parts + (out - after)
        rows += float(route["stats"]["rows"])
    close(after + routed_parts, after + mixed, 3e-4)
    assert float(jnp.abs(routed_parts).max()) > 0.1
    assert rows == B * T * 4
    all_held, *_ = layer_of(0, (kind, False), chips=1).apply({"params": whole}, x, seg, *carry)
    close(all_held, after + mixed, 3e-4)


def test_the_dense_layer_is_whole_on_every_rank_and_counts_once():
    """Layer 0 has no experts: every rank computes the same convolution and
    the same SwiGLU MLP, the reference's; it hands back its tail and no
    record. With the two expert layers' routed parts of both ranks, the dense
    layer counted once, the ranks add up to the uncut reference's ``D C``."""
    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    seam = np.zeros((B, T), bool)
    seam[:, 7] = True
    seg = jnp.cumsum(jnp.asarray(seam, jnp.int32), axis=1)
    tail0 = jnp.zeros((B, K - 1, 64))
    p = layer_of(0, ("conv", True), chips=2).init(jax.random.key(3), x, seg, tail0)["params"]
    p = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), p)
    assert set(p) == {"operator_norm", "ffn_norm", "conv", "w1", "w2", "w3"}
    assert p["w1"]["kernel"].shape == p["w3"]["kernel"].shape == (64, 160)
    outs = [layer_of(rank, ("conv", True), chips=2).apply({"params": p}, x, seg, tail0)
            for rank in (0, 1)]
    assert all(len(out) == 2 for out in outs)  # x and the tail: a dense layer counts nothing
    close(outs[0][0], outs[1][0], 0)
    u = reference.norm(x, p["operator_norm"]["scale"], 1e-5)
    after = x + reference.short_conv(u, jnp.asarray(seam), p["conv"], ARCH)
    h = reference.norm(after, p["ffn_norm"]["scale"], 1e-5)
    want = after + reference.swiglu(h, *(p[leaf]["kernel"] for leaf in ("w1", "w3", "w2")))
    close(outs[0][0], want, 3e-4)
    by_line = (jax.nn.silu(h @ p["w1"]["kernel"]) * (h @ p["w3"]["kernel"])) @ p["w2"]["kernel"]
    close(outs[0][0], after + by_line, 3e-4)

    # D C over two ranks: the dense layer once, then each rank's routed part of the next
    q = jax.jit(lambda k: layer_of(0, ("conv", False), chips=1).init(k, x, seg, tail0))(
        jax.random.key(4))["params"]
    q = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), q)
    uncut = {k: v for k, v in ARCH.items() if k != "expert_parallel"} | {
        "num_experts": 16, "num_hidden_layers": 2, "layer_types": ["conv", "conv"]}
    tree = {"params": {
        "embed": {"kernel": jnp.eye(64), "bias": jnp.zeros((64,))}, "layer0": p, "layer1": q,
        "norm_f": {"scale": jnp.ones((64,))},
        "logits": {"kernel": jnp.eye(64), "bias": jnp.zeros((64,))},
        "value": {"kernel": jnp.zeros((64, 1)), "bias": jnp.zeros((1,))}}}
    batch = {"obs": x, "is_fir": jnp.asarray(seam, jnp.float32)[..., None]}
    # the reference's D C from the raw stream: undo its final norm and softmax by hand
    x1 = want
    u1 = reference.norm(x1, q["operator_norm"]["scale"], 1e-5)
    after1 = x1 + reference.short_conv(u1, jnp.asarray(seam), q["conv"], uncut)
    mixed1, _ = reference.experts(
        reference.norm(after1, q["ffn_norm"]["scale"], 1e-5), q["experts"], uncut)
    logits, _, routes = reference.forward_routed(tree, batch, {"arch": uncut})
    assert len(routes) == 1
    close(logits, jax.nn.log_softmax(reference.norm(after1 + mixed1, jnp.ones((64,)), 1e-5)), 1e-4)
    parts = jnp.zeros_like(x)
    for rank in (0, 1):
        held = slice(8 * rank, 8 * (rank + 1))
        mine = {**q, "experts": {k: (v[held] if k in ("w_gate", "w_in", "w_out") else v)
                                 for k, v in q["experts"].items()}}
        dense_out = layer_of(rank, ("conv", True), chips=2).apply({"params": p}, x, seg, tail0)[0]
        out, _, _ = layer_of(rank, ("conv", False), chips=2).apply(
            {"params": mine}, dense_out, seg, tail0)
        parts = parts + (out - after1)
    close(after1 + parts, after1 + mixed1, 3e-4)


def test_the_expert_block_is_the_shared_one_at_this_familys_fields(family, actor):
    """``swiglu`` experts under the sigmoid router with its bias, scale 1 and
    no shared expert: a fifth combination of the block's fields, no new leaf."""
    experts = actor["params"]["layer1"]["experts"]
    assert set(experts) == {"router", "router_bias", "w_gate", "w_in", "w_out"}
    assert experts["router"].shape == (64, 16) and experts["w_gate"].shape == (8, 64, 48)
    rng = np.random.default_rng(22)
    h = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    got, route = reference.experts(h, experts, ARCH)
    s = 1 / (1 + np.exp(-np.asarray(h[0, 3] @ experts["router"], np.float64)))
    chosen = np.argsort(-(s + np.asarray(experts["router_bias"])), kind="stable")[:4]
    assert set(chosen) == set(np.asarray(route["choice"][0, 3]))
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    want = np.zeros((64,))
    for e in chosen:
        if 8 <= e < 16:  # rank 1 of 2
            w = 1.0 * s[e] / s[chosen].sum()
            want = want + w * (silu(h[0, 3] @ experts["w_gate"][e - 8]) * (h[0, 3] @ experts["w_in"][e - 8])
                               ) @ experts["w_out"][e - 8]
    close(got[0, 3], want, 1e-4)


def test_the_expert_bias_enters_the_choice_alone(family, actor, system):
    batch = make_batch(5)
    pushed = jax.tree.map(lambda a: a, actor)
    bias = pushed["params"]["layer2"]["experts"]["router_bias"]
    pushed["params"]["layer2"]["experts"]["router_bias"] = bias.at[9].set(10.0)
    routes = system(pushed, batch)[2]
    assert (np.asarray(routes[1]["choice"]) == 9).any(-1).all()
    grads = jax.jit(jax.grad(lambda p: jnp.sum(policy_outputs(
        family, {"actor": p}, Batch.from_mapping(batch))[2])))(actor)
    assert float(jnp.abs(grads["params"]["layer2"]["experts"]["router_bias"]).max()) == 0


def test_a_layers_mixer_and_its_feed_forward_part_vary_independently():
    """``layer_args`` hands a pair: all four kinds of layer build, and a dense
    attention layer hands back its mask's counts and no routing."""
    from tpu_rl.models.lfm2_moe import layer_kinds

    arch = {**ARCH, "num_dense_layers": 2}
    assert layer_kinds(arch) == [("conv", True), ("full_attention", True), ("conv", False),
                                 ("conv", False)]
    x = jnp.ones((B, T, 64))
    seg = jnp.zeros((B, T), jnp.int32)
    layer = Lfm2MoeLayer(ARCH, ("full_attention", True))
    p = jax.jit(lambda k: layer.init(k, x, seg))(jax.random.key(0))
    assert set(p["params"]) == {"operator_norm", "ffn_norm", "attention", "w1", "w2", "w3"}
    out, record = layer.apply(p, x, seg)
    assert out.shape == x.shape and set(record) == {
        "attn-pairs", "attn-tiles-run", "attn-tiles-band", "attn-bwd-steps"}
    fam = build_family(config(arch=arch))
    tree = jax.eval_shape(fam.init_params, jax.random.key(0))["actor"]["params"]
    assert "w1" in tree["layer1"] and "experts" not in tree["layer1"] and "experts" in tree["layer2"]


# ------------------------------------------------------------------ the refusals
REFUSED = {
    "an off-policy algorithm": (dict(algo="SAC"), "on-policy"),
    "no arch": (dict(arch=None), "needs arch"),
    "a layer type it does not know": (
        dict(arch={**ARCH, "layer_types": ["conv", "sliding_attention", "conv", "conv"]}),
        "sliding_attention"),
    "too few layer types": (dict(arch={**ARCH, "layer_types": ["conv", "conv"]}),
                            "names 2 layers of 4"),
    "a convolution bias": (dict(arch={**ARCH, "conv_bias": True}), "no bias"),
    "a convolution of one tap": (dict(arch={**ARCH, "conv_L_cache": 1}), "a tail of none"),
    "no expert layer": (dict(arch={**ARCH, "num_dense_layers": 4}), "expert layer has to follow"),
    "key heads that do not divide": (dict(arch={**ARCH, "num_key_value_heads": 3}), r"\(4, 3\)"),
    "another head size": (dict(arch={**ARCH, "head_dim": 32}), "hidden_size / num_attention_heads"),
    "an odd head": (dict(arch={**ARCH, "hidden_size": 60, "num_attention_heads": 4,
                               "num_key_value_heads": 4}), "rotate-half"),
    "rotary scaling": (dict(arch={**ARCH, "rope_parameters": dict(
        rope_theta=1e6, rope_type="yarn", factor=4)}), "rotary scaling"),
    "no rotary base": (dict(arch={**ARCH, "rope_parameters": dict(rope_type="default")}),
                       "rotary scaling"),
    "unnormalised weights": (dict(arch={**ARCH, "norm_topk_prob": False}), "normalised"),
    "no expert bias": (dict(arch={**ARCH, "use_expert_bias": False}), "expert bias"),
    "a share that does not add up": (dict(arch={**ARCH, "num_experts": 4}), "is not the published"),
    "more chosen than published": (dict(arch={**ARCH, "num_experts_per_tok": 17}), "17"),
    "a sequence mesh": (dict(mesh_seq=2, attention_impl="ring"), "sequence-parallel"),
    **{f"no {key}": (dict(arch={k: v for k, v in ARCH.items() if k != key}), f"lacks.*{key}")
       for key in LFM2_MOE_ARCH_KEYS},
}


@pytest.mark.parametrize("change, message", REFUSED.values(), ids=REFUSED.keys())
def test_what_the_family_refuses(change, message):
    with pytest.raises(AssertionError, match=message):
        config(**change)


def test_an_arch_that_names_the_head_size_it_has_is_taken():
    assert config(arch={**ARCH, "head_dim": 16}).arch["head_dim"] == 16
