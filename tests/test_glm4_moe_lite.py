"""The glm4_moe_lite family (``tpu_rl/models/glm4_moe_lite.py``) at tiny widths
on the CPU against the benchmark's plain reference
(``benchmarks/reference/glm4_moe_lite.py``: latent attention in its expanded
form, dense and masked; the held experts as a loop under a mask): outputs, the
PPO loss and every gradient with identical choices asserted; the absorbed
acting form over the latent ring against the expanded one, across seams and
past a wrapped ring; the one rotated key every head shares; the ranks' parts
of a layer adding up to the uncut one with the shared expert and the dense
layer counted once; the counters; what the config check refuses; and the
update programs of the five configurations the benchmark already had, held to
the text they lowered to before this family came. One dense layer and two
expert layers (``D E E``), 16 routed experts over 2 ranks (rank 1 holds
experts 8-15), 4 chosen per token; heads of 24 + 8 = 32."""

import hashlib
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm4_moe_lite as reference
from benchmarks.reference import losses as ref_losses
from test_granite_hybrid import close, make_batch
from test_nemotron_h import ref_ppo_loss, same_choices
from tpu_rl.algos.base import TrainState, make_train_state, rmsprop
from tpu_rl.algos.ppo import make_train_step, policy_outputs, policy_outputs_routed
from tpu_rl.algos.registry import get_algo
from tpu_rl.config import GLM4_MOE_LITE_ARCH_KEYS, Config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.models import cells
from tpu_rl.models.families import ModelFamily, build_family
from tpu_rl.models.backbone import state_widths
from tpu_rl.models.glm4_moe_lite import Glm4MoeLiteActorCritic, Glm4MoeLiteLayer, MLAttention
from tpu_rl.models.layers import rope
from tpu_rl.parallel.sequence import full_attention
from tpu_rl.types import Batch

SHARE = dict(published_n_routed_experts=16, chips=2, rank=1)
ARCH = dict(
    hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, rms_norm_eps=1e-5,
    num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24, kv_lora_rank=16,
    qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, rope_theta=1000000,
    rope_scaling=None, partial_rotary_factor=1, attention_bias=False, hidden_act="silu",
    intermediate_size=160, moe_intermediate_size=48, n_routed_experts=8, n_shared_experts=1,
    num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=1.8,
    topk_method="noaux_tc", n_group=1, topk_group=1, expert_parallel=SHARE,
)
T, B, OBS, ACTIONS = 32, 2, 6, 3
HEADS, RANK, NOPE, ROPE, VDIM = 4, 16, 24, 8, 32
PARAMS = dict(algo="PPO", model="glm4_moe_lite", arch=ARCH, obs_shape=(OBS,),
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
    assert len(routes) == len(ref_routes) == 2  # the expert layers of D E E
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
        # narrow latents and every leaf moved by 0.1: a bf16 step is a larger share here
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
        # the correction bias enters the choice alone: no gradient reaches it
        assert (float(jnp.abs(got).max()) > 0) == ("router_bias" not in name), name
    for leaf in ("q_a_proj", "q_a_norm", "q_b_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj",
                 "o_proj", "input_norm", "post_norm"):
        assert sum(leaf in name for name in names) == 3, leaf  # in each of the three layers
    for leaf in ("'router'", "router_bias", "w_gate", "w_in", "w_out", "shared_gate", "shared_in",
                 "shared_out"):
        assert sum(leaf in name for name in names) == 2, leaf  # in the two expert layers
    for leaf in ("gate_proj", "up_proj", "down_proj"):
        assert sum(leaf in name for name in names) == 1, leaf  # the leading dense layer


def test_the_correction_bias_enters_the_choice_alone(family, actor, system):
    """A large bias on one expert makes every step choose it and leaves the
    weights' formula alone: no gradient reaches the bias."""
    batch = make_batch(5)
    pushed = jax.tree.map(lambda a: a, actor)
    bias = pushed["params"]["layer1"]["experts"]["router_bias"]
    pushed["params"]["layer1"]["experts"]["router_bias"] = bias.at[9].set(10.0)
    routes = system(pushed, batch)[2]
    assert (np.asarray(routes[0]["choice"]) == 9).any(-1).all()
    grads = jax.jit(jax.grad(lambda p: jnp.sum(policy_outputs(
        family, {"actor": p}, Batch.from_mapping(batch))[2])))(actor)
    assert float(jnp.abs(grads["params"]["layer1"]["experts"]["router_bias"]).max()) == 0


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
    assert all("router_bias" in name for name in still) and len(still) == 2, still


# ----------------------------------------------------------------- the counters
def test_the_routing_counters_count_expert_layers_only(family, actor, system):
    """Three layers, two of them expert layers: the routing counters sum and
    average over two records; the attention counters (every layer has latent
    attention) over three, the dense layer's riding with the first record."""
    from tpu_rl.obs import learn

    batch = make_batch(16, firsts=(13,))
    routes = system(actor, batch)[2]
    assert len(routes) == 2 and all("stats" in r and "choice" in r for r in routes)
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
    assert float(attn["attn-pairs-global"]) == 3 * kept
    assert float(attn["attn-tiles-run-global"]) == float(attn["attn-tiles-band-global"]) == 3 * B
    assert float(attn["attn-bwd-steps-global"]) == 3 * B  # three layers, a grid of one tile
    step = make_train_step(config(learn_diag=True), family)
    params = {"actor": actor}
    state = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                       opt_state=rmsprop(config()).init(params))
    _, metrics = jax.jit(step)(state, Batch.from_mapping(batch), jax.random.key(1))
    diag = metrics["diag"]["scalars"]
    assert float(diag["moe-rows"]) == float(scalars["moe-rows"])
    assert float(diag["attn-pairs-global"]) == 3 * kept


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
    assert {"mla", "attn_full", "attn_rope", "moe_experts"} <= paths
    # a CPU: ragged_dot, and no other family's mixer
    assert not {"moe_gmm_pallas", "attn_global", "attn_window", "gdn_scan", "ssd_scan"} & paths
    text = lowered.as_text(debug_info=True)
    for scope in ("/mla/attention/mla_down", "/mla/attention/mla_up", "/mla/attention/attn_rope",
                  "/mla/attention/attn_full", "/mla/attention/mla_o", "layer0/mlp/", "/moe/",
                  "moe_route/", "moe_dispatch/", "moe_combine/", "experts._add_shared/moe_shared",
                  "opt_update"):
        assert scope in text, scope
    assert "layer0/moe/" not in text and "layer1/mlp/" not in text
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    assert {"mla", "moe_experts", "moe_gmm_pallas"} <= set(program_paths(lower())["paths"])


# ------------------------------------------------------ latent attention alone
def mixer(**fields) -> MLAttention:
    return MLAttention(
        hidden=64, heads=HEADS, q_rank=24, kv_rank=RANK, nope_dim=NOPE, rope_dim=ROPE,
        v_dim=VDIM, rope_theta=1e6, eps=1e-5, **fields)


def mixer_case(seed: int, seam: int | None = 13):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    first = jnp.zeros((B, T), bool)
    if seam is not None:
        first = first.at[:, seam].set(True)
    seg = jnp.cumsum(first.astype(jnp.int32), axis=1)
    p = mixer().init(jax.random.key(seed), u, seg)["params"]
    p = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), p)
    return u, first, seg, p


def by_hand(u, seg, p, window=None):
    """The expanded form, a line at a time: every head's keys and values from
    the latent, the one rotated key copied to every head."""
    def normed(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * w

    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))
    q = (normed(u @ p["q_a_proj"]["kernel"], p["q_a_norm"]["scale"])
         @ p["q_b_proj"]["kernel"]).reshape(B, T, HEADS, NOPE + ROPE)
    down = u @ p["kv_a_proj"]["kernel"]
    kv = (normed(down[..., :RANK], p["kv_a_norm"]["scale"])
          @ p["kv_b_proj"]["kernel"]).reshape(B, T, HEADS, NOPE + VDIM)
    shared = rope(down[..., None, RANK:], pos, 1e6)  # (B, T, 1, 8): no head of its own
    q = jnp.concatenate([q[..., :NOPE], rope(q[..., NOPE:], pos, 1e6)], -1)
    k = jnp.concatenate([kv[..., :NOPE], jnp.repeat(shared, HEADS, axis=2)], -1)
    o = full_attention(q, k, kv[..., NOPE:], pos, seg, sm_scale=32 ** -0.5, window=window)
    return o.reshape(B, T, -1) @ p["o_proj"]["kernel"]


def test_the_mixer_is_the_expanded_form_by_hand_and_the_references():
    u, first, seg, p = mixer_case(40)
    assert {k: v["kernel"].shape for k, v in p.items() if "proj" in k} == {
        "q_a_proj": (64, 24), "q_b_proj": (24, HEADS * 32), "kv_a_proj": (64, RANK + ROPE),
        "kv_b_proj": (RANK, HEADS * (NOPE + VDIM)), "o_proj": (HEADS * VDIM, 64)}
    assert p["q_a_norm"]["scale"].shape == (24,) and p["kv_a_norm"]["scale"].shape == (RANK,)
    got = mixer().apply({"params": p}, u, seg)
    close(got, by_hand(u, seg, p), 1e-4)
    arch = {**ARCH, "rope_theta": 1e6}
    close(got, reference.latent_attention(u, first, p, arch), 1e-4)
    fresh = mixer().init(jax.random.key(0), u, seg)["params"]
    assert float(jnp.abs(fresh["q_a_norm"]["scale"] - 1).max()) == 0  # plain: starts at 1


def test_the_rotation_is_on_the_last_features_of_a_head_and_of_the_shared_key():
    """Zeroing the rotated part's columns (the *last* 8 of a head's 32 query
    columns; the last 8 of ``kv_a_proj``'s) leaves an attention without
    positions: an episode's outputs then do not depend on where it lies."""
    u, _, _, p = mixer_case(41, seam=None)
    u = u.at[:, 16:].set(u[:, :16])
    seg = jnp.cumsum(jnp.zeros((B, T), jnp.int32).at[:, 16].set(1), axis=1)
    q_b = p["q_b_proj"]["kernel"].reshape(24, HEADS, 32)
    no_rope = {"q_b_proj": {"kernel": q_b.at[..., NOPE:].set(0).reshape(24, -1)},
               "kv_a_proj": {"kernel": p["kv_a_proj"]["kernel"].at[:, RANK:].set(0)}}
    both = mixer().apply({"params": {**p, **no_rope}}, u, seg)
    for name, leaf in no_rope.items():
        cut = {**p, name: leaf}
        out = mixer().apply({"params": cut}, u, seg)
        close(out, by_hand(u, seg, cut), 1e-4)
        close(out, both, 1e-5)  # one zero kills the term
    close(both[:, 16:], both[:, :16], 2e-5)  # no positions: the episode's place does not matter


def test_a_score_moves_only_with_the_distance_between_query_and_key():
    """The same episode at steps 0-15 and at steps 16-31 of a window: the
    rotation's position is the window's index, the outputs are the same."""
    u, _, _, p = mixer_case(42, seam=None)
    u = u.at[:, 16:].set(u[:, :16])
    seg = jnp.cumsum(jnp.zeros((B, T), jnp.int32).at[:, 16].set(1), axis=1)
    out = mixer().apply({"params": p}, u, seg)
    close(out[:, 16:], out[:, :16], 2e-5)
    one_episode = mixer().apply({"params": p}, u, jnp.zeros((B, T), jnp.int32))
    assert float(jnp.abs(one_episode[:, 16:] - out[:, 16:]).max()) > 1e-2  # the seam matters


def test_heads_differ_only_through_their_queries_where_only_the_shared_key_scores():
    """With every head's unrotated query zeroed, a score is ``q_h^rope . k^r``
    and ``k^r`` has no head: heads given the same rotated query weigh the
    steps alike, so their outputs are the same mix of their own values."""
    u, _, seg, p = mixer_case(43)
    q_b = p["q_b_proj"]["kernel"].reshape(24, HEADS, 32).at[..., :NOPE].set(0)
    q_b = q_b.at[:, 1:, NOPE:].set(q_b[:, :1, NOPE:])  # every head asks head 0's question
    kv_b = p["kv_b_proj"]["kernel"].reshape(RANK, HEADS, NOPE + VDIM)
    kv_b = kv_b.at[:, 1:, NOPE:].set(kv_b[:, :1, NOPE:])  # ... of head 0's values
    cut = {**p, "q_b_proj": {"kernel": q_b.reshape(24, -1)},
           "kv_b_proj": {"kernel": kv_b.reshape(RANK, -1)}}
    per_head = []
    for h in range(HEADS):  # o_proj reads head h alone
        o_h = jnp.zeros((HEADS * VDIM, 64)).at[h * VDIM:(h + 1) * VDIM, :VDIM].set(jnp.eye(VDIM))
        per_head.append(mixer().apply({"params": {**cut, "o_proj": {"kernel": o_h}}}, u, seg))
    for other in per_head[1:]:
        close(other, per_head[0], 1e-5)
    assert float(jnp.abs(per_head[0]).max()) > 0.1
    # the keys' unrotated part differs per head and was not touched: it is not read
    changed = {**cut, "kv_b_proj": {"kernel": kv_b.at[..., :NOPE].add(1.0).reshape(RANK, -1)}}
    close(mixer().apply({"params": changed}, u, seg), mixer().apply({"params": cut}, u, seg), 1e-5)


def stepped(layer: MLAttention, p, u, first, ctx: int):
    """``layer.step`` over the window with the worker's zeroing at episode
    starts; the ring after the last step too."""
    ring = jnp.zeros((B, ctx, RANK + ROPE))
    count = jnp.zeros((B,), jnp.int32)
    step = jax.jit(lambda p, u, ring, count: layer.apply({"params": p}, u, ring, count, method="step"))
    outs = []
    for t in range(T):
        if bool(first[0, t]):
            ring, count = jnp.zeros_like(ring), jnp.zeros_like(count)
        out, ring = step(p, u[:, t], ring, count)
        count = count + 1
        outs.append(out)
    return jnp.stack(outs, axis=1), ring


@pytest.mark.parametrize("ctx, seam", [(T, 13), (T, None), (8, None), (8, 5)],
                         ids=["a-seam", "one-episode", "a-wrapped-ring", "wrapped-after-a-seam"])
def test_the_absorbed_form_over_the_latent_ring_equals_the_expanded_form(ctx, seam):
    """Acting stores 16 + 8 numbers a step whatever the head count and folds
    ``kv_b_proj``'s key half into the query and its value half into the
    output; a ring of 8 slots is an exact window of 8 steps."""
    u, first, seg, p = mixer_case(44, seam)
    got, ring = stepped(mixer(), p, u, first, ctx)
    assert ring.shape == (B, ctx, 24)
    close(got, by_hand(u, seg, p, window=ctx if ctx < T else None), 1e-4)
    whole = ctx if ctx < T else T  # the steps before the ring wraps: the module's own unroll
    close(got[:, :whole], mixer().apply({"params": p}, u, seg)[:, :whole], 1e-4)

    # a slot holds the normed latent and the key as rotated at its own step
    def normed(x, w):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * w

    last = u[:, -1] @ p["kv_a_proj"]["kernel"]
    since = T - 1 - (seam or 0)
    slot = since % ctx
    close(ring[:, slot, :RANK], normed(last[:, :RANK], p["kv_a_norm"]["scale"]), 1e-5)
    close(ring[:, slot, RANK:],
          rope(last[:, None, RANK:], jnp.full((B,), since), 1e6)[:, 0], 1e-5)


def test_the_absorbed_form_in_bfloat16_stays_at_rounding_of_the_expanded_form():
    u, first, seg, p = mixer_case(45)
    got, _ = stepped(mixer(dtype=jnp.bfloat16), p, u, first, T)
    want = by_hand(u, seg, p)
    close(got, want, 4e-2 * float(jnp.abs(want).max()))


def test_acting_step_by_step_equals_the_unroll(family, actor):
    """``family.act`` over one latent ring a layer, with the worker's zeroing
    at episode starts: an episode of 21 steps after one of 11."""
    batch = make_batch(9, firsts=(0, 11))
    logits = jax.jit(lambda p, b: policy_outputs_routed(
        family, {"actor": p}, Batch.from_mapping(b))[3])(actor, batch)
    widths = state_widths(Glm4MoeLiteActorCritic.acting_state(ARCH, T))
    assert family.carry_widths == widths == (0, 3 * T * (16 + 8) + 1)
    h = jnp.zeros((B, family.carry_widths[0]))
    c = jnp.zeros((B, family.carry_widths[1]))
    act = jax.jit(family.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            h, c = jnp.zeros_like(h), jnp.zeros_like(c)
        _, step_logits, _, h, c = act({"actor": actor}, jnp.asarray(batch["obs"][:, t]), h, c,
                                      jax.random.key(t))
        close(step_logits, logits[:, t], 3e-5)
    assert float(c[0, -1]) == T - 11 and h.shape == (B, 0)
    # a full-width K/V ring would hold 2 x heads x 32 numbers a step and layer
    assert 2 * HEADS * 32 / (RANK + ROPE) > 10


def test_a_short_acting_context_is_a_window_of_that_many_steps(actor):
    """``act_ctx`` 8: the carry holds 8 slots a layer and acting agrees with
    the unroll while an episode is shorter than that."""
    fam = build_family(config(act_ctx=8))
    assert fam.carry_widths == (0, 3 * 8 * 24 + 1)
    batch = make_batch(10, firsts=(0, 6, 12, 19, 25))
    logits = jax.jit(lambda p, b: policy_outputs_routed(
        fam, {"actor": p}, Batch.from_mapping(b))[3])(actor, batch)
    h, c = jnp.zeros((B, 0)), jnp.zeros((B, fam.carry_widths[1]))
    act = jax.jit(fam.act)
    for t in range(T):
        if batch["is_fir"][0, t, 0]:
            h, c = jnp.zeros_like(h), jnp.zeros_like(c)
        _, step_logits, _, h, c = act({"actor": actor}, jnp.asarray(batch["obs"][:, t]), h, c,
                                      jax.random.key(t))
        close(step_logits, logits[:, t], 3e-5)


# ------------------------------------------------------------- the ranks' parts
def layer_of(rank: int, index: int, chips: int) -> Glm4MoeLiteLayer:
    arch = {**ARCH, "n_routed_experts": 16 // chips,
            "expert_parallel": dict(published_n_routed_experts=16, chips=chips, rank=rank)}
    return Glm4MoeLiteLayer(arch, index)


@pytest.mark.parametrize("chips, form", [(2, "auto"), (16, "auto"), (2, "interpret")],
                         ids=["two-jnp", "sixteen-jnp", "two-pallas"])
def test_the_ranks_parts_add_up_to_the_uncut_layer(monkeypatch, chips, form):
    """Each rank computes latent attention whole, the shared expert whole and
    its own experts' part of the routed sum. The routed parts of all the ranks
    (two holding eight experts each, or sixteen holding one), with attention's
    residual and the shared expert (what every rank computes alike) counted
    once, equal the uncut reference's layer."""
    monkeypatch.setattr(cells, "_PALLAS_MODE", form)
    rng = np.random.default_rng(20)
    x = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    seam = np.zeros((B, T), bool)
    seam[:, 11] = True
    seg = jnp.cumsum(jnp.asarray(seam, jnp.int32), axis=1)
    whole = jax.jit(lambda k: layer_of(0, 1, chips=1).init(k, x, seg))(jax.random.key(2))["params"]
    whole = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), whole)
    uncut = {k: v for k, v in ARCH.items() if k != "expert_parallel"} | {"n_routed_experts": 16}
    u = reference.norm(x, whole["input_norm"]["scale"], 1e-5)
    after = x + reference.latent_attention(u, jnp.asarray(seam), whole["attention"], uncut)
    h = reference.norm(after, whole["post_norm"]["scale"], 1e-5)
    mixed, ref_route = reference.experts(h, whole["experts"], uncut)
    per_expert = ("w_gate", "w_in", "w_out")
    no_experts = {**whole["experts"], **{k: whole["experts"][k][:0] for k in per_expert}}
    shared, _ = reference.experts(h, no_experts, uncut | {"n_routed_experts": 0})
    routed_parts, rows = jnp.zeros_like(x), 0.0
    for rank in range(chips):
        held = slice(16 // chips * rank, 16 // chips * (rank + 1))
        mine = {**whole, "experts": {
            k: (v[held] if k in per_expert else v) for k, v in whole["experts"].items()}}
        out, route = jax.jit(
            lambda p, r=rank: layer_of(r, 1, chips).apply({"params": p}, x, seg))(mine)
        assert np.array_equal(np.sort(route["choice"], -1), np.sort(ref_route["choice"], -1))
        routed_parts = routed_parts + (out - after - shared)
        rows += float(route["stats"]["rows"])
    close(after + shared + routed_parts, after + mixed, 3e-4)
    assert float(jnp.abs(routed_parts).max()) > 0.1 and float(jnp.abs(shared).max()) > 0.01
    assert rows == B * T * 4
    all_held, _ = layer_of(0, 1, chips=1).apply({"params": whole}, x, seg)
    close(all_held, after + mixed, 3e-4)


def test_the_dense_layer_is_whole_on_every_rank():
    """Layer 0 has no experts: every rank computes the same attention and the
    same SwiGLU MLP, the reference's; its record holds no routing."""
    rng = np.random.default_rng(21)
    x = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    seam = np.zeros((B, T), bool)
    seam[:, 7] = True
    seg = jnp.cumsum(jnp.asarray(seam, jnp.int32), axis=1)
    p = layer_of(0, 0, chips=2).init(jax.random.key(3), x, seg)["params"]
    p = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), p)
    assert set(p) == {"input_norm", "post_norm", "attention", "gate_proj", "up_proj", "down_proj"}
    assert p["gate_proj"]["kernel"].shape == p["up_proj"]["kernel"].shape == (64, 160)
    outs = [layer_of(rank, 0, chips=2).apply({"params": p}, x, seg) for rank in (0, 1)]
    close(outs[0][0], outs[1][0], 0)
    assert set(outs[0][1]) == {"attn-pairs", "attn-tiles-run", "attn-tiles-band", "attn-bwd-steps"}
    u = reference.norm(x, p["input_norm"]["scale"], 1e-5)
    after = x + reference.latent_attention(u, jnp.asarray(seam), p["attention"], ARCH)
    h = reference.norm(after, p["post_norm"]["scale"], 1e-5)
    want = after + reference.swiglu(
        h, *(p[leaf]["kernel"] for leaf in ("gate_proj", "up_proj", "down_proj")))
    close(outs[0][0], want, 3e-4)
    by_line = (jax.nn.silu(h @ p["gate_proj"]["kernel"]) * (h @ p["up_proj"]["kernel"])
               ) @ p["down_proj"]["kernel"]
    close(outs[0][0], after + by_line, 3e-4)


def test_the_expert_block_is_the_shared_one_at_this_familys_fields(family, actor):
    """``swiglu`` experts under the sigmoid router with bias and scale 1.8 and
    an ungated shared expert: no leaf of another family's block."""
    experts = actor["params"]["layer1"]["experts"]
    assert set(experts) == {"router", "router_bias", "w_gate", "w_in", "w_out", "shared_gate",
                            "shared_in", "shared_out"}
    assert experts["router"].shape == (64, 16) and experts["w_gate"].shape == (8, 64, 48)
    rng = np.random.default_rng(22)
    h = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    got, route = reference.experts(h, experts, ARCH)
    s = 1 / (1 + np.exp(-np.asarray(h[0, 3] @ experts["router"], np.float64)))
    chosen = np.argsort(-(s + np.asarray(experts["router_bias"])), kind="stable")[:4]
    assert set(chosen) == set(np.asarray(route["choice"][0, 3]))
    silu = lambda a: a / (1 + np.exp(-a))  # noqa: E731
    want = (silu(h[0, 3] @ experts["shared_gate"]["kernel"]) * (h[0, 3] @ experts["shared_in"]["kernel"])
            ) @ experts["shared_out"]["kernel"]
    for e in chosen:
        if 8 <= e < 16:  # rank 1 of 2
            w = 1.8 * s[e] / s[chosen].sum()
            want = want + w * (silu(h[0, 3] @ experts["w_gate"][e - 8]) * (h[0, 3] @ experts["w_in"][e - 8])
                               ) @ experts["w_out"][e - 8]
    close(got[0, 3], want, 1e-4)


# ------------------------------------------------------------------ the refusals
REFUSED = {
    "an off-policy algorithm": (dict(algo="SAC"), "on-policy"),
    "no arch": (dict(arch=None), "needs arch"),
    "unequal query and value head sizes": (dict(arch={**ARCH, "v_head_dim": 16}),
                                           "is not v_head_dim = 16"),
    "a wider unrotated part": (dict(arch={**ARCH, "qk_nope_head_dim": 56}),
                               "unequal query/value head sizes is not built"),
    "grouped key/value heads": (dict(arch={**ARCH, "num_key_value_heads": 2}), "multi-head"),
    "queries without a latent": (dict(arch={**ARCH, "q_lora_rank": None}), "q_lora_rank"),
    "an odd rotated part": (dict(arch={**ARCH, "qk_nope_head_dim": 25, "qk_rope_head_dim": 7}),
                            "rotate-half"),
    "a partial rotation": (dict(arch={**ARCH, "partial_rotary_factor": 0.5}), "whole rotated part"),
    "rotary scaling": (dict(arch={**ARCH, "rope_scaling": {"type": "yarn"}}), "rotary scaling"),
    "attention bias": (dict(arch={**ARCH, "attention_bias": True}), "no bias"),
    "multi-token prediction": (dict(arch={**ARCH, "num_nextn_predict_layers": 1}),
                               "multi-token prediction"),
    "another choice rule": (dict(arch={**ARCH, "topk_method": "greedy"}), "topk_method"),
    "router groups": (dict(arch={**ARCH, "n_group": 4, "topk_group": 2}), "group stage"),
    "unnormalised weights": (dict(arch={**ARCH, "norm_topk_prob": False}), "normalised"),
    "no shared expert": (dict(arch={**ARCH, "n_shared_experts": 0}), "shared expert"),
    "no expert layer": (dict(arch={**ARCH, "first_k_dense_replace": 3}), "expert layer has to follow"),
    "a share that does not add up": (dict(arch={**ARCH, "n_routed_experts": 4}),
                                     "is not the published"),
    "a sequence mesh": (dict(mesh_seq=2, attention_impl="ring"), "sequence-parallel"),
    **{f"no {key}": (dict(arch={k: v for k, v in ARCH.items() if k != key}), f"lacks.*{key}")
       for key in GLM4_MOE_LITE_ARCH_KEYS},
}


@pytest.mark.parametrize("change, message", REFUSED.values(), ids=REFUSED.keys())
def test_what_the_family_refuses(change, message):
    with pytest.raises(AssertionError, match=message):
        config(**change)


# ------------------------------------- the programs the benchmark already had
TRANSFORMER = dict(algo="PPO", model="transformer", hidden_size=64, n_heads=4, n_layers=2,
                   attention_impl="flash", obs_shape=(OBS,), action_space=ACTIONS, seq_len=T,
                   batch_size=B)
# sha256 of each family's update program as the commit before this family
# (5ca21d4) lowered it at its test module's tiny widths: StableHLO without
# locations, on the CPU (no Mosaic body). A PR that changes one of these
# programs on purpose records the new digest here and says so. PR 40:
# ``smallthinker`` and ``qwen3_next`` recorded anew — their ``diag`` gained the
# counter ``attn-bwd-steps-*``; with the counter left out of
# ``obs/learn.ATTENTION_COUNTERS`` both lowered to the digests they had
# (af779e4d…, c6f9c2a0…). PR 41: all five recorded anew — the update's tail
# changed in every program (the guard a select and no ``cond``, the module
# norms from the raw gradients, the diagnostics' sums under ``opt_update``);
# no model file was touched. PR 42: ``glm4_moe_lite``'s own joins them, taken on
# the parent commit (4aa4e3c) before any model file moved; the five above it
# were not edited. PR 43: ``lfm2_moe``'s own joins them as that PR left it; the
# six above it hold through the trunk's new statement of a carry
# (``backbone.tail``) and ``GQAttention.qk_norm_zero_centered``. PR 46:
# ``evabyte``'s own joins them as that PR left it; the seven above it hold
# through the logsumexp output of the rows' walk (``flash_attention_lse``: a new
# caller's path), ``unroll_routed`` handing a family without experts its
# records back and ``route_scalars`` skipping records without ``stats``. PR 48:
# ``evabyte`` recorded anew on purpose (95908026… before) — its chunk pooling has
# a backward of its own (``models/evabyte._summaries_bwd``: the members'
# gradients by the inverse of the gather, no scatter); no other file of the
# program was touched and the seven above it hold. PR 51: ``ling_flash``'s own
# joins them as that PR left it; the eight above it hold through ``MLAttention``'s
# move into ``models/layers.py`` with its two new fields (``q_rank=None``,
# ``head_gate``), ``moe.route``'s group stage (``n_group`` 1 is the plain choice),
# ``route_stats``' optional counter, ``flash_attention_tpu``'s padding of unequal
# head sizes and the walk over spans taking another rule's span
# (``gated_delta._chunked_jnp(span_fn=)``).
BEFORE = {
    "transformer": "8b9c8c0764ab242a3da73822e1ea003dae077495da61006398cca64864b2a7d9",
    "granite_hybrid": "17b496ea1eb174484e74ab740489fb01f84614423ea1b2bb87c5bfa2d17b5e29",
    "nemotron_h": "4e1709b9bd0a4ebc69dc7e12d43e7acf242dd683ff00f104163cc48fa097514a",
    "smallthinker": "f267e0c3ebc64d3cdc8240c7bd886a68b27fe6e183e7360f33b4acb96b571f8a",
    "qwen3_next": "409273e605b448ff61c5f707fdc53a715cbabc46767dd103aad7ace85f409026",
    "glm4_moe_lite": "8adba8ef85184cacb1a698214cc811f36182bea9c6f1867eb6ac2884f75959dd",
    "lfm2_moe": "15b0774f9969df58a3b6f2286eed0dddc3c44289ea7c0775688cc38bd04dc8b5",
    "evabyte": "3491e6fa66a6cf7e9fe4a11af0bd923006d94d3d93c4b5ffe859029d89a90aba",
    "ling_flash": "d8ef1332738c81022b04c2352c4f6eb55f2efffedf75a48b50f0f34ccab27a31",
}


def update_program_digest(params: dict) -> str:
    """The update program (diagnostics and guard on, as every cell runs it)
    lowered from shapes: no weight is made."""
    cfg = Config.from_dict({**params, "learn_diag": True, "update_guard": True})
    fam = build_family(cfg)
    state = jax.eval_shape(lambda k: make_train_state(cfg, fam, k), jax.random.key(0))
    step = get_algo(cfg.algo).make_train_step(cfg, fam)
    lay = BatchLayout.from_config(cfg)
    batch = jax.eval_shape(lambda: Batch.zeros(
        cfg.batch_size, cfg.seq_len, cfg.obs_shape, cfg.action_space, cfg.hidden_size,
        hx_width=lay.hx, cx_width=lay.cx))
    key = jax.eval_shape(lambda: jax.random.key(1))
    return hashlib.sha256(jax.jit(step).lower(state, batch, key).as_text().encode()).hexdigest()


def family_params(model: str) -> dict:
    if model == "transformer":
        return TRANSFORMER
    return importlib.import_module(f"test_{model}").PARAMS


@pytest.mark.parametrize("model", BEFORE)
def test_the_benchmarks_five_update_programs_lower_as_before(model):
    digest = update_program_digest(family_params(model))
    assert digest == BEFORE[model], f"{model}'s update program now lowers to {digest}"
