"""The ling_flash family (``tpu_rl/models/ling_flash.py``) at tiny widths on the
CPU against the benchmark's plain reference
(``benchmarks/reference/ling_flash.py``: the per-channel delta rule step by
step, dense masked latent attention, the group-limited choice by sorting, the
held experts as a loop under a mask): outputs, the PPO loss and every gradient
with identical choices asserted; the ranks' parts of a layer adding up to the
uncut one; a routing case in which the group limit changes the choice; latent
attention without a query latent at unequal head sizes and the kernels' padded
call against ``full_attention``; acting against the unroll over both kinds of
state; the counters; the refusals. Published layers 1-7 of a stack in groups
of six (dense + KDA, three KDA, latent, two KDA), chunks of 8 steps in
sub-blocks of 4 and spans of 2, 16 routed experts in 4 groups over 4 ranks
(rank 1 holds experts 4-7: group 1), 3 chosen per token from 2 kept groups."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import ling_flash as reference
from benchmarks.reference import losses as ref_losses
from test_granite_hybrid import close, make_batch
from test_nemotron_h import ref_ppo_loss, same_choices
from tpu_rl.algos.base import TrainState, rmsprop
from tpu_rl.algos.ppo import make_train_step, policy_outputs, policy_outputs_routed
from tpu_rl.algos.registry import get_algo
from tpu_rl.config import KDA_SUB_BLOCK, Config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.models import cells, ling_flash
from tpu_rl.models.families import ModelFamily, build_family
from tpu_rl.models.layers import MLAttention
from tpu_rl.models.ling_flash import LingFlashLayer
from tpu_rl.ops import gated_delta, kda, moe
from tpu_rl.parallel import sequence
from tpu_rl.types import Batch

SHARE = dict(published_n_routed_experts=16, chips=4, rank=1)
ARCH = dict(
    hidden_size=64, num_hidden_layers=7, layer_group_size=6, layer_offset=1,
    first_k_dense_replace=1, rms_norm_eps=1e-6, num_attention_heads=4, num_key_value_heads=4,
    head_dim=16, q_lora_rank=None, kv_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, rope_theta=6000000, short_conv_kernel_size=4, kda_safe_gate=True,
    kda_lower_bound=-5, intermediate_size=96, moe_intermediate_size=48,
    moe_shared_expert_intermediate_size=40, num_experts=4, num_experts_per_tok=3, n_group=4,
    topk_group=2, score_function="sigmoid", moe_router_enable_expert_bias=True,
    routed_scaling_factor=2.5, norm_topk_prob=True, expert_parallel=SHARE,
)
T, B, OBS, ACTIONS = 32, 2, 6, 3
CHUNK = 8
PARAMS = dict(algo="PPO", model="ling_flash", arch=ARCH, obs_shape=(OBS,),
              action_space=ACTIONS, seq_len=T, batch_size=B)
KINDS = ling_flash.layer_kinds(ARCH)
PRODUCTION_SUB = kda.SUB  # read at import: the module's fixture patches it afterwards


def config(**kw) -> Config:
    return Config.from_dict({**PARAMS, **kw})


@pytest.fixture(scope="module", autouse=True)
def small_chunks():
    """Chunks of 8 steps in sub-blocks of 4 and spans of 2: a 32-step window is
    two spans of two chunks, so every seam of a test batch falls inside some
    chunk and most inside a sub-block."""
    before = ling_flash.CHUNK, kda.SUB, gated_delta.SPAN_CHUNKS
    ling_flash.CHUNK, kda.SUB, gated_delta.SPAN_CHUNKS = CHUNK, 4, 2
    yield
    ling_flash.CHUNK, kda.SUB, gated_delta.SPAN_CHUNKS = before


@pytest.fixture(params=["auto", "interpret"], ids=["jnp", "pallas"])
def kernel_form(request, monkeypatch):
    """The form of the experts' products a test's programs are traced in."""
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
def test_the_stack_is_the_published_layers_one_to_seven():
    assert KINDS == [("kda", True), ("kda", False), ("kda", False), ("kda", False),
                     ("mla", False), ("kda", False), ("kda", False)]
    assert ling_flash.layer_kinds({**ARCH, "layer_offset": 0, "num_hidden_layers": 6}) == [
        ("kda", True), *[("kda", False)] * 4, ("mla", False)]
    assert KDA_SUB_BLOCK == PRODUCTION_SUB == 16  # config.py's check of the bound, the scan's sub-block


def test_outputs_and_choices_match_the_reference_in_float32(actor, system, plain):
    batch = make_batch(2)
    assert batch["is_fir"].sum() >= 4
    value, logits, routes = system(actor, batch)
    ref_value, ref_logits, ref_routes = plain(actor, batch)
    assert len(routes) == 6 and routes[0]["choice"].shape == (B, T, 3)
    assert same_choices(routes, ref_routes)
    close(logits, ref_logits, 1e-4)
    close(value, ref_value, 1e-4)
    stats = routes[0]["stats"]
    held = float(stats["held-share"])
    assert 0.05 < held < 0.6 and float(stats["rows"]) == round(held * B * T * 3)
    # a token without group 1 among its two kept groups sends nothing here
    assert 0.2 < float(stats["group-hit-share"]) < 0.9
    assert float(stats["no-held-share"]) >= 1.0 - float(stats["group-hit-share"]) - 1e-6


def test_bfloat16_matches_the_reference_on_the_systems_choices(actor, plain):
    fam = build_family(config(compute_dtype="bfloat16"))
    batch = make_batch(3)
    value, logits, routes = jax.jit(
        lambda p, b: policy_outputs_routed(fam, {"actor": p}, Batch.from_mapping(b))[2:])(actor, batch)
    ref_value, ref_logits, ref_routes = plain(actor, batch, [r["choice"] for r in routes])
    for got, want in ((logits, ref_logits), (value, ref_value)):
        # 16-wide heads, every leaf moved by 0.1 (A_log too: exp(A_log) up to 16 multiplies the
        # decay projection's rounding) and seven layers: a bf16 step is a far larger share here
        # than at the published widths — one value of 64 reads 0.106 of the largest, the rest
        # under 0.08; the reference with bf16-rounded operands and float32 results reads 0.026
        close(got, want, 0.15 * float(np.abs(want).max()))
        assert float(np.abs(np.asarray(got) - np.asarray(want)).max()) > 1e-6
    worst = 0.0
    for mine, theirs in zip(routes, ref_routes):
        differ = (np.sort(mine["choice"], -1) != np.sort(theirs["choice"], -1)).any(-1)
        assert differ.mean() < 0.25
        worst = max(worst, float(np.asarray(theirs["margin"])[differ].max(initial=0.0)))
    assert worst < 0.3


def test_ppo_loss_and_every_gradient_match_the_reference(family, actor, system, plain, kernel_form):
    """The train step's own loss and ``jax.grad`` of it against the reference
    forward under the reference loss, leaf by leaf: the router's weights get a
    gradient through the chosen scores, its bias gets none, on both sides."""
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
    routes = system(actor, batch)[2]
    assert float(scalars["moe-rows"]) == sum(float(r["stats"]["rows"]) for r in routes)
    assert float(scalars["moe-chunks"]) == 1.0
    np.testing.assert_allclose(
        float(scalars["moe-group-hit-share"]),
        np.mean([float(r["stats"]["group-hit-share"]) for r in routes]), rtol=1e-6)
    assert 0.0 <= float(scalars["kda-decay-floor-share"]) <= 1.0

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
        if "router_bias" in name:  # only the choice reads it
            assert float(jnp.abs(got).max()) == float(jnp.abs(want).max()) == 0, name
        else:
            assert float(jnp.abs(got).max()) > 0, name
    for leaf, layers in (
        ("router'", 6), ("router_bias", 6), ("w_gate", 6), ("w_in", 6), ("w_out", 6),
        ("shared_gate", 6), ("gate_proj", 1), ("down_proj", 1), ("input_norm", 7),
        ("post_norm", 7), ("in_proj_qkv", 6), ("['a_proj']", 6), ("in_proj_bz", 6),
        ("conv_weight", 6), ("A_log", 6), ("dt_bias", 6), ("norm_scale", 6), ("['q_proj']", 1),
        ("kv_a_proj", 1), ("kv_a_norm", 1), ("kv_b_proj", 1), ("g_proj", 1),
    ):
        assert sum(leaf in name for name in names) == layers, leaf
    assert not any("q_a_proj" in name or "q_b_proj" in name for name in names)


def layer_of(rank: int, kind: tuple, chips: int) -> LingFlashLayer:
    arch = {**ARCH, "num_experts": 16 // chips,
            "expert_parallel": dict(published_n_routed_experts=16, chips=chips, rank=rank)}
    return LingFlashLayer(arch, kind)


@pytest.mark.parametrize("mixer, chips, form", [
    ("kda", 4, "auto"), ("mla", 4, "auto"), ("kda", 16, "auto"), ("mla", 4, "interpret"),
], ids=["kda-four-jnp", "mla-four-jnp", "kda-sixteen-jnp", "mla-four-pallas"])
def test_the_ranks_parts_add_up_to_the_uncut_layer(monkeypatch, mixer, chips, form):
    """The share test. Each rank computes the whole mixer, the whole shared
    expert and its own experts' part of the routed sum under the group-limited
    router over all 16 experts in 4 groups. The routed parts of all the ranks
    (four holding a group each, or sixteen holding one expert each), with the
    mixer's residual and the shared expert counted once, equal the uncut
    reference's layer; a rank whose group a token did not keep adds nothing
    for it."""
    monkeypatch.setattr(cells, "_PALLAS_MODE", form)
    rng = np.random.default_rng(20)
    x = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    seam = np.zeros((B, T), bool)
    seam[:, 11] = True
    seg = jnp.cumsum(jnp.asarray(seam, jnp.int32), axis=1)
    kind = (mixer, False)
    carry = (jnp.zeros((B, 4, 16, 16)), jnp.zeros((B, 3, 192))) if mixer == "kda" else ()
    whole = jax.jit(lambda k: layer_of(0, kind, chips=1).init(k, x, seg, *carry))(
        jax.random.key(2))["params"]
    whole = jax.tree.map(lambda a: a + 0.1 * jnp.asarray(rng.standard_normal(a.shape), a.dtype), whole)
    uncut = {k: v for k, v in ARCH.items() if k != "expert_parallel"} | {"num_experts": 16}
    u = reference.norm(x, whole["input_norm"]["scale"], 1e-6)
    if mixer == "kda":
        after = x + reference.delta_attention(u, jnp.asarray(seam), whole["linear_attn"], uncut)
    else:
        after = x + reference.latent_attention(u, jnp.asarray(seam), whole["attention"], uncut)
    h = reference.norm(after, whole["post_norm"]["scale"], 1e-6)
    mixed, ref_route = reference.experts(h, whole["experts"], uncut)
    per_expert = ("w_gate", "w_in", "w_out")
    no_experts = {**whole["experts"], **{k: whole["experts"][k][:0] for k in per_expert}}
    shared, _ = reference.experts(h, no_experts, uncut | {"num_experts": 0})
    routed_parts, rows, hits = jnp.zeros_like(x), 0.0, 0.0
    for rank in range(chips):
        held = slice(16 // chips * rank, 16 // chips * (rank + 1))
        mine = {**whole, "experts": {
            k: (v[held] if k in per_expert else v) for k, v in whole["experts"].items()}}
        out, *_, route = jax.jit(
            lambda p, r=rank: layer_of(r, kind, chips).apply({"params": p}, x, seg, *carry))(mine)
        assert np.array_equal(np.sort(route["choice"], -1), np.sort(ref_route["choice"], -1))
        part = out - after - shared
        routed_parts = routed_parts + part
        rows += float(route["stats"]["rows"])
        hits += float(route["stats"]["group-hit-share"])
        # this rank's group among a token's kept ones, or no row from that token
        group = np.asarray(route["choice"]) // 4 == (16 // chips * rank) // 4
        assert float(jnp.abs(part)[~group.any(-1)].max(initial=0.0)) < 1e-5
    close(after + shared + routed_parts, after + mixed, 3e-4)
    assert float(jnp.abs(routed_parts).max()) > 0.1 and float(jnp.abs(shared).max()) > 0.01
    assert rows == B * T * 3
    # two of four groups kept a token: summed over a partition of the groups, 2
    assert abs(hits / (chips // 4) - 2.0) < 1e-5
    all_held, *_ = layer_of(0, kind, chips=1).apply({"params": whole}, x, seg, *carry)
    close(all_held, after + mixed, 3e-4)


# ------------------------------------------------------ the group-limited router
def test_the_group_limit_changes_the_choice_against_plain_top_k():
    """Eight experts in four groups of two, two groups kept, three chosen. The
    three largest scores lie in three groups; the groups' scores (sums of two)
    keep groups 0 and 3, so expert 2 — second largest of all — cannot be
    chosen, and the weights are formed over the chosen experts' unbiased
    scores."""
    logit = jnp.asarray([[2.0, 1.5, 2.4, -4.0, -1.0, -1.2, 2.6, 1.0]])
    u, kernel, bias = jnp.ones((1, 1)), logit, jnp.zeros((8,))
    plain_choice, _ = moe.route(u, kernel, bias, 3, 2.5)
    assert sorted(np.asarray(plain_choice)[0]) == [0, 2, 6]
    choice, weight, kept = moe.route(u, kernel, bias, 3, 2.5, "sigmoid", 4, 2, with_groups=True)
    assert sorted(np.asarray(kept)[0]) == [0, 3]
    assert list(np.asarray(choice)[0]) == [6, 0, 1]
    s = 1.0 / (1.0 + np.exp(-np.asarray(logit)[0]))
    np.testing.assert_allclose(
        np.asarray(weight)[0], 2.5 * s[[6, 0, 1]] / s[[6, 0, 1]].sum(), rtol=1e-6)
    ref_choice, margin = reference.group_limited_choice(jnp.asarray(s)[None], 3, 4, 2)
    assert list(np.asarray(ref_choice)[0]) == [6, 0, 1]
    group_scores = sorted([s[0] + s[1], s[2] + s[3], s[4] + s[5], s[6] + s[7]])
    np.testing.assert_allclose(float(margin[0]), min(
        s[1] - s[7], group_scores[2] - group_scores[1]), rtol=1e-5)
    # the bias moves the choice and not the weights
    biased, w2 = moe.route(u, kernel, bias.at[7].set(0.5), 3, 2.5, "sigmoid", 4, 2)
    assert list(np.asarray(biased)[0]) == [7, 6, 0]
    np.testing.assert_allclose(
        np.asarray(w2)[0], 2.5 * s[[7, 6, 0]] / s[[7, 6, 0]].sum(), rtol=1e-6)
    # one group is the plain choice
    same, _ = moe.route(u, kernel, bias, 3, 2.5, "sigmoid", 1, 1)
    assert np.array_equal(same, plain_choice)


def test_a_seeded_router_agrees_with_the_references_sort():
    rng = np.random.default_rng(31)
    u = jnp.asarray(rng.standard_normal((200, 12)), jnp.float32)
    kernel = jnp.asarray(rng.standard_normal((12, 32)), jnp.float32)
    bias = jnp.asarray(0.05 * rng.standard_normal(32), jnp.float32)
    choice, weight, kept = moe.route(u, kernel, bias, 6, 2.5, "sigmoid", 8, 3, with_groups=True)
    s = jax.nn.sigmoid(u @ kernel)
    ref_choice, _ = reference.group_limited_choice(s + bias, 6, 8, 3)
    assert np.array_equal(np.sort(choice, -1), np.sort(ref_choice, -1))
    assert (np.asarray(choice)[..., None] // 4 == np.asarray(kept)[:, None, :]).any(-1).all()
    plain_choice, _ = moe.route(u, kernel, bias, 6, 2.5)
    assert (np.sort(plain_choice, -1) != np.sort(choice, -1)).any(-1).mean() > 0.3
    stats = moe.route_stats(choice[None], 8, 4, 256, kept, 4)  # experts 8-11: group 2
    np.testing.assert_allclose(
        float(stats["group-hit-share"]), (np.asarray(kept) == 2).any(-1).mean(), rtol=1e-6)


# ------------------------------------ latent attention at the two new settings
def latent(**fields) -> MLAttention:
    return MLAttention(**{**dict(
        hidden=64, heads=4, q_rank=None, kv_rank=24, nope_dim=16, rope_dim=8, v_dim=16,
        rope_theta=6e6, eps=1e-6, head_gate=True), **fields})


def test_queries_without_a_latent_and_unequal_heads_against_the_reference():
    rng = np.random.default_rng(41)
    u = jnp.asarray(rng.standard_normal((B, T, 64)), jnp.float32)
    seam = np.zeros((B, T), bool)
    seam[0, [7, 20]] = True
    seg = jnp.cumsum(jnp.asarray(seam, jnp.int32), axis=1)
    layer = latent()
    p = layer.init(jax.random.key(3), u, seg)["params"]
    assert set(p) == {"q_proj", "g_proj", "kv_a_proj", "kv_a_norm", "kv_b_proj", "o_proj"}
    assert p["q_proj"]["kernel"].shape == (64, 4 * 24) and p["kv_b_proj"]["kernel"].shape == (24, 4 * 32)
    assert p["o_proj"]["kernel"].shape == (4 * 16, 64)
    got = layer.apply({"params": p}, u, seg)
    arch = {**ARCH, "num_attention_heads": 4}
    close(got, reference.latent_attention(u, jnp.asarray(seam), p, arch), 2e-5)
    with_latent = latent(q_rank=12, head_gate=False).init(jax.random.key(3), u, seg)["params"]
    assert {"q_a_proj", "q_a_norm", "q_b_proj"} <= set(with_latent) and "g_proj" not in with_latent


def test_the_kernels_padded_call_equals_full_attention_at_unequal_heads(monkeypatch):
    """On a TPU ``flash_attention_tpu`` pads 24-wide queries and keys and
    16-wide values to 128 and slices the output: the same numbers and
    gradients as ``full_attention`` at the two sizes (the splash kernels in the
    interpreter, T 256 in tiles of 128)."""
    rng = np.random.default_rng(42)
    Tk = 256
    q, k = (jnp.asarray(rng.standard_normal((1, Tk, 2, 24)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.standard_normal((1, Tk, 2, 16)), jnp.float32)
    seam = np.zeros((1, Tk), bool)
    seam[0, [50, 130]] = True
    seg = jnp.cumsum(jnp.asarray(seam, jnp.int32), axis=1)
    pos = jnp.broadcast_to(jnp.arange(Tk, dtype=jnp.int32), (1, Tk))
    cot = jnp.asarray(rng.standard_normal((1, Tk, 2, 16)), jnp.float32)
    want_fn = lambda q, k, v: jnp.sum(cot * sequence.full_attention(  # noqa: E731
        q, k, v, pos, seg, sm_scale=24 ** -0.5))
    want = jax.grad(want_fn, argnums=(0, 1, 2))(q, k, v)
    seen = []
    inner = sequence._splash_mha

    def interpreted(q, k, v, seg, **kw):
        seen.append((q.shape, v.shape))
        return inner(q, k, v, seg, **{**kw, "interpret": True})

    monkeypatch.setattr(sequence, "_splash_mha", interpreted)
    monkeypatch.setattr(cells, "_program_devices", lambda: ("tpu", 1))
    got_fn = lambda q, k, v: jnp.sum(cot * sequence.flash_attention_tpu(  # noqa: E731
        q, k, v, pos, seg, sm_scale=24 ** -0.5))
    out = sequence.flash_attention_tpu(q, k, v, pos, seg, sm_scale=24 ** -0.5)
    assert out.shape == v.shape and seen[0] == ((1, Tk, 2, 128), (1, Tk, 2, 128))
    close(out, sequence.full_attention(q, k, v, pos, seg, sm_scale=24 ** -0.5), 2e-5)
    for g, w in zip(jax.grad(got_fn, argnums=(0, 1, 2))(q, k, v), want):
        assert g.shape == w.shape
        close(g, w, 5e-5)
    # equal sizes are handed over as they are
    seen.clear()
    sequence.flash_attention_tpu(q, k, q, pos, seg)
    assert seen[0] == ((1, Tk, 2, 24), (1, Tk, 2, 24))


# ------------------------------------------------------------ acting, the carry
def test_acting_step_by_step_equals_the_unroll(family, actor):
    """``family.act`` over the KDA layers' states and convolution tails and the
    latent layer's ring, with the worker's zeroing at episode starts: an
    episode of 21 steps after one of 11, across chunks, sub-blocks and spans."""
    batch = make_batch(9, firsts=(0, 11))
    logits = jax.jit(lambda p, b: policy_outputs_routed(
        family, {"actor": p}, Batch.from_mapping(b))[3])(actor, batch)
    assert family.carry_widths == (6 * (4 * 16 * 16 + 3 * 192), T * 32 + 1)
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


def test_the_unroll_hands_back_the_carry_acting_would_reach(family, actor):
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
    still = [jax.tree_util.keystr(path) for path, delta in jax.tree.leaves_with_path(moved)
             if delta == 0]
    assert all("router_bias" in name for name in still), still


REFUSED = {
    "an off-policy algorithm": (dict(algo="SAC"), "on-policy"),
    "no arch": (dict(arch=None), "needs arch"),
    "a key left out": (dict(arch={k: v for k, v in ARCH.items() if k != "kda_lower_bound"}),
                       "lacks"),
    "no expert layer": (dict(arch={**ARCH, "first_k_dense_replace": 7}), "expert layer has to follow"),
    "a group of one": (dict(arch={**ARCH, "layer_group_size": 1}), "layer_group_size"),
    "a query latent": (dict(arch={**ARCH, "q_lora_rank": 32}), "queries have no latent"),
    "grouped key heads": (dict(arch={**ARCH, "num_key_value_heads": 2}), "multi-head"),
    "fewer linear key heads": (dict(arch={**ARCH, "num_kv_heads_for_linear_attn": 2}),
                               "num_kv_heads_for_linear_attn"),
    "a low-rank gate": (dict(arch={**ARCH, "no_kda_lora": False}), "no_kda_lora"),
    "a group norm over heads": (dict(arch={**ARCH, "group_norm_size": 4}), "group_norm_size"),
    "an unbounded gate": (dict(arch={**ARCH, "kda_safe_gate": False}), "bounded"),
    "a bound of zero": (dict(arch={**ARCH, "kda_lower_bound": 0}), "bound below 0"),
    "a bound the sub-block cannot hold": (dict(arch={**ARCH, "kda_lower_bound": -6}),
                                          "past float32"),
    "clamped experts": (dict(arch={**ARCH, "expert_swiglu_limit_list": [0, 0, 0, 4, 0, 0, 0, 0]}),
                        "clamped"),
    "rotary scaling": (dict(arch={**ARCH, "rope_scaling": {"type": "yarn"}}), "rope_scaling"),
    "a softmax router": (dict(arch={**ARCH, "score_function": "softmax"}), "sigmoid"),
    "unnormalised weights": (dict(arch={**ARCH, "norm_topk_prob": False}), "normalised"),
    "groups that do not divide": (dict(arch={**ARCH, "n_group": 3, "topk_group": 2}),
                                  "whole number of n_group"),
    "more groups kept than there are": (dict(arch={**ARCH, "topk_group": 5}), "topk_group 5"),
    "more experts a token than the kept groups hold": (
        dict(arch={**ARCH, "topk_group": 1, "num_experts_per_tok": 5}), "kept groups"),
    "a rank across two groups": (
        dict(arch={**ARCH, "n_group": 3, "num_experts": 6, "expert_parallel": dict(
            published_n_routed_experts=24, chips=4, rank=1)}), "whole groups or lies inside"),
    "a share that does not add up": (dict(arch={**ARCH, "num_experts": 8}), "is not the published"),
    "multi-token prediction": (dict(arch={**ARCH, "num_nextn_predict_layers": 1}),
                               "multi-token"),
    "a sequence mesh": (dict(mesh_seq=2, attention_impl="ring"), "sequence-parallel"),
}


@pytest.mark.parametrize("change, message", REFUSED.values(), ids=REFUSED.keys())
def test_what_the_family_refuses(change, message):
    with pytest.raises(AssertionError, match=message):
        config(**change)


def test_glm_still_refuses_what_only_this_family_builds():
    from test_glm4_moe_lite import ARCH as GLM, PARAMS as GLM_PARAMS

    for change, message in (({"q_lora_rank": None}, "q_lora_rank null"),
                            ({"v_head_dim": GLM["v_head_dim"] // 2}, "unequal query/value"),
                            ({"n_group": 2, "topk_group": 1}, "group stage")):
        with pytest.raises(AssertionError, match=message):
            Config.from_dict({**GLM_PARAMS, "arch": {**GLM, **change}})


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
    assert {"kda", "kda_scan", "mla", "attn_full", "attn_rope", "moe_experts"} <= paths
    assert not {"moe_gmm_pallas", "gdn_scan", "gdn_pallas", "ssd_scan", "attn_window"} & paths
    text = lowered.as_text(debug_info=True)
    for scope in ("/kda/linear_attn/", "kda_in", "kda_conv", "kda_gate", "kda/linear_attn/kda_scan",
                  "kda_out", "/mla/attention/", "mla_down", "mla_up", "mla_o", "/mlp/", "/moe/",
                  "moe_route/", "moe_dispatch/", "moe_combine/", "experts._add_shared/moe_shared",
                  "opt_update"):
        assert scope in text, scope
    assert "gdn_conv" not in text and "ssd_conv" not in text
    monkeypatch.setattr(cells, "_PALLAS_MODE", "interpret")
    assert {"moe_experts", "moe_gmm_pallas", "kda_scan"} <= set(program_paths(lower())["paths"])


def test_the_counters_reach_the_diagnostics(actor, system):
    from tpu_rl.obs import learn

    batch = make_batch(16, firsts=(13,))
    routes = system(actor, batch)[2]
    # the latent layer is the fourth expert layer; the dense layer's share rides with the first
    assert [set(r.get("attn-pairs", {})) for r in routes] == [
        set(), set(), set(), {"global"}, set(), set()]
    assert ["kda-decay-floor-share" in r for r in routes] == [True, True, True, False, True, True]
    scalars = learn.attention_scalars(routes)
    assert set(scalars) == {
        f"attn-{what}-global" for what in ("pairs", "tiles-run", "tiles-band", "bwd-steps")}
    fir = batch["is_fir"][..., 0] > 0
    episode = np.cumsum(fir, axis=1)
    kept = sum(int(((e[:, None] == e[None, :]) & np.tri(T, dtype=bool)).sum()) for e in episode)
    assert float(scalars["attn-pairs-global"]) == kept
    routed = learn.route_scalars(routes)
    assert {"moe-rows", "moe-group-hit-share", "kda-decay-floor-share"} <= set(routed)
    # every gate at the bound reads 1 over the six layers, none 0
    assert 0.0 <= float(routed["kda-decay-floor-share"]) <= 1.0
    stuck = jax.tree_util.tree_map_with_path(
        lambda path, x: x + 50.0 if "dt_bias" in jax.tree_util.keystr(path) else x, actor)
    at_floor = learn.route_scalars(system(stuck, batch)[2])["kda-decay-floor-share"]
    assert float(at_floor) > 0.9
    assert np.isfinite(np.asarray(system(stuck, batch)[1])).all()
