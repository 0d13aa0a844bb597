"""Model-family registry: algo name -> modules + pure init/act/unroll fns.

Replaces the reference's ``module_switcher`` class table
(``/root/reference/main.py:98-110``) with a declarative registry. Each family
bundles the Flax modules with *pure functions* used by workers (single-step
``act`` with explicit RNG) and learners (sequence ``unroll``), so every consumer
jits against plain ``(params, arrays)`` signatures.
"""

from __future__ import annotations

import importlib
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from tpu_rl.config import ARCH_CHECKS, Config
from tpu_rl.ops import distributions as D
from tpu_rl.models.policies import (
    ContinuousActorCritic,
    DiscreteActorCritic,
    SACContinuousActor,
    SACContinuousTwinCritic,
    SACDiscreteActor,
    SACDiscreteTwinCritic,
)

Params = Any


@dataclass(frozen=True)
class ModelFamily:
    """One algorithm's model bundle.

    ``act(params, obs, h, c, key)`` mirrors the reference worker step contract
    (``/root/reference/agents/worker.py:105-123``): returns
    ``(action, behavior_logits, log_prob, h', c')`` where ``action`` is a
    float vector ((1,) index for discrete, (A,) for continuous), ``logits`` is
    the (A,) log-softmax (zeros for Gaussian policies, ``models.py:46-49``),
    and ``log_prob`` is (1,) discrete / (A,) per-dim continuous.
    """

    algo: str
    continuous: bool
    separate: bool
    actor: nn.Module
    critic: nn.Module | None
    obs_dim: int
    n_actions: int
    hidden: int
    act: Callable[..., tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]] = (
        field(repr=False, default=None)
    )
    # Deterministic acting for evaluation: ``act_greedy(params, obs, h, c)
    # -> (action, h', c')``. Continuous families return the distribution mean
    # (already tanh-squashed); discrete evaluation argmaxes the logits that
    # ``act`` returns, so only continuous families set this.
    act_greedy: Callable[..., tuple[jax.Array, jax.Array, jax.Array]] | None = field(
        repr=False, default=None
    )
    # Widths of the worker-side acting carry (h, c). LSTM: (hidden, hidden).
    # Transformer: (obs-history window, step counter).
    act_carry_widths: tuple[int, int] | None = None
    # Whether the per-step carry must be stored into the batch (LSTM training
    # inits from seq-step-0 states; transformers ignore the carry, so
    # shipping it would waste DCN bandwidth and shm).
    store_carry: bool = True
    # Families with sparse-expert layers: ``(actor_params, obs, carry0,
    # firsts) -> (the unroll's outputs, each expert layer's routing)``.
    route_unroll: Callable[..., tuple[tuple, list]] | None = field(repr=False, default=None)

    @property
    def carry_widths(self) -> tuple[int, int]:
        return self.act_carry_widths or (self.hidden, self.hidden)

    # -------------------------------------------------------------- builders
    def init_params(self, key: jax.Array, seq_len: int = 2) -> Params:
        """Initialize the full parameter tree: ``{"actor": ...}`` for
        shared-torso families, ``{"actor": ..., "critic": ...}`` for SAC."""
        obs = jnp.zeros((1, seq_len, self.obs_dim))
        firsts = jnp.zeros((1, seq_len, 1))
        carry = (jnp.zeros((1, self.hidden)), jnp.zeros((1, self.hidden)))
        ka, kc = jax.random.split(key)
        params = {"actor": self.actor.init(ka, obs, carry, firsts)}
        if self.critic is not None:
            if self.continuous:
                act = jnp.zeros((1, seq_len, self.n_actions))
                params["critic"] = self.critic.init(kc, obs, act, carry, firsts)
            else:
                params["critic"] = self.critic.init(kc, obs, carry, firsts)
        return params

    # --------------------------------------------------------------- applies
    def actor_unroll(self, actor_params, obs, carry0, firsts):
        return self.actor.apply(actor_params, obs, carry0, firsts)

    def critic_unroll(self, critic_params, *args):
        assert self.critic is not None
        return self.critic.apply(critic_params, *args)


# ---------------------------------------------------------------- act fns
def _act_discrete_ac(actor: DiscreteActorCritic, params, obs, h, c, key):
    logits, _v, (h2, c2) = actor.apply(params["actor"], obs, (h, c), method="act")
    a = D.categorical_sample(key, logits)
    log_prob = D.categorical_log_prob(logits, a)
    return a[..., None].astype(jnp.float32), logits, log_prob[..., None], h2, c2


def _act_continuous_ac(actor: ContinuousActorCritic, params, obs, h, c, key):
    mu, std, _v, (h2, c2) = actor.apply(params["actor"], obs, (h, c), method="act")
    a = D.normal_sample(key, mu, std)
    log_prob = D.normal_log_prob(mu, std, a)
    return a, jnp.zeros_like(mu), log_prob, h2, c2


def _greedy_continuous_ac(actor: ContinuousActorCritic, params, obs, h, c):
    mu, _std, _v, (h2, c2) = actor.apply(params["actor"], obs, (h, c), method="act")
    return mu, h2, c2


def _greedy_sac_continuous(actor, params, obs, h, c):
    mu, _log_std, (h2, c2) = actor.apply(params["actor"], obs, (h, c), method="act")
    return jnp.tanh(mu), h2, c2


def _act_sac_discrete(actor: SACDiscreteActor, params, obs, h, c, key):
    logits, (h2, c2) = actor.apply(params["actor"], obs, (h, c), method="act")
    a = D.categorical_sample(key, logits)
    log_prob = D.categorical_log_prob(logits, a)
    return a[..., None].astype(jnp.float32), logits, log_prob[..., None], h2, c2


def _act_transformer(
    actor, ctx: int, n_layers: int, n_heads: int, hidden: int,
    params, obs, h, c, key,
):
    """KV-cached incremental acting for the transformer family: O(ctx·d + d²)
    per env step instead of the O(ctx²·d) full-window recompute
    (``_act_transformer_window``, kept as the equivalence oracle).

    The carry reuses the (hx, cx) plumbing: ``h`` is the flattened per-layer
    K caches (n_layers · ctx · hidden), ``c`` is the flattened V caches plus a
    trailing 1-float step counter. The worker zeroes both at episode starts,
    which empties the caches — no state crosses episodes. Positions are
    episode-relative, matching the training unroll's segment-relative
    positions, so behavior and training policies agree exactly while an
    episode fits one window (``tests/test_transformer.py`` asserts agreement
    with the window path to float tolerance, and within mixed-precision
    rounding under bf16); beyond ``ctx`` the ring-buffer keeps each
    token's K/V as originally computed — a policy-lag-like bias absorbed by
    the IS/V-trace corrections."""
    head_d = hidden // n_heads
    B = h.shape[0]
    k_caches = h.reshape(B, n_layers, ctx, n_heads, head_d)
    v_caches = c[:, :-1].reshape(B, n_layers, ctx, n_heads, head_d)
    count = c[:, -1].astype(jnp.int32)  # (B,) — per env row
    logits, _value, k2, v2 = actor.apply(
        params["actor"], obs, k_caches, v_caches, count, method="decode"
    )
    a = D.categorical_sample(key, logits)
    log_prob = D.categorical_log_prob(logits, a)
    h2 = k2.reshape(B, -1)
    c2 = jnp.concatenate(
        [v2.reshape(B, -1), (count + 1).astype(jnp.float32)[:, None]], axis=1
    )
    return a[..., None].astype(jnp.float32), logits, log_prob[..., None], h2, c2


def _act_transformer_window(
    actor, ctx: int, obs_dim: int, params, obs, h, c, key
):
    """Full-window recompute acting (the pre-KV-cache path): ``h`` is the
    flattened history of the last ``ctx`` observations (newest last), ``c`` a
    1-float counter of valid steps. O(ctx²·d) per step — kept as the
    equivalence oracle for ``_act_transformer`` and for contexts where window
    re-positioning (exact sliding semantics) matters more than speed."""
    hist = h.reshape(1, ctx, obs_dim)
    hist = jnp.concatenate([hist[:, 1:], obs[:, None, :]], axis=1)
    n_valid = jnp.minimum(c[0, 0] + 1.0, float(ctx))
    idx = jnp.arange(ctx)
    # Invalid (pre-episode) rows get segment 0, valid rows segment 1: the
    # query (last row) is always valid, so padding is masked out exactly.
    seg = (idx >= ctx - n_valid.astype(jnp.int32))[None].astype(jnp.int32)
    # Episode-relative positions: the oldest valid row is position 0 (or the
    # sliding offset once the episode outgrows the window).
    pos = jnp.maximum(idx - (ctx - n_valid.astype(jnp.int32)), 0)[None]
    firsts = jnp.zeros((1, ctx, 1))
    logits, _value, _ = actor.apply(
        params["actor"], hist, None, firsts, pos=pos, seg=seg
    )
    last = logits[:, -1]
    a = D.categorical_sample(key, last)
    log_prob = D.categorical_log_prob(last, a)
    h2 = hist.reshape(1, ctx * obs_dim)
    c2 = jnp.full_like(c, n_valid)
    return a[..., None].astype(jnp.float32), last, log_prob[..., None], h2, c2


def _act_backbone(actor, params, obs, h, c, key):
    """One acting step of a catalog family (``models/backbone.py``): ``h``
    holds the recurrent layers' (Mamba-2, linear attention) states and
    convolution tails, ``c`` the attention layers' rings — keys and values, or
    for latent attention one ring of the compressed key/value latent beside
    the shared rotated key — and a step counter. The worker zeroes both at
    episode starts, so no state crosses episodes."""
    logits, _value, (h2, c2) = actor.apply(params["actor"], obs, h, c, method="act")
    a = D.categorical_sample(key, logits)
    log_prob = D.categorical_log_prob(logits, a)
    return a[..., None].astype(jnp.float32), logits, log_prob[..., None], h2, c2


def _act_sac_continuous(actor: SACContinuousActor, params, obs, h, c, key):
    mu, log_std, (h2, c2) = actor.apply(params["actor"], obs, (h, c), method="act")
    a, log_prob = D.tanh_normal_sample(key, mu, jnp.exp(log_std))
    return a, jnp.zeros_like(mu), log_prob, h2, c2


def build_family(cfg: Config, mesh=None) -> ModelFamily:
    """Build the model family for ``cfg.algo`` (registry equivalent of
    ``main.py:98-110``). ``mesh`` is required only for sequence-parallel
    transformer training (attention_impl ring/ulysses)."""
    obs_dim = int(cfg.obs_shape[0])
    n = int(cfg.action_space)
    kw = dict(
        hidden=cfg.hidden_size,
        reset_on_first=cfg.reset_carry_on_first,
        # Mixed precision for the LSTM families: params f32, torso/LSTM
        # matmuls at MXU bf16 rate with f32 accumulation (heads and the
        # recurrent carry stay f32 — see LSTMCell.dtype).
        dtype=jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else None,
    )

    if cfg.model == "transformer":
        from tpu_rl.models.transformer import TransformerActorCritic

        assert cfg.algo in ("PPO", "IMPALA", "V-MPO"), (
            "transformer backbone supports the discrete on-policy algorithms"
        )
        actor = TransformerActorCritic(
            n_actions=n,
            hidden=cfg.hidden_size,
            n_heads=cfg.n_heads,
            n_layers=cfg.n_layers,
            attention_impl=cfg.attention_impl,
            mesh=mesh,
            dtype=jnp.bfloat16 if cfg.compute_dtype == "bfloat16" else None,
        )
        ctx = cfg.effective_act_ctx
        kv = cfg.n_layers * ctx * cfg.hidden_size
        fam = ModelFamily(
            cfg.algo, False, False, actor, None, obs_dim, n, cfg.hidden_size,
            act=partial(
                _act_transformer, actor, ctx, cfg.n_layers, cfg.n_heads,
                cfg.hidden_size,
            ),
            # h = K caches; c = V caches + step counter (see _act_transformer).
            act_carry_widths=(kv, kv + 1),
            store_carry=False,
        )
        return fam

    if cfg.model in ARCH_CHECKS:  # a catalog family: one file, models/<cfg.model>.py
        from tpu_rl.models.backbone import state_widths

        core = importlib.import_module(f"tpu_rl.models.{cfg.model}").ActorCritic
        ctx = cfg.effective_act_ctx
        actor = core(n_actions=n, arch=cfg.arch, act_ctx=ctx, dtype=kw["dtype"])
        return ModelFamily(
            cfg.algo, False, False, actor, None, obs_dim, n, cfg.arch["hidden_size"],
            act=partial(_act_backbone, actor),
            act_carry_widths=state_widths(core.acting_state(cfg.arch, ctx)),
            store_carry=False,
            route_unroll=partial(actor.apply, method="unroll_routed") if core.routed else None,
        )

    if cfg.algo in ("PPO", "IMPALA", "V-MPO"):
        actor = DiscreteActorCritic(n_actions=n, **kw)
        fam = ModelFamily(
            cfg.algo, False, False, actor, None, obs_dim, n, cfg.hidden_size,
            act=partial(_act_discrete_ac, actor),
        )
    elif cfg.algo == "PPO-Continuous":
        actor = ContinuousActorCritic(n_actions=n, std_floor=cfg.std_floor, **kw)
        fam = ModelFamily(
            cfg.algo, True, False, actor, None, obs_dim, n, cfg.hidden_size,
            act=partial(_act_continuous_ac, actor),
            act_greedy=partial(_greedy_continuous_ac, actor),
        )
    elif cfg.algo == "SAC":
        actor = SACDiscreteActor(n_actions=n, **kw)
        critic = SACDiscreteTwinCritic(n_actions=n, **kw)
        fam = ModelFamily(
            cfg.algo, False, True, actor, critic, obs_dim, n, cfg.hidden_size,
            act=partial(_act_sac_discrete, actor),
        )
    elif cfg.algo == "SAC-Continuous":
        actor = SACContinuousActor(n_actions=n, **kw)
        critic = SACContinuousTwinCritic(**kw)
        fam = ModelFamily(
            cfg.algo, True, True, actor, critic, obs_dim, n, cfg.hidden_size,
            act=partial(_act_sac_continuous, actor),
            act_greedy=partial(_greedy_sac_continuous, actor),
        )
    else:
        raise ValueError(f"unknown algo {cfg.algo!r}")
    return fam


ALGOS = ("PPO", "PPO-Continuous", "IMPALA", "V-MPO", "SAC", "SAC-Continuous")
