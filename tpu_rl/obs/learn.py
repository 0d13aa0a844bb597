"""Learning-dynamics diagnostics: watch the learning, not just the machines.

Every observability layer before this one (telemetry, tracing, perf/SLO,
goodput) watches the *system*; this module watches the *update math*. The
seven ``make_train_step`` loops (``tpu_rl/algos``) additionally return an
in-jit ``diag`` pytree — per-row moment sums of policy entropy, approx-KL,
clip rates, importance weights, advantages and value errors, plus per-update
scalars (per-module grad norms, update/param norm, SAC alpha + target-Q,
V-MPO eta) — and the learner folds each dispatch's ``diag`` into an
on-device accumulator **bucketed by the batch's policy staleness** (the
learner-version delta that rides every RolloutBatch). Host readback happens
only on the existing loss-log cadence (the PR 13 nonfinite-counter pattern:
zero extra per-step syncs), where :func:`derive` turns the raw moment sums
into the published curves — ``learner-diag-*`` gauges, the per-staleness
``learner-diag-by-stale-*`` gauge families, and ``result_dir/learn.jsonl``.

The staleness-conditioned ESS/KL curves are exactly the inputs the
IMPACT-style adaptive update:data controller (ROADMAP item 1) regulates
against; until that lands they are SLO-able for free
(``gauge:learner-diag-approx-kl<0.5``-style rules need no engine change).

Contracts:

- **bit-identity**: the diag pytree is derived from existing intermediates
  and never feeds back into the update — params/opt-state with
  ``Config.learn_diag`` on are bitwise equal to off (pinned per algo);
- **row channels are per-row means**: every entry in ``diag["rows"]`` is a
  ``(R,)`` array of per-row means over that row's elements, so bucket
  aggregation needs no element-count bookkeeping — pooled first/second
  moments weight rows equally, which is exact here because every row spans
  the same ``(seq_len - 1) * width`` region;
- **the accumulator is pure sums**: ``accumulate`` is a single jitted
  scatter-add (one-hot matmul over the bucket axis); all division happens
  host-side in :func:`derive`.
"""

from __future__ import annotations

import math
import time
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np

# Power-of-two staleness buckets: 0 (fresh / colocated), 1, 2-3, 4-7, ...
# 64+. Eight buckets cover the update:data ratios the IMPACT controller
# will sweep (2^6 updates of lag is already deep off-policy for the
# on-policy families) while keeping the one-hot scatter tiny.
N_STALE_BUCKETS = 8
STALE_BUCKET_LABELS: tuple[str, ...] = (
    "0", "1", "2-3", "4-7", "8-15", "16-31", "32-63", "64+",
)

GAUGE_PREFIX = "learner-diag-"
BUCKET_GAUGE_PREFIX = "learner-diag-by-stale-"

# Headline series of the two families (drift-checked against
# docs/ARCHITECTURE.md; the full set is GAUGE_PREFIX/BUCKET_GAUGE_PREFIX +
# derived channel name — channels an algo doesn't emit don't appear).
ENTROPY_GAUGE = "learner-diag-entropy"
APPROX_KL_GAUGE = "learner-diag-approx-kl"
ESS_GAUGE = "learner-diag-ess"
BY_STALE_ESS_GAUGE = "learner-diag-by-stale-ess"
APPROX_KL_HIST = "learner-diag-approx-kl-hist"
ESS_HIST = "learner-diag-ess-hist"

_EPS = 1e-12


# --------------------------------------------------------------- in-jit math
def rows_mean(x: jax.Array) -> jax.Array:
    """Per-row mean over all non-batch axes: (R, ...) -> (R,). The canonical
    ``diag["rows"]`` channel producer (see module contract)."""
    return jnp.mean(x.reshape(x.shape[0], -1), axis=1)


def module_grad_norms(grads: Any, scale: Any = 1.0) -> dict[str, jax.Array]:
    """Global grad norm split by module group — ``torso`` (any path part
    containing "body": the shared MLP/conv torsos, SAC's obs/act bodies),
    ``cell`` (the recurrent core), ``heads`` (everything else: output heads,
    dual variables like log_eta/log_alpha). Static path walk, so this is
    free to call under jit.

    ``grads`` are the gradients *before* the clip and ``scale`` the clip's
    factor (``ops/losses.clip_subtree_by_global_norm``): one for the whole
    tree, or a dict from top-level key to that subtree's factor (a key it
    lacks was not clipped). The norms are the clipped gradients' to
    rounding, and ``g * scale`` keeps one reader: with a second one XLA
    contracts the optimizer's pass otherwise, and ``learn_diag`` would move
    a bit of the state."""
    sq = {"torso": 0.0, "cell": 0.0, "heads": 0.0}
    for path, leaf in jax.tree_util.tree_leaves_with_path(grads):
        group = "heads"
        for part in path:
            key = getattr(part, "key", None)
            if not isinstance(key, str):
                continue
            if "body" in key:
                group = "torso"
                break
            if key == "cell":
                group = "cell"
                break
        s = scale.get(getattr(path[0], "key", None), 1.0) if isinstance(scale, dict) else scale
        sq[group] = sq[group] + jnp.sum(jnp.square(leaf.astype(jnp.float32))) * (s * s)
    return {k: jnp.sqrt(v) for k, v in sq.items()}


def route_scalars(routes: list) -> dict[str, jax.Array]:
    """Per-update routing counters of a sparse-expert family, from what its
    expert layers handed back (``ops/moe.route_stats``): routed rows computed
    (summed over the layers); per layer, averaged: rows of the fullest held
    expert, of the mean one, their ratio, the share of assignments that fell
    on held experts, the share of tokens with no held expert and the trips of
    the block's walk over its held rows (``moe-chunks``: 1.0 when every layer
    took one); under a group-limited router ``moe-group-hit-share``, the share
    of tokens whose kept groups hold a held expert; for a family with
    per-channel delta-rule layers ``kda-decay-floor-share``, the share of
    (step, channel) gates within 1% of their bound, averaged over those layers
    (a health gauge). Empty for a family without expert layers, whose records (if
    its layers hand any back) hold counters alone."""
    stats = [r["stats"] for r in routes if "stats" in r]
    if not stats:
        return {}
    mean = lambda k: sum(s[k] for s in stats) / len(stats)  # noqa: E731
    more = {}
    if "group-hit-share" in stats[0]:  # a group-limited router (ops/moe.route)
        more["moe-group-hit-share"] = mean("group-hit-share")
    floor = [r["kda-decay-floor-share"]["kda"] for r in routes if "kda-decay-floor-share" in r]
    if floor:  # models/ling_flash.py: each layer's share over the count of such layers
        more["kda-decay-floor-share"] = sum(floor)
    return {
        **more,
        "moe-rows": sum(s["rows"] for s in stats),
        "moe-rows-max": mean("rows-max"),
        "moe-rows-mean": mean("rows-mean"),
        "moe-rows-max-over-mean": sum(
            s["rows-max"] / jnp.maximum(s["rows-mean"], 1e-6) for s in stats
        ) / len(stats),
        "moe-held-share": mean("held-share"),
        "moe-no-held-share": mean("no-held-share"),
        "moe-chunks": mean("chunks"),
    }


# What an attention layer hands back beside its routing: the kept pairs, then
# ``parallel/sequence.attention_tiles``' three counts in its order.
ATTENTION_COUNTERS = ("attn-pairs", "attn-tiles-run", "attn-tiles-band", "attn-bwd-steps")


def attention_scalars(routes: list) -> dict[str, jax.Array]:
    """What the attention masks did this update, from what the layers handed
    back beside their routing (counter -> kind -> count;
    ``models/layers.attention_counts``), each summed over the layers of its kind:
    the query-key pairs kept (``attn-pairs-global``, ``attn-pairs-window``; an
    EVA layer's ``attn-pairs-block`` and ``attn-pairs-summary``),
    the tiles of the splash kernels' static band (``attn-tiles-band-*``),
    those of them the kernels computed because no seam emptied them
    (``attn-tiles-run-*``), and the grid steps the backward took a head
    (``attn-bwd-steps-*``: run over steps is the share of its steps that
    compute). Empty for a family that counts none."""
    out: dict[str, jax.Array] = {}
    for r in routes:
        for counter in ATTENTION_COUNTERS:
            for kind, count in r.get(counter, {}).items():
                out[f"{counter}-{kind}"] = out.get(f"{counter}-{kind}", 0.0) + count
    return out


def tree_delta_norm(new: Any, old: Any) -> jax.Array:
    """Global norm of ``new - old`` over a param pytree (the applied update's
    magnitude; exactly 0 when a guard skipped the update)."""
    import optax

    return optax.global_norm(
        jax.tree.map(lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old)
    )


def tree_norm(tree: Any) -> jax.Array:
    import optax

    return optax.global_norm(tree)


def update_scalars(grads: Any, scale: Any, new: Any, old: Any) -> dict[str, jax.Array]:
    """One update's ``diag`` scalars from the raw gradients, the clip's
    factor and the parameters after and before it. Call it under the
    optimizer's named scope: nothing stands between these sums and the
    optimizer's pass, so XLA folds them into it and the device trace books
    them there."""
    return {
        **{f"grad-norm-{k}": v for k, v in module_grad_norms(grads, scale).items()},
        "update-norm": tree_delta_norm(new, old),
        "param-norm": tree_norm(new),
    }


def stale_bucket_index(stale: jax.Array) -> jax.Array:
    """Map per-row staleness (updates of policy lag, any numeric dtype) to a
    bucket index in ``[0, N_STALE_BUCKETS)``: 0 for <=0, else
    ``min(1 + floor(log2(s)), K-1)`` — the power-of-two layout above."""
    s = jnp.maximum(stale.astype(jnp.float32), 1.0)
    idx = 1 + jnp.floor(jnp.log2(s)).astype(jnp.int32)
    idx = jnp.minimum(idx, N_STALE_BUCKETS - 1)
    return jnp.where(stale.astype(jnp.float32) <= 0.0, 0, idx)


def host_stale_rows(idx: int, vers: Any, n_rows: int) -> np.ndarray:
    """Per-row policy staleness for one dispatch: ``max(0, idx - ver)`` where
    the version sidecar is known, 0 elsewhere. ``vers`` is the per-row
    learner-version array the store read out of its per-slot sidecar (a
    chained dispatch concatenates its K raws' sidecars, matching the
    flattened row channels); None or a size mismatch degrades to all-fresh
    rather than misattributing rows to the wrong bucket."""
    if vers is None:
        return np.zeros(n_rows, np.float32)
    v = np.asarray(vers).reshape(-1)
    if v.size != n_rows:
        return np.zeros(n_rows, np.float32)
    return np.where(
        v >= 0, np.maximum(np.float64(idx) - v, 0.0), 0.0
    ).astype(np.float32)


def init_acc(diag: Mapping[str, Any]) -> dict:
    """Zero accumulator matching a ``diag`` pytree's channel set (the set is
    static per algo+config, so the jitted :func:`accumulate` traces once)."""
    k = N_STALE_BUCKETS
    return {
        "n-updates": jnp.zeros((), jnp.float32),
        "rows-n": jnp.zeros((k,), jnp.float32),
        "rows": {n: jnp.zeros((k,), jnp.float32) for n in diag["rows"]},
        "scalars": {n: jnp.zeros((), jnp.float32) for n in diag["scalars"]},
    }


def accumulate(acc: dict, diag: Mapping[str, Any], stale: jax.Array) -> dict:
    """Fold one dispatch's ``diag`` into the accumulator: per-row channels
    scatter-add into their staleness bucket (one-hot matmul — no host sync,
    no dynamic shapes), scalars and counts add. ``stale`` is ``(R,)``
    aligned with the row channels; chained dispatch pre-flattens both
    (``parallel.dp``) and carries the update count in ``diag["n-updates"]``."""
    onehot = jax.nn.one_hot(
        stale_bucket_index(stale), N_STALE_BUCKETS, dtype=jnp.float32
    )  # (R, K)
    n_up = diag.get("n-updates", 1.0)
    return {
        "n-updates": acc["n-updates"] + n_up,
        "rows-n": acc["rows-n"] + jnp.sum(onehot, axis=0),
        "rows": {
            n: acc["rows"][n] + onehot.T @ v.astype(jnp.float32)
            for n, v in diag["rows"].items()
        },
        "scalars": {
            n: acc["scalars"][n] + v.astype(jnp.float32)
            for n, v in diag["scalars"].items()
        },
    }


def make_accumulate():
    """The jitted accumulator program (donates the running accumulator, so
    steady state allocates nothing new)."""
    return jax.jit(accumulate, donate_argnums=(0,))


def make_accumulate_first():
    """The fold of a line's first dispatch as one program: the zeros are made
    inside it, not eagerly on the host (one ``jnp.zeros`` a channel, 0.4 ms
    each on a TPU's host). With the chip at work, the donating fold of such
    zeros returned only when the running update had finished — 430 ms of
    the main lane in granite's cell (PERF.md section 6, PR 44)."""
    return jax.jit(lambda diag, stale: accumulate(init_acc(diag), diag, stale))


# ---------------------------------------------------- host-side derived math
def ess_normalized(w_mean: float, w2_mean: float) -> float:
    """Normalized importance-weight effective sample size
    ``(Σw)² / (N·Σw²) = E[w]²/E[w²]`` in (0, 1]: 1 for uniform weights,
    ``1/N`` when one element carries all the mass. 0 on no data."""
    if w2_mean <= _EPS:
        return 0.0
    return min(1.0, (w_mean * w_mean) / w2_mean)


def explained_variance(
    ret_mean: float, ret2_mean: float, err_mean: float, err2_mean: float
) -> float:
    """Value explained-variance ``1 - Var(err)/Var(ret)`` from pooled first
    and second moments (``err = target - value``). A constant predictor
    scores 0, a perfect one 1; degenerate targets (Var(ret)=0) score 0."""
    var_ret = max(0.0, ret2_mean - ret_mean * ret_mean)
    var_err = max(0.0, err2_mean - err_mean * err_mean)
    if var_ret <= _EPS:
        return 0.0
    return 1.0 - var_err / var_ret


# Row-channel pairs -> derived metric names. Channels an algo doesn't emit
# simply don't appear (SAC has no "clip"; PPO has no "rho-clip").
_MEAN_CHANNELS = {
    "ent": "entropy",
    "kl": "approx-kl",
    "clip": "clip-frac",
    "rho-clip": "rho-clip-rate",
    "c-clip": "c-clip-rate",
    "adv": "adv-mean",
    "tq": "target-q-mean",
}


def _derive_channels(sums: Mapping[str, float], n_rows: float) -> dict:
    """Derived metrics for one pool (a staleness bucket or the global sum)
    from per-row-mean sums and the pooled row count."""
    if n_rows <= 0:
        return {}
    m = {k: v / n_rows for k, v in sums.items()}
    out = {
        name: m[ch] for ch, name in _MEAN_CHANNELS.items() if ch in m
    }
    if "w" in m and "w2" in m:
        out["ess"] = ess_normalized(m["w"], m["w2"])
    if "adv" in m and "adv2" in m:
        out["adv-std"] = math.sqrt(max(0.0, m["adv2"] - m["adv"] ** 2))
    if "tq" in m and "tq2" in m:
        out["target-q-std"] = math.sqrt(max(0.0, m["tq2"] - m["tq"] ** 2))
    if all(ch in m for ch in ("ret", "ret2", "err", "err2")):
        out["explained-variance"] = explained_variance(
            m["ret"], m["ret2"], m["err"], m["err2"]
        )
    return out


def derive(acc: Mapping[str, Any]) -> dict:
    """Turn a host copy of the accumulator (``jax.device_get``) into the
    published document: ``{"n_updates", "global": {...}, "buckets":
    {label: {..., "rows": n}}}`` — global pools every bucket; only nonempty
    buckets appear."""
    n_up = float(acc["n-updates"])
    rows_n = [float(x) for x in acc["rows-n"]]
    sums = {k: [float(x) for x in v] for k, v in acc["rows"].items()}

    glob = _derive_channels(
        {k: sum(v) for k, v in sums.items()}, sum(rows_n)
    )
    if n_up > 0:
        for name, v in acc["scalars"].items():
            glob[name] = float(v) / n_up
        if glob.get("param-norm", 0.0) > _EPS:
            glob["update-ratio"] = glob.get("update-norm", 0.0) / glob["param-norm"]
    buckets = {}
    for b, label in enumerate(STALE_BUCKET_LABELS):
        if rows_n[b] <= 0:
            continue
        d = _derive_channels({k: v[b] for k, v in sums.items()}, rows_n[b])
        d["rows"] = rows_n[b]
        buckets[label] = d
    return {"n_updates": n_up, "global": glob, "buckets": buckets}


def publish(reg, derived: Mapping[str, Any]) -> None:
    """Export one derived document into a MetricsRegistry: global curves as
    ``learner-diag-<name>`` gauges (the SLO-able series), per-staleness
    families as ``learner-diag-by-stale-<name>`` gauges labeled
    ``stale_bucket`` (a distinct family so a sparsely-populated bucket can
    never trip a worst-case-over-samples SLO rule on the global name), and
    approx-KL/ESS additionally as histograms for distribution-over-time."""
    for name, val in derived["global"].items():
        reg.gauge(GAUGE_PREFIX + name).set(val)
        if name in ("approx-kl", "ess"):
            reg.histogram(GAUGE_PREFIX + name + "-hist").observe(float(val))
    for label, vals in derived["buckets"].items():
        for name, val in vals.items():
            reg.gauge(
                BUCKET_GAUGE_PREFIX + name, labels={"stale_bucket": label}
            ).set(val)


def learn_record(idx: int, derived: Mapping[str, Any]) -> dict:
    """One ``learn.jsonl`` line: the derived document stamped with the
    update index and wall clock (obs/audit.py writer shape)."""
    return {
        "ts": time.time(),
        "idx": int(idx),
        "n_updates": derived["n_updates"],
        **derived["global"],
        "buckets": derived["buckets"],
    }


class DiagAccumulator:
    """Host-side wrapper owning the device accumulator and its jitted fold:
    ``add(diag, stale)`` per dispatch (lazy — one extra device program, no
    sync); at the log cadence ``take()`` hands the sums over without reading
    them (the next ``add`` starts afresh) and ``read(sums)`` is the only
    readback, returning the derived document — ``drain(idx)`` is the two in
    one place. Constructed only when ``Config.learn_diag`` is on and the algo
    emitted a ``diag`` — callers guard on ``is None`` like every other
    plane."""

    def __init__(self):
        self._acc = None
        self._fold = make_accumulate()
        self._first = make_accumulate_first()

    def add(self, diag: Mapping[str, Any], stale: jax.Array) -> None:
        if self._acc is None:
            self._acc = self._first(diag, stale)
        else:
            self._acc = self._fold(self._acc, diag, stale)

    @property
    def live(self) -> bool:
        """Sums are held on the device (folded, not yet handed over)."""
        return self._acc is not None

    def take(self) -> dict | None:
        """Hand over the on-device sums as they stand — no sync, no
        readback — and start afresh: what was folded so far is the caller's
        to ``read`` whenever it likes, what is folded next is a new line's.
        None when nothing was accumulated since the last hand-over."""
        acc, self._acc = self._acc, None
        return acc

    @staticmethod
    def read(acc: dict | None) -> dict | None:
        """Block on + read back handed-over sums and derive the document.
        None for no sums, or sums of no update."""
        if acc is None:
            return None
        host = jax.device_get(acc)
        if float(host["n-updates"]) <= 0:
            return None
        return derive(host)

    def drain(self, idx: int) -> dict | None:
        """Block on + read back the accumulated sums, derive, reset. Returns
        None when nothing was accumulated since the last drain."""
        return self.read(self.take())
