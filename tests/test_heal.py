"""Self-healing plane (tpu_rl.heal) tests: in-jit update guards (bit
identity + NaN containment across every algo and the chained dispatch),
the divergence watchdog on synthetic traces, the windowed rollback budget,
ingress validation + the quarantine strike/clear lifecycle, the chaos data
faults (``nan:``/``spike:`` grammar and injector), the nth-latest
checkpoint reader behind rollback, and the `==` SLO comparator."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.conftest import small_config
from tests.test_algos import make_batch
from tpu_rl.algos.registry import get_algo
from tpu_rl.heal import DivergenceWatchdog, IngressGuard, RollbackBudget

ALL_ALGOS = [
    "PPO", "PPO-Continuous", "IMPALA", "V-MPO", "SAC", "SAC-Continuous",
]


def _algo_cfg(algo, **kw):
    return small_config(
        algo=algo,
        action_space=1 if "Continuous" in algo else 2,
        is_continuous="Continuous" in algo,
        **kw,
    )


def _assert_trees_identical(a, b, what=""):
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb), what
    for x, y in zip(la, lb, strict=True):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y), err_msg=what)


def _param_trees(state):
    if hasattr(state, "params"):
        return (state.params, state.opt_state)
    return (
        state.actor_params, state.critic_params, state.target_critic_params,
        state.log_alpha, state.actor_opt, state.critic_opt, state.alpha_opt,
    )


# ------------------------------------------------------------- in-jit guards
@pytest.mark.parametrize("algo", ALL_ALGOS)
def test_guard_on_clean_is_bit_identical(algo):
    """With finite data every leaf's select takes the applied value, which
    is literally the pre-guard update: every state leaf must match guard-off
    bitwise."""
    cfg_on = _algo_cfg(algo, update_guard=True)
    cfg_off = _algo_cfg(algo, update_guard=False)
    fam, s_on, step_on = get_algo(algo).build(cfg_on, jax.random.PRNGKey(0))
    _, s_off, step_off = get_algo(algo).build(cfg_off, jax.random.PRNGKey(0))
    batch = make_batch(cfg_on, fam)
    k = jax.random.PRNGKey(1)
    s_on1, m_on = jax.jit(step_on)(s_on, batch, k)
    s_off1, m_off = jax.jit(step_off)(s_off, batch, k)
    _assert_trees_identical(s_on1, s_off1, algo)
    assert float(m_on["nonfinite-updates"]) == 0.0
    assert "nonfinite-updates" not in m_off


@pytest.mark.parametrize("algo", ALL_ALGOS)
def test_guard_contains_nonfinite_update(algo):
    """A NaN batch must leave every parameter, optimizer-state, and target
    leaf bitwise untouched, and count one skip per sub-update."""
    cfg = _algo_cfg(algo, update_guard=True)
    fam, state, train_step = get_algo(algo).build(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, fam)
    bad = batch.replace(obs=batch.obs.at[0, 0].set(jnp.nan))
    s1, m = jax.jit(train_step)(state, bad, jax.random.PRNGKey(1))
    _assert_trees_identical(_param_trees(s1), _param_trees(state), algo)
    assert float(m["nonfinite-updates"]) == float(cfg.K_epoch)
    # step still advances: the dispatch happened, the update was skipped
    assert int(s1.step) == int(state.step) + 1


@pytest.mark.parametrize("algo", ALL_ALGOS)
def test_guard_selects_and_never_branches(algo):
    """The guard is a select inside the optimizer's pass: no ``cond`` at any
    depth of the jitted step's jaxpr (a conditional is a fusion edge: behind
    one the diagnostics' norms and a copy of every donated parameter are
    passes over the weights of their own)."""
    cfg = _algo_cfg(algo, update_guard=True)
    fam, state, train_step = get_algo(algo).build(cfg, jax.random.PRNGKey(0))
    jaxpr = jax.make_jaxpr(jax.jit(train_step))(
        state, make_batch(cfg, fam), jax.random.PRNGKey(1)
    )

    def primitives(jx):
        for eqn in jx.eqns:
            yield eqn.primitive.name
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from primitives(sub)

    names = set(primitives(jaxpr.jaxpr))
    assert "select_n" in names
    assert "cond" not in names


@pytest.mark.parametrize("algo", ALL_ALGOS)
def test_guard_select_does_not_leak_the_unselected_side(algo):
    """Every observation NaN: the applied side holds NaN in every parameter
    leaf (shown with the guard off), and the guarded step still leaves every
    state leaf bitwise untouched, with ``update-norm`` exactly 0."""
    cfg = _algo_cfg(algo, update_guard=True, learn_diag=True)
    fam, state, train_step = get_algo(algo).build(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, fam)
    bad = batch.replace(obs=jnp.full_like(batch.obs, jnp.nan))
    _, _, unguarded = get_algo(algo).build(
        _algo_cfg(algo, update_guard=False), jax.random.PRNGKey(0)
    )
    s_off, _ = jax.jit(unguarded)(state, bad, jax.random.PRNGKey(1))
    applied = s_off.params if hasattr(s_off, "params") else (
        s_off.actor_params, s_off.critic_params
    )
    for leaf in jax.tree_util.tree_leaves(applied):
        assert np.isnan(np.asarray(leaf)).any(), algo
    s1, m = jax.jit(train_step)(state, bad, jax.random.PRNGKey(1))
    _assert_trees_identical(_param_trees(s1), _param_trees(state), algo)
    assert float(m["nonfinite-updates"]) == float(cfg.K_epoch)
    assert float(m["diag"]["scalars"]["update-norm"]) == 0.0


def test_guard_skip_count_rides_chained_dispatch():
    """chain=K sums per-update skip counts over the scan axis (dp.py): one
    poisoned slice out of K must report exactly K_epoch skips."""
    from tpu_rl.parallel import (
        make_parallel_train_step,
        make_mesh,
        replicate,
        shard_chained_batch,
    )

    cfg = small_config(algo="PPO", batch_size=8, update_guard=True)
    fam, state, train_step = get_algo("PPO").build(cfg, jax.random.PRNGKey(0))
    clean = make_batch(cfg, fam, key=1)
    poisoned = clean.replace(obs=clean.obs.at[0, 0].set(jnp.nan))
    mesh = make_mesh(4)
    cstep = make_parallel_train_step(train_step, mesh, cfg, chain=2)
    _, metrics = cstep(
        replicate(state, mesh),
        shard_chained_batch([clean, poisoned], mesh),
        replicate(jax.random.PRNGKey(2), mesh),
    )
    assert float(metrics["nonfinite-updates"]) == float(cfg.K_epoch)


# ---------------------------------------------------------------- watchdog
def test_watchdog_clean_trace_never_trips():
    wd = DivergenceWatchdog(window=8, z_max=6.0, sustain=3)
    for i in range(200):
        assert not wd.observe({"loss": 1.0 + 0.05 * np.sin(i)})


def test_watchdog_slow_drift_never_trips():
    """A drifting-but-smooth signal tracks its own EWMA baseline."""
    wd = DivergenceWatchdog(window=8, z_max=6.0, sustain=3)
    for i in range(300):
        assert not wd.observe({"loss": 1.0 + 0.01 * i})


def test_watchdog_sustained_spike_trips_at_sustain():
    wd = DivergenceWatchdog(window=8, z_max=6.0, sustain=3)
    rng = np.random.default_rng(0)
    for i in range(50):  # warm the stats past the window
        wd.observe({"loss": 1.0 + 0.01 * rng.standard_normal()})
    assert not wd.observe({"loss": 1e6})
    assert not wd.observe({"loss": 1e6})
    assert wd.observe({"loss": 1e6})
    assert "loss" in wd.last_reason


def test_watchdog_single_spike_is_noise_not_a_trip():
    wd = DivergenceWatchdog(window=8, z_max=6.0, sustain=3)
    for i in range(50):
        wd.observe({"loss": 1.0})
    assert not wd.observe({"loss": 1e6})
    for i in range(20):  # streak resets on the next clean check
        assert not wd.observe({"loss": 1.0})


def test_watchdog_nonfinite_host_signal_trips_without_warmup():
    """A non-finite observable is anomalous from sample one — no z-score
    warmup applies (the stats never even see it)."""
    wd = DivergenceWatchdog(window=32, z_max=6.0, sustain=2)
    assert not wd.observe({"loss": float("nan")})
    assert wd.observe({"loss": float("inf")})


def test_watchdog_nonfinite_counter_channel():
    wd = DivergenceWatchdog(nonfinite_max=3)
    assert not wd.note_nonfinite(2.0)
    assert wd.note_nonfinite(3.0)
    assert "nonfinite" in wd.last_reason


def test_watchdog_reset_restarts_detection():
    wd = DivergenceWatchdog(window=8, z_max=6.0, sustain=1)
    for i in range(50):
        wd.observe({"loss": 1.0})
    assert wd.observe({"loss": 1e6})
    wd.reset()
    # Fresh stats are warming up again: the same magnitude is not anomalous.
    assert not wd.observe({"loss": 1e6})


def test_rollback_budget_window_and_exhaustion():
    t = [0.0]
    budget = RollbackBudget(max_rollbacks=2, window_s=10.0, clock=lambda: t[0])
    assert not budget.exhausted()
    budget.record()
    t[0] = 1.0
    budget.record()
    assert budget.used == 2
    assert budget.exhausted()
    t[0] = 12.0  # both rollbacks age out of the trailing window
    assert not budget.exhausted()
    assert budget.used == 0


# --------------------------------------------- ingress guard + quarantine
def _frame(obs=0.5, rew=0.1, wid=1):
    return {
        "obs": np.full((4, 3), obs, np.float32),
        "rew": np.full((4, 1), rew, np.float32),
        "wid": wid,
    }


def test_ingress_guard_classifies():
    g = IngressGuard(abs_max=1e6)
    assert g.tick_clean(_frame())
    assert not g.tick_clean(_frame(obs=np.nan))
    assert not g.tick_clean(_frame(rew=np.nan))
    assert not g.tick_clean(_frame(obs=1e9))  # finite spike over the bound
    assert not g.tick_clean(_frame(rew=-1e9))
    assert g.tick_clean({})  # no validated columns -> clean
    assert g.n_checked == 6


def test_membership_quarantine_lifecycle():
    from tpu_rl.runtime.storage import MembershipTable

    t = [0.0]
    mt = MembershipTable(lease_s=60.0, clock=lambda: t[0])
    # Strikes below the limit never quarantine.
    assert not mt.strike(1, limit=3)
    assert not mt.strike(1, limit=3)
    assert not mt.is_quarantined(1)
    assert mt.strike(1, limit=3)  # third strike trips
    assert mt.is_quarantined(1)
    assert mt.n_quarantines == 1
    # Another poisoned frame refreshes the cooldown clock, no double count.
    t[0] = 1.0
    assert not mt.strike(1, limit=3)
    assert mt.n_quarantines == 1
    # A clean frame before the cooldown does NOT clear.
    t[0] = 2.5
    assert not mt.probe_clear(1, cooldown=2.0)
    assert mt.is_quarantined(1)
    # After the cooldown the clean re-probe clears and resets strikes.
    t[0] = 3.5
    assert mt.probe_clear(1, cooldown=2.0)
    assert not mt.is_quarantined(1)
    assert mt.strikes[1] == 0
    assert mt.n_unquarantines == 1
    # Other wids are untouched throughout.
    assert not mt.is_quarantined(2)


def test_storage_ingress_admit_counts_and_parity():
    """The single-site drop accounting: poisoned frames count poisoned even
    from a quarantined wid (exact chaos parity), clean frames from a
    quarantined wid count quarantined-frames until the cooldown clears."""
    from tpu_rl.runtime.storage import LearnerStorage, MembershipTable

    cfg = small_config(
        ingress_validate=True, quarantine_strikes=2, quarantine_clear_s=5.0
    )
    store = LearnerStorage.__new__(LearnerStorage)  # no sockets/shm needed
    store.cfg = cfg
    t = [0.0]
    store.members = MembershipTable(lease_s=60.0, clock=lambda: t[0])
    store._ingress = IngressGuard(abs_max=cfg.ingress_abs_max)

    assert store._ingress_admit(_frame())
    assert not store._ingress_admit(_frame(obs=np.nan))  # strike 1
    assert not store._ingress_admit(_frame(obs=np.nan))  # strike 2 -> jail
    assert store.members.is_quarantined(1)
    # Poisoned while quarantined: still poisoned (parity), never quarantined-
    # frames; refreshes the cooldown.
    t[0] = 1.0
    assert not store._ingress_admit(_frame(obs=np.nan))
    assert store._ingress.n_poisoned == 3
    assert store._ingress.n_quarantined_frames == 0
    # Clean while quarantined, inside cooldown: dropped + counted separately.
    t[0] = 3.0
    assert not store._ingress_admit(_frame())
    assert store._ingress.n_quarantined_frames == 1
    # Clean after cooldown: clears and admits.
    t[0] = 7.0
    assert store._ingress_admit(_frame())
    assert not store.members.is_quarantined(1)
    assert store._ingress.n_poisoned == 3


# ------------------------------------------------------- chaos data faults
def test_chaos_grammar_parses_data_clauses():
    from tpu_rl.chaos import FaultPlan

    plan = FaultPlan.parse(
        "nan:rollout@p=0.5@t+2s@for=3s@wid=1,spike:rollout@p=0.25,"
        "nan:logp@p=1.0@wid=0,kill:worker-0-1@t+6s"
    )
    f = plan.data_faults()[0]
    assert (f.action, f.target, f.p) == ("nan", "rollout", 0.5)
    assert (f.at_s, f.dur_s, f.wid, f.site) == (2.0, 3.0, 1, "worker")
    assert len(plan.data_faults()) == 3
    # wid filtering: wid=None faults apply to every instance
    assert [x.action for x in plan.data_faults(1)] == ["nan", "spike"]
    assert [x.target for x in plan.data_faults(0)] == ["rollout", "logp"]
    # Data faults never leak into the transport shim lists.
    send_f, recv_f = plan.transport_faults("worker")
    assert send_f == [] and recv_f == []


@pytest.mark.parametrize(
    "bad",
    [
        "nan:rollout",  # missing p
        "nan:model@p=0.5",  # not a data target
        "spike:rollout@p=0.5@for=xs",  # unparseable window length
        "nan:rollout@p=0.5@wid=one",  # unparseable wid
    ],
)
def test_chaos_grammar_rejects_bad_data_clauses(bad):
    from tpu_rl.chaos import FaultPlan

    with pytest.raises(ValueError):
        FaultPlan.parse(bad)


def test_data_chaos_window_and_injection_parity():
    from tpu_rl.chaos import DataChaos, FaultPlan

    plan = FaultPlan.parse(
        "nan:rollout@p=1.0@t+1s@for=2s,spike:rollout@p=1.0@t+1s@for=2s,"
        "nan:logp@p=1.0@t+1s@for=2s"
    )
    t = [0.0]
    dc = DataChaos(plan.data_faults(), seed=3, clock=lambda: t[0])

    def payload():
        return {
            "obs": np.zeros((2, 3), np.float32),
            "rew": np.zeros((2, 1), np.float32),
            "log_prob": np.zeros((2, 1), np.float32),
        }

    p = payload()
    t[0] = 0.5  # before the window: untouched
    dc.on_tick(p)
    assert np.isfinite(p["obs"]).all() and np.isfinite(p["log_prob"]).all()
    assert dc.n_nan + dc.n_spike + dc.n_logp_nan == 0

    t[0] = 1.5  # inside: both rollout faults fire, but only ONE lands
    for _ in range(5):
        dc.on_tick(payload())
    assert dc.n_nan + dc.n_spike == 5  # exact injected==poisoned parity
    assert dc.n_logp_nan == 5  # logp is a separate channel

    before = (dc.n_nan, dc.n_spike, dc.n_logp_nan)
    t[0] = 3.5  # past the window: silent again
    p = payload()
    dc.on_tick(p)
    assert np.isfinite(p["obs"]).all()
    assert (dc.n_nan, dc.n_spike, dc.n_logp_nan) == before


def test_data_chaos_copies_read_only_columns():
    """Worker payload columns are numpy views of jax outputs (read-only):
    the injector must swap in a writable copy, never touch the original."""
    from tpu_rl.chaos import DataChaos, FaultPlan

    dc = DataChaos(
        FaultPlan.parse("nan:logp@p=1.0").data_faults(), seed=0
    )
    orig = np.zeros((2, 1), np.float32)
    orig.setflags(write=False)
    p = {"log_prob": orig}
    dc.on_tick(p)
    assert np.isnan(p["log_prob"]).any()
    assert p["log_prob"] is not orig
    assert np.isfinite(orig).all()


def test_maybe_data_chaos_respects_wid():
    from tpu_rl.chaos import maybe_data_chaos

    cfg = small_config(chaos_spec="nan:rollout@p=0.5@wid=1", chaos_seed=9)
    assert maybe_data_chaos(cfg, "worker", instance=0) is None
    assert maybe_data_chaos(cfg, "worker", instance=1) is not None
    assert maybe_data_chaos(small_config(), "worker", instance=1) is None


# ------------------------------------------------- rollback checkpoint reader
def test_restore_nth_latest_and_discard_above(tmp_path):
    from tpu_rl.checkpoint import Checkpointer

    def _state(val):
        return {"w": np.full((3,), val, np.float32)}

    ck = Checkpointer(str(tmp_path), "PPO")
    assert ck.restore_nth_latest(_state(0.0)) is None  # nothing committed
    for idx, val in ((100, 1.0), (200, 2.0), (300, 3.0)):
        ck.save(_state(val), idx)

    got, idx, _meta = ck.restore_nth_latest(_state(0.0), n=1)
    assert idx == 300 and float(got["w"][0]) == 3.0
    got, idx, _meta = ck.restore_nth_latest(_state(0.0), n=2)
    assert idx == 200 and float(got["w"][0]) == 2.0
    got, idx, _meta = ck.restore_nth_latest(_state(0.0), n=99)  # clamps
    assert idx == 100 and float(got["w"][0]) == 1.0

    assert ck.discard_above(200) == 1  # the diverged newest is gone
    assert ck.latest_idx() == 200
    got, idx, _meta = ck.restore_nth_latest(_state(0.0), n=1)
    assert idx == 200
    ck.close()


# -------------------------------------------------------- config + slo glue
def test_config_watchdog_requires_guard_and_ckpt_depth():
    with pytest.raises(AssertionError):
        small_config(watchdog_enabled=True, update_guard=False)
    with pytest.raises(AssertionError):
        small_config(watchdog_enabled=True, ckpt_keep=1)
    cfg = small_config(watchdog_enabled=True, ckpt_keep=2)
    assert cfg.update_guard
    with pytest.raises(AssertionError):
        small_config(watchdog_window=1)
    with pytest.raises(AssertionError):
        small_config(quarantine_strikes=0)


def test_slo_equality_comparator():
    from tpu_rl.obs.slo import parse_slo_spec

    rule = parse_slo_spec("counter:learner-nonfinite-updates==0")[0]
    assert rule.op == "==" and rule.threshold == 0.0
    assert rule.upper_bound  # worst-cased by the largest source value
    assert rule.check(0.0)
    assert not rule.check(1.0)
    # The longest-first op scan still resolves <= and >= correctly.
    assert parse_slo_spec("gauge:x<=3")[0].op == "<="
    assert parse_slo_spec("gauge:x>=3")[0].op == ">="


# ------------- the watchdog behind a dispatch (ISSUE 44): the learner's loop
def _tripping(at_calls):
    """A ``prepare`` for ``_run_learner``: every crossing that can hides its
    books behind the next dispatch, and the watchdog trips at these of its
    calls (1-based), whatever the signals say."""
    from tests.test_trace_lanes import _with_feed

    calls = []

    def prepare(svc):
        _with_feed(patient=True)(svc)

        def tripped(watchdog, losses, _diag_doc, _nf_base):
            calls.append(dict(losses))
            watchdog.last_reason = f"test trip at call {len(calls)}"
            return len(calls) in at_calls

        svc._watchdog_tripped = tripped

    return prepare, calls


def _heal_run(tmp_path, port, n_updates, at_calls, **kw):
    import json

    from tests.test_trace_lanes import _lanes_of, _learn_lines, _run_learner

    prepare, calls = _tripping(at_calls)
    svc, cfg = _run_learner(
        tmp_path, port, n_updates=n_updates, prepare=prepare,
        watchdog_enabled=True, loss_log_interval=2, **kw,
    )
    main = sorted(_lanes_of(tmp_path / "run")["main"], key=lambda e: e["ts"])
    lines = _learn_lines(tmp_path / "run")
    try:
        with open(tmp_path / "run" / "learner_rollback.jsonl") as f:
            rollbacks = [json.loads(line) for line in f]
    except FileNotFoundError:
        rollbacks = []
    return svc, cfg, main, lines, rollbacks, calls


def _committed(cfg):
    from tpu_rl.checkpoint import _ckpt_dirs

    return [idx for idx, _path in _ckpt_dirs(cfg.model_dir, cfg.algo)]


def _assert_books_closed(svc, main):
    syncs = [e for e in main if e["name"] == "log-sync"]
    writes = [e for e in main if e["name"] == "log-write"]
    assert len(syncs) == len(writes)
    # the two counters add up to the logged updates
    assert svc.n_log_behind_dispatch + sum(svc.n_log_inline.values()) == len(syncs)
    from tests.test_trace_lanes import _counters

    counters = _counters(svc)
    assert counters["learner-log-behind-dispatch"] == svc.n_log_behind_dispatch
    assert counters["learner-log-inline"] == sum(svc.n_log_inline.values())
    assert counters["learner-rollbacks"] == svc.n_rollbacks


@pytest.mark.timeout(300)
def test_a_trip_seen_one_dispatch_late_restores_the_committed_state(tmp_path):
    """Update 10's books are closed behind dispatch 11: the watchdog trips
    there, the previous committed checkpoint comes back with its index, and
    update 11 goes with the state it came from — its dispatch, its fold."""
    svc, cfg, main, lines, rollbacks, calls = _heal_run(
        tmp_path, 29811, 14, at_calls={5}, model_save_interval=4,
    )
    assert svc.n_rollbacks == 1
    # saves at 4 and 8 were committed; the previous one is restored, the
    # newer one (it may hold the divergence) discarded
    assert [r["idx"] for r in rollbacks] == [4]
    updates = [e["args"]["update"] for e in main if e["name"] == "dispatch"]
    assert updates == list(range(1, 12)) + list(range(5, 15))
    names = [e["name"] for e in main]
    at = names.index("rollback")
    late = max(i for i in range(at) if names[i] == "dispatch")
    assert main[late]["args"]["update"] == 11
    sync = max(i for i in range(at) if names[i] == "log-sync")
    assert main[sync]["args"]["update"] == 10 and sync < late
    assert names[late + 1 : at + 1][-4:] == ["log-write", "diag-drain", "watchdog", "rollback"]
    # the iteration starts over: no publish, save or log of update 11
    assert names[at + 1] == "feed-wait"
    # a line holds the updates it held: none counts the discarded update
    assert [r["idx"] for r in lines] == [2, 4, 6, 8, 10, 6, 8, 10, 12, 14]
    assert all(r["n_updates"] == 2.0 for r in lines)
    assert len(calls) == 10 and svc.n_nonfinite_updates == 0.0
    _assert_books_closed(svc, main)
    assert svc.n_log_behind_dispatch == 5  # 2, 6, 10 and, again, 6, 10
    assert svc.n_log_inline == {"save": 4, "stop": 1, "empty feed": 0}
    assert _committed(cfg)[-1] == 14  # the run's last state, saved on the way out


@pytest.mark.timeout(300)
def test_a_save_and_a_trip_on_one_update_never_commit_the_unverified_state(tmp_path):
    """Where a save is due the order is read, verify, save: the trip at
    update 6 is seen before 6 could be committed."""
    svc, cfg, main, lines, rollbacks, calls = _heal_run(
        tmp_path, 29813, 10, at_calls={3}, model_save_interval=2,
    )
    assert svc.n_rollbacks == 1 and [r["idx"] for r in rollbacks] == [2]
    names = [e["name"] for e in main]
    at = names.index("rollback")
    assert names[at - 4 : at] == ["log-sync", "log-write", "diag-drain", "watchdog"]
    assert main[at - 4]["args"]["update"] == 6
    assert names[at + 1] == "feed-wait"  # and no ckpt-save of the restored index
    # every save stands right behind the watchdog's look at that very update
    saves = [i for i, n in enumerate(names) if n == "ckpt-save"]
    assert len(saves) == 2 + 4  # 2, 4; then 4, 6, 8, 10 of the second pass
    for i in saves:
        assert names[i - 4 : i] == ["log-sync", "log-write", "diag-drain", "watchdog"]
    updates = [e["args"]["update"] for e in main if e["name"] == "dispatch"]
    assert updates == list(range(1, 7)) + list(range(3, 11))
    assert svc.n_log_behind_dispatch == 0 and svc.n_log_inline["save"] == 3 + 4
    _assert_books_closed(svc, main)


@pytest.mark.timeout(300)
def test_a_spent_rollback_budget_stops_the_loop_with_the_books_closed(tmp_path, capsys):
    svc, cfg, main, lines, rollbacks, calls = _heal_run(
        tmp_path, 29815, 40, at_calls={5, 8}, model_save_interval=4, max_rollbacks=1,
    )
    # the first trip (update 10, seen behind dispatch 11) spends the budget;
    # from there every crossing is read in line, so the second trip (the
    # third look after the restore: update 10 again) stops the loop at once
    assert svc.n_rollbacks == 1 and [r["idx"] for r in rollbacks] == [4]
    assert "rollback budget exhausted (1/1" in capsys.readouterr().out
    names = [e["name"] for e in main]
    assert names[-4:] == ["log-sync", "log-write", "diag-drain", "watchdog"]
    updates = [e["args"]["update"] for e in main if e["name"] == "dispatch"]
    assert updates == list(range(1, 12)) + list(range(5, 11))
    assert [r["idx"] for r in lines] == [2, 4, 6, 8, 10, 6, 8, 10]
    assert len(calls) == 8 and svc.last_losses == calls[-1]
    _assert_books_closed(svc, main)
    assert svc.n_log_behind_dispatch == 3  # 2, 6, 10: before the budget was spent
    assert svc.n_log_inline == {"save": 3, "stop": 2, "empty feed": 0}
