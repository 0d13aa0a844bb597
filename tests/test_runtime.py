"""Runtime integration tests: the full worker -> manager -> storage -> learner
pipeline over real ZMQ + shm between real processes (SURVEY.md §4 — the
multi-process capability the reference only ever validated on live clusters).

Kept fast: tiny batch, no worker throttle, bounded updates, localhost ports.
"""

import os
import time

import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.config import MachinesConfig, WorkerMachine


def _machines(base_port: int) -> MachinesConfig:
    return MachinesConfig(
        learner_ip="127.0.0.1",
        learner_port=base_port,
        workers=[
            WorkerMachine(
                num_p=2, manager_ip="127.0.0.1", ip="127.0.0.1",
                # base+1 is the model broadcast, base+2 the centralized-
                # inference ROUTER (MachinesConfig.inference_port): the
                # worker relay port must clear both.
                port=base_port + 5,
            )
        ],
    )


def _cluster_cfg(tmp_path, **kw):
    base = dict(
        env="CartPole-v1",
        algo="PPO",
        batch_size=8,
        seq_len=5,
        hidden_size=16,
        worker_step_sleep=0.0,
        learner_device="cpu",  # deterministic CI: never touch a (possibly
        # held) real accelerator from the test cluster
        rollout_lag_sec=30.0,  # no stale drops on slow CI hosts
        time_horizon=100,
        result_dir=None,
        model_dir=str(tmp_path / "models"),
        model_save_interval=5,
        loss_log_interval=1000,
    )
    base.update(kw)
    return small_config(**base)


@pytest.mark.timeout(300)
@pytest.mark.parametrize("relay_mode", ["raw", "decode"])
def test_local_cluster_end_to_end(tmp_path, relay_mode):
    """Spawn the whole local cluster; the learner must complete updates fed
    ONLY by worker rollouts over ZMQ, then checkpoint. Runs in both relay
    modes: the zero-copy raw fan-in (manager forwards opaque wire parts,
    storage ingests whole ticks via push_tick) and the decode A/B baseline
    must be indistinguishable end-to-end (bit-level window equivalence is
    pinned separately in test_push_tick_equivalence.py)."""
    from tpu_rl.runtime.runner import local_cluster

    cfg = _cluster_cfg(tmp_path, relay_mode=relay_mode)
    base = 29100 if relay_mode == "raw" else 28100
    sup = local_cluster(cfg, _machines(base), max_updates=6)
    try:
        learner = next(c for c in sup.children if c.name == "learner")
        deadline = time.time() + 240
        while time.time() < deadline and learner.proc.is_alive():
            time.sleep(1.0)
        # learner exits after max_updates; that exit proves batches flowed
        assert not learner.proc.is_alive(), "learner never finished 6 updates"
        assert learner.proc.exitcode == 0
        # checkpoint appeared with the algo_{idx} naming
        ckpts = os.listdir(tmp_path / "models")
        assert any(name.startswith("PPO_") for name in ckpts), ckpts
    finally:
        sup.stop()


@pytest.mark.timeout(300)
def test_remote_acting_cluster_end_to_end(tmp_path):
    """The SEED-style split as real processes: workers act via the learner-
    colocated InferenceService (act_mode="remote", DEALER -> ROUTER on
    inference_port) instead of their local policy, and the learner still
    completes its update budget fed only by those remotely-acted rollouts.
    The generous inference_timeout_ms keeps CI jit-compile latency from
    silently triggering the local-acting fallback, which would let this
    test pass without exercising the remote path."""
    from tpu_rl.runtime.runner import local_cluster

    cfg = _cluster_cfg(
        tmp_path,
        act_mode="remote",
        inference_batch=4,
        inference_flush_us=2000,
        inference_timeout_ms=60_000,
    )
    sup = local_cluster(cfg, _machines(29800), max_updates=6)
    try:
        learner = next(c for c in sup.children if c.name == "learner")
        deadline = time.time() + 240
        while time.time() < deadline and learner.proc.is_alive():
            time.sleep(1.0)
        assert not learner.proc.is_alive(), (
            "learner never finished 6 updates under remote acting"
        )
        assert learner.proc.exitcode == 0
        ckpts = os.listdir(tmp_path / "models")
        assert any(name.startswith("PPO_") for name in ckpts), ckpts
    finally:
        sup.stop()


# slow: a full off-policy cluster run (~80s on this one-core box). The
# replay path stays tier-1-covered by test_train_inline's replay test and
# test_shm_ring_mp's torn-slot sampler tests; the on-policy cluster e2e
# tests below keep the supervised-runtime surface in the fast gate.
@pytest.mark.slow
@pytest.mark.timeout(300)
def test_sac_replay_cluster_end_to_end(tmp_path):
    """Off-policy path as real processes: worker rollouts -> manager ->
    storage -> seqlock ReplayStore -> SAC learner SAMPLES (not consumes) to
    N updates, then checkpoints (the reference's second storage mode,
    agents/learner.py:369-400 + storage_module/shared_batch.py:71-72)."""
    from tpu_rl.runtime.runner import local_cluster

    cfg = _cluster_cfg(
        tmp_path, algo="SAC", buffer_size=32, model_save_interval=4
    )
    sup = local_cluster(cfg, _machines(29400), max_updates=5)
    try:
        learner = next(c for c in sup.children if c.name == "learner")
        deadline = time.time() + 240
        while time.time() < deadline and learner.proc.is_alive():
            time.sleep(1.0)
        assert not learner.proc.is_alive(), "SAC learner never finished 5 updates"
        assert learner.proc.exitcode == 0
        ckpts = os.listdir(tmp_path / "models")
        assert any(name.startswith("SAC_") for name in ckpts), ckpts
    finally:
        sup.stop()


@pytest.mark.timeout(300)
def test_supervisor_restarts_dead_child(tmp_path):
    """Kill a worker; the supervisor must respawn it (the capability the
    reference ships commented out, main.py:417-473)."""
    from tpu_rl.runtime.runner import Supervisor, manager_role, worker_role

    cfg = _cluster_cfg(tmp_path)
    sup = Supervisor(heartbeat_timeout=5.0)
    machines = _machines(29200)
    manager_role(cfg, machines, supervisor=sup)
    worker_role(cfg, machines, supervisor=sup)
    try:
        w = next(c for c in sup.children if c.name.startswith("worker"))
        # wait for the worker to come up
        deadline = time.time() + 60
        while time.time() < deadline and not w.proc.is_alive():
            time.sleep(0.2)
        w.proc.kill()
        w.proc.join(10)
        assert not w.proc.is_alive()
        restarted = []
        deadline = time.time() + 30
        while time.time() < deadline and not restarted:
            restarted = sup.check()
            time.sleep(0.5)
        assert any(name.startswith("worker") for name in restarted)
        assert w.restarts == 1 and w.proc.is_alive()
    finally:
        sup.stop()


@pytest.mark.timeout(300)
def test_worker_late_join_feeds_live_cluster(tmp_path):
    """Elastic join, demonstrated rather than asserted: bring up learner +
    storage + manager with ZERO workers (the learner idles, waiting on
    data), then join a worker into the already-live topology. The learner
    completing its updates is attributable entirely to the late joiner —
    the PUB/SUB property the reference has only 'in principle' (SURVEY §5.3:
    'a late worker just SUBs and starts publishing', with no demonstration
    anywhere in the reference repo)."""
    from tpu_rl.runtime.runner import (
        Supervisor, learner_role, manager_role, worker_role,
    )

    cfg = _cluster_cfg(tmp_path)
    machines = _machines(29700)
    sup = Supervisor()
    learner_role(cfg, machines, supervisor=sup, max_updates=4)
    manager_role(cfg, machines, supervisor=sup)
    try:
        learner = next(c for c in sup.children if c.name == "learner")
        deadline = time.time() + 60
        while time.time() < deadline and not learner.proc.is_alive():
            time.sleep(0.2)
        # Let the learner/storage/manager sockets settle into their steady
        # "waiting for rollouts" state, and pin down that no data source
        # exists yet: the learner must still be blocked.
        time.sleep(5.0)
        assert learner.proc.is_alive(), "learner exited with no workers"

        worker_role(cfg, machines, supervisor=sup)  # the late join
        deadline = time.time() + 200
        while time.time() < deadline and learner.proc.is_alive():
            time.sleep(1.0)
        assert not learner.proc.is_alive(), (
            "learner never finished after the late worker joined"
        )
        assert learner.proc.exitcode == 0
        ckpts = os.listdir(tmp_path / "models")
        assert any(name.startswith("PPO_") for name in ckpts), ckpts
    finally:
        sup.stop()


@pytest.mark.timeout(180)
def test_worker_warm_start_from_checkpoint(tmp_path):
    """A worker spawned by worker_role where a checkpoint exists must act with
    the checkpoint's actor params (reference loads the newest checkpoint into
    every worker at spawn, main.py:247-252) — verified by recomputing the
    published behavior logits from the rollout's own (obs, hx, cx) under the
    checkpointed actor. A random-init worker could not reproduce them."""
    import jax
    import jax.numpy as jnp

    from tpu_rl.algos.registry import get_algo
    from tpu_rl.checkpoint import Checkpointer
    from tpu_rl.runtime.protocol import Protocol
    from tpu_rl.runtime.runner import Supervisor, worker_role
    from tpu_rl.runtime.transport import Sub

    cfg = _cluster_cfg(tmp_path)
    family, state, _ = get_algo(cfg.algo).build(cfg, jax.random.key(42))
    ck = Checkpointer(str(tmp_path / "models"), cfg.algo)
    ck.save(state, 11)
    ck.close()

    machines = _machines(29300)
    machines.workers[0].num_p = 1
    # Fake manager: bind a SUB where the worker's rollout PUB connects.
    sub = Sub("127.0.0.1", machines.workers[0].port, bind=True)
    sup = Supervisor()
    worker_role(cfg, machines, supervisor=sup)
    try:
        from tpu_rl.data.assembler import split_rollout_batch

        msg = None
        deadline = time.time() + 120
        while time.time() < deadline and msg is None:
            got = sub.recv(timeout_ms=1000)
            if got is not None and got[0] == Protocol.RolloutBatch:
                msg = split_rollout_batch(got[1])[0]
        assert msg is not None, "no rollout received from warm-started worker"
        expected = family.act(
            {"actor": state.params["actor"]},
            jnp.asarray(msg["obs"], jnp.float32)[None],
            jnp.asarray(msg["hx"], jnp.float32)[None],
            jnp.asarray(msg["cx"], jnp.float32)[None],
            jax.random.key(0),
        )[1]
        np.testing.assert_allclose(
            np.asarray(msg["logits"]), np.asarray(expected[0]),
            rtol=1e-5, atol=1e-6,
        )
    finally:
        sup.stop()
        sub.close()


@pytest.mark.timeout(300)
def test_learner_chain_matches_sequential_through_shm(tmp_path):
    """learner_chain=K in the PRODUCTION loop (VERDICT r4 #4): a
    LearnerService running K-chained dispatch fed through the REAL
    OnPolicyStore shm path must produce exactly the params that sequential
    application of the raw train_step yields on the same consumed batches
    with the same per-update keys (the service's documented key schedule:
    one split per dispatch, fold_in per in-chain update)."""
    import threading

    import jax
    import numpy as np_

    from tpu_rl.algos.registry import get_algo
    from tpu_rl.checkpoint import Checkpointer
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.data.shm_ring import OnPolicyStore, alloc_handles
    from tpu_rl.runtime.learner_service import LearnerService
    from tpu_rl.types import BATCH_FIELDS, Batch

    K, n_updates, B = 2, 4, 4
    cfg = _cluster_cfg(
        tmp_path, batch_size=B, learner_chain=K, model_save_interval=100,
    )
    layout = BatchLayout.from_config(cfg)
    handles = alloc_handles(layout, capacity=B)
    store = OnPolicyStore(handles, layout)

    wrng = np.random.default_rng(5)
    windows = []
    for _ in range(n_updates * B):
        w = {}
        for f in BATCH_FIELDS:
            shape = (layout.seq_len, layout.width(f))
            if f == "act":
                w[f] = wrng.integers(0, 2, size=shape).astype(np.float32)
            elif f == "is_fir":
                a = np.zeros(shape, np.float32)
                a[0] = 1.0
                w[f] = a
            elif f == "log_prob":
                w[f] = np.full(shape, -0.7, np.float32)
            else:
                w[f] = wrng.standard_normal(shape).astype(np.float32) * 0.1
        windows.append(w)

    def feed():
        for w in windows:
            while not store.put(w):
                time.sleep(0.001)

    feeder = threading.Thread(target=feed, daemon=True)
    feeder.start()
    svc = LearnerService(
        cfg, handles, model_port=29800, stop_event=threading.Event(),
        max_updates=n_updates, seed=0,
    )
    svc.run()
    feeder.join(timeout=30)
    assert not feeder.is_alive()

    # ---- expected: raw train_step applied sequentially, same keys ----
    spec = get_algo(cfg.algo)
    _family, state, train_step = spec.build(cfg, jax.random.key(0))
    step = jax.jit(train_step)
    key = jax.random.key(1)  # service loop key: jax.random.key(seed + 1)
    for d in range(n_updates // K):
        gen = windows[d * K * B : (d + 1) * K * B]
        key, sub = jax.random.split(key)
        for i in range(K):
            raw = {
                f: np_.stack([w[f] for w in gen[i * B : (i + 1) * B]])
                for f in BATCH_FIELDS
            }
            state, _ = step(
                state, Batch.from_mapping(raw), jax.random.fold_in(sub, i)
            )

    got, idx = Checkpointer(str(tmp_path / "models"), cfg.algo).restore_latest(
        spec.build(cfg, jax.random.key(0))[1]
    )
    assert idx == n_updates
    want = jax.tree_util.tree_leaves(state.params)
    have = jax.tree_util.tree_leaves(got.params)
    for a, b in zip(want, have, strict=True):
        np_.testing.assert_allclose(
            np_.asarray(a), np_.asarray(b), rtol=2e-5, atol=1e-6
        )


@pytest.mark.timeout(120)
def test_checkpoint_roundtrip(tmp_path):
    """Save -> restore latest preserves params, opt state, and step index."""
    import jax

    from tpu_rl.algos.registry import get_algo
    from tpu_rl.checkpoint import Checkpointer

    cfg = small_config(model_dir=str(tmp_path))
    _family, state, _ = get_algo("PPO").build(cfg, jax.random.key(0))
    ckpt = Checkpointer(str(tmp_path), "PPO", keep=2)
    assert ckpt.restore_latest(state) is None
    ckpt.save(state, 100)
    ckpt.save(state, 200)
    restored, idx = ckpt.restore_latest(state)
    assert idx == 200
    orig = jax.tree_util.tree_leaves(state.params)
    rest = jax.tree_util.tree_leaves(restored.params)
    for a, b in zip(orig, rest, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # gc keeps only the newest `keep`
    ckpt.save(state, 300)
    names = sorted(os.listdir(tmp_path))
    assert names == ["PPO_200", "PPO_300"]


def test_launch_plan_covers_all_machines(tmp_path):
    """Launcher emits rsync per host + tmux/ssh per role (reference run.py)."""
    import json

    from tpu_rl.launch import plan

    mpath = tmp_path / "machines.json"
    mpath.write_text(json.dumps({
        "learner": {"ip": "10.0.0.1", "port": 40000},
        "workers": [
            {"num_p": 4, "manager_ip": "10.0.0.2", "ip": "10.0.0.2",
             "port": 41000},
            {"num_p": 4, "manager_ip": "10.0.0.3", "ip": "10.0.0.3",
             "port": 41000},
        ],
    }))
    machines = MachinesConfig.from_json(mpath)
    cmds = plan(machines, str(mpath), None, "/repo", "me", None)
    flat = [" ".join(c) for c in cmds]
    # 3 rsyncs (unique hosts) + 1 learner + 2 managers + 2 workers
    assert sum("rsync" in c for c in flat) == 3
    assert sum("tpu_rl learner" in c for c in flat) == 1
    assert sum("tpu_rl manager" in c for c in flat) == 2
    assert sum("tpu_rl worker" in c for c in flat) == 2
    # ssh targets carry the user; machine-idx flows into worker cmds
    assert any("me@10.0.0.3" in c and "--machine-idx 1" in c for c in flat)


@pytest.mark.timeout(60)
def test_execution_timer_scalars():
    from tpu_rl.utils.timer import ExecutionTimer

    t = ExecutionTimer(num_transition=640)
    for _ in range(3):
        with t.timer("learner-throughput", check_throughput=True):
            time.sleep(0.01)
    s = t.scalars()
    assert s["learner-throughput-elapsed-mean-sec"] >= 0.01
    assert 0 < s["learner-throughput-transition-per-secs"] < 640 / 0.01


@pytest.mark.timeout(120)
def test_crash_writes_error_log(tmp_path):
    """A crashing child leaves logs/<role>/error_log_*.txt (reference
    SaveErrorLog parity, utils/utils.py:192-198)."""
    from tpu_rl.runtime.runner import Supervisor

    sup = Supervisor(log_root=str(tmp_path / "logs"), max_restarts=0)
    sup.spawn("crasher", _crash_main, cpu_only=True)
    c = sup.children[0]
    c.proc.join(60)
    assert c.proc.exitcode not in (0, None)
    logdir = tmp_path / "logs" / "crasher"
    files = list(logdir.glob("error_log_*.txt"))
    assert files, list((tmp_path / "logs").rglob("*"))
    assert "boom" in files[0].read_text()
    sup.stop()


def _crash_main(stop_event, heartbeat):
    raise RuntimeError("boom")


@pytest.mark.timeout(300)
def test_vectorized_worker_rollout():
    """worker_num_envs=4: one worker process drives 4 envs with a single
    batched act per tick and ONE framed RolloutBatch per tick (4 stacked
    transitions). Split back into steps, the stream must show 4
    concurrently-open episodes, each starting with an is_fir=1 seam, with
    per-env carries (a reset zeroes only that env's rows — observable as a
    fresh episode id whose first message carries is_fir=1)."""
    import threading

    from tpu_rl.data.assembler import split_rollout_batch
    from tpu_rl.runtime.protocol import Protocol
    from tpu_rl.runtime.transport import Pub, Sub
    from tpu_rl.runtime.worker import Worker

    base = 29500
    cfg = _cluster_cfg(
        __import__("pathlib").Path("/tmp"), worker_num_envs=4, time_horizon=12
    )
    relay_sub = Sub("127.0.0.1", base, bind=True)       # manager side
    model_pub = Pub("127.0.0.1", base + 1, bind=True)   # learner side (idle)
    stop = threading.Event()
    w = Worker(
        cfg, worker_id=0, manager_ip="127.0.0.1", manager_port=base,
        learner_ip="127.0.0.1", model_port=base + 1, stop_event=stop,
    )
    t = threading.Thread(target=w.run, daemon=True)
    t.start()
    try:
        msgs, stats = [], []
        deadline = time.time() + 120
        while time.time() < deadline and len(msgs) < 200:
            got = relay_sub.recv(timeout_ms=500)
            if got is None:
                continue
            proto, payload = got
            if proto == Protocol.RolloutBatch:
                steps = split_rollout_batch(payload)
                assert len(steps) == 4  # one frame = one 4-env tick
                msgs.extend(steps)
            else:
                stats.append(payload)
    finally:
        stop.set()
        t.join(timeout=30)
        relay_sub.close()
        model_pub.close()
    assert len(msgs) >= 200
    episodes = {}
    for m in msgs:
        episodes.setdefault(m["id"], []).append(m)
    # 4 envs x horizon 12 over 200+ steps -> several distinct episodes.
    assert len(episodes) >= 4
    # ZMQ slow-joiner: the SUB may lose a PREFIX of the stream (and only a
    # prefix — per-peer ordering is preserved), so the first few observed
    # episodes can be truncated mid-flight. Episodes that OPEN during
    # observation (first observed message has is_fir=1) are fully observed:
    # assert the seam semantics on those.
    complete = [s for s in episodes.values() if s[0]["is_fir"][0] == 1.0]
    assert len(complete) >= 4, "most episodes must be observed from their opener"
    for steps in complete:
        assert all(s["is_fir"][0] == 0.0 for s in steps[1:])
        assert steps[0]["obs"].shape == (4,)
    # Concurrency: mid-stream, 4 envs publish round-robin each tick, so any
    # 8 consecutive messages span >= 4 distinct episode ids.
    mid = len(msgs) // 2
    assert len({m["id"] for m in msgs[mid : mid + 8]}) >= 4
    # horizon-capped episodes publish their stat
    assert stats, "episode-end stats must flow"
