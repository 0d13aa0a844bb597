"""Inference-fleet subsystem tests (ISSUE 12): the checked replica port
plan, FleetClient hedging (duplicate deduped exactly once), failover past a
SIGKILL'd replica with counters matching the injected faults, the replica's
ver-keyed never-rollback weight swap, the ReplicaTable's monotonic version
floor across evict/rejoin, and the continuous-batching replica serving real
clients end to end (the load-plane proof lives in
``examples/loadgen_smoke.py``)."""

import multiprocessing as mp
import threading
import time

import jax
import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.config import Config, MachinesConfig
from tpu_rl.fleet import FleetClient, InferenceReplica, ReplicaTable
from tpu_rl.models.families import build_family
from tpu_rl.runtime.inference_service import InferenceClient
from tpu_rl.runtime.protocol import Protocol
from tpu_rl.runtime.transport import Router

BASE = 30420  # this module's port range; test_inference_service owns 30150+


def _fleet_config(**kw):
    base = dict(
        env="CartPole-v1",
        algo="PPO",
        act_mode="remote",
        worker_num_envs=2,
        inference_batch=8,
        inference_flush_us=2000,
        inference_timeout_ms=5000,
        inference_retries=1,
        worker_step_sleep=0.0,
    )
    base.update(kw)
    return small_config(**base)


def _obs(n, cfg, seed=0):
    rng = np.random.default_rng(seed)
    return rng.standard_normal((n, int(cfg.obs_shape[0]))).astype(np.float32)


# ---------------------------------------------------------------- fakes
class _FakeReplica(threading.Thread):
    """A scripted replica: a bare ROUTER that answers ObsRequest with an Act
    reply after ``delay_s``, stamped with ``self.ver``. Lets the hedging /
    dedup / floor tests inject exact timing without real model forwards."""

    def __init__(self, port: int, delay_s: float = 0.0, ver: int = 0):
        super().__init__(daemon=True)
        self.port = port
        self.delay_s = delay_s
        self.ver = ver
        self.n_served = 0
        self._halt = threading.Event()  # not _stop: Thread owns that name
        self._router = Router("127.0.0.1", port, bind=True)

    def run(self):
        while not self._halt.is_set():
            got = self._router.recv(timeout_ms=50)
            if got is None:
                continue
            identity, proto, payload = got
            if proto != Protocol.ObsRequest or not isinstance(payload, dict):
                continue
            if self.delay_s:
                time.sleep(self.delay_s)
            n = np.asarray(payload["obs"]).shape[0]
            self._router.send(identity, Protocol.Act, {
                "seq": payload["seq"],
                "act": np.zeros((n, 1), np.float32),
                "logits": np.zeros((n, 2), np.float32),
                "log_prob": np.zeros((n, 1), np.float32),
                "ver": self.ver,
            })
            self.n_served += 1

    def close(self):
        self._halt.set()
        self.join(timeout=5)
        self._router.close()


def _fake_replica_proc(port):
    """mp target for the SIGKILL test: a real OS process serving the replica
    wire protocol, killed -9 mid-request by the test."""
    import numpy as np  # noqa: PLC0415 — spawn child re-imports

    from tpu_rl.runtime.protocol import Protocol
    from tpu_rl.runtime.transport import Router

    router = Router("127.0.0.1", port, bind=True)
    while True:
        got = router.recv(timeout_ms=100)
        if got is None:
            continue
        identity, proto, payload = got
        if proto != Protocol.ObsRequest:
            continue
        n = np.asarray(payload["obs"]).shape[0]
        router.send(identity, Protocol.Act, {
            "seq": payload["seq"],
            "act": np.zeros((n, 1), np.float32),
            "logits": np.zeros((n, 2), np.float32),
            "log_prob": np.zeros((n, 1), np.float32),
            "ver": 0,
        })


# ------------------------------------------------------------- port plan
class TestPortPlan:
    def test_explicit_range_is_consecutive(self):
        cfg = _fleet_config(inference_replicas=3, inference_base_port=31000)
        m = MachinesConfig()
        assert m.inference_ports(cfg) == [31000, 31001, 31002]

    def test_default_base_is_legacy_learner_plus_two(self):
        cfg = _fleet_config()
        m = MachinesConfig()
        assert m.inference_ports(cfg) == [m.learner_port + 2]

    def test_collision_with_learner_port_raises(self):
        m = MachinesConfig()
        cfg = _fleet_config(
            inference_replicas=2, inference_base_port=m.learner_port - 1
        )  # range [lp-1, lp+1) covers learner_port
        with pytest.raises(ValueError, match="collides"):
            m.inference_ports(cfg)

    def test_collision_with_telemetry_port_raises(self):
        # Caught at config validation already (both knobs live on Config).
        with pytest.raises(AssertionError, match="telemetry"):
            _fleet_config(
                inference_replicas=4, inference_base_port=31010,
                telemetry_port=31012,
            )

    def test_collision_with_worker_manager_port_raises(self):
        m = MachinesConfig()
        wport = m.workers[0].port
        cfg = _fleet_config(
            inference_replicas=2, inference_base_port=wport - 1
        )
        with pytest.raises(ValueError, match="worker manager"):
            m.inference_ports(cfg)

    def test_validate_rejects_bad_fleet_fields(self):
        with pytest.raises(AssertionError):
            _fleet_config(inference_replicas=0)
        with pytest.raises(AssertionError):
            _fleet_config(inference_hedge_ms=-1)
        with pytest.raises(AssertionError):
            # Hedge beyond the timeout can never fire.
            _fleet_config(inference_timeout_ms=100, inference_hedge_ms=200)
        with pytest.raises(AssertionError):
            _fleet_config(inference_mesh_data=0)
        with pytest.raises(AssertionError):
            # Range walks off the end of port space.
            _fleet_config(inference_replicas=2, inference_base_port=65535)


# ----------------------------------------------------------- fleet client
class TestFleetClient:
    def test_hedge_fires_and_duplicate_deduped_exactly_once(self):
        cfg = _fleet_config(
            inference_hedge_ms=50, inference_timeout_ms=5000,
            inference_reprobe_s=0.5,
        )
        slow = _FakeReplica(BASE, delay_s=0.3, ver=1)
        fast = _FakeReplica(BASE + 1, delay_s=0.0, ver=1)
        slow.start(), fast.start()
        cl = FleetClient(cfg, [("127.0.0.1", BASE), ("127.0.0.1", BASE + 1)])
        try:
            obs = _obs(2, cfg)
            first = np.ones(2, np.float32)
            # Bench the fast lane for a moment so the slow replica is the
            # forced primary; by hedge time (50ms) the bench has lapsed.
            cl.lanes[1].dead_until = time.monotonic() + 0.01
            got = cl.act(obs, first, retries=0)
            assert got is not None and got["ver"] == 1
            assert cl.n_hedges == 1  # fleet-hedge-fired
            assert cl.n_failovers == 1  # the winning reply was the hedge's
            # The slow primary's reply is still in flight; once it lands the
            # next round's stale-sweep discards it — counted exactly once.
            time.sleep(0.5)
            assert cl.act(obs, np.zeros(2, np.float32), retries=0) is not None
            assert cl.n_dedups == 1  # fleet-dedup-replies
        finally:
            cl.close()
            slow.close()
            fast.close()

    def test_sigkilled_replica_mid_request_fails_over(self):
        cfg = _fleet_config(
            inference_hedge_ms=50, inference_timeout_ms=5000,
            inference_reprobe_s=0.5,
        )
        ctx = mp.get_context("spawn")
        victim = ctx.Process(
            target=_fake_replica_proc, args=(BASE + 2,), daemon=True
        )
        victim.start()
        live = _FakeReplica(BASE + 3, ver=0)
        live.start()
        cl = FleetClient(
            cfg, [("127.0.0.1", BASE + 2), ("127.0.0.1", BASE + 3)]
        )
        try:
            obs = _obs(2, cfg)
            # Warm both lanes so the victim is provably serving first.
            cl.lanes[1].dead_until = time.monotonic() + 0.2
            assert cl.act(obs, np.ones(2, np.float32)) is not None
            victim.kill()  # SIGKILL, mid-run: no FIN handshake, no cleanup
            victim.join(timeout=10)
            time.sleep(0.3)  # let the lane-1 bench lapse
            # The request must still succeed — either a hedge onto the
            # surviving replica wins now, or an earlier hedge-win already
            # condemned the silent victim and selection routes around it.
            cl.lanes[1].dead_until = time.monotonic() + 0.01
            got = cl.act(obs, np.zeros(2, np.float32), retries=0)
            assert got is not None
            assert cl.n_hedges >= 1 and cl.n_failovers >= 1
            # Either way the dead lane ends up condemned with backoff armed,
            # so it no longer attracts primary traffic.
            assert cl.lanes[0].fails >= 1
            assert cl.n_timeouts == 0  # the round never exhausted the fleet
        finally:
            cl.close()
            if victim.is_alive():
                victim.kill()
            live.close()

    def test_version_floor_rejects_stale_replies(self):
        cfg = _fleet_config(
            inference_timeout_ms=300, inference_retries=0,
            inference_reprobe_s=0.2,
        )
        srv = _FakeReplica(BASE + 4, ver=5)
        srv.start()
        cl = FleetClient(cfg, [("127.0.0.1", BASE + 4)])
        try:
            obs = _obs(2, cfg)
            assert cl.act(obs, np.ones(2, np.float32)) is not None
            assert cl.floor == 5
            # The replica regresses (a restarted fake): its replies are now
            # BELOW the client's pinned floor and must be refused.
            srv.ver = 3
            got = cl.act(obs, np.zeros(2, np.float32))
            assert got is None  # no floor-respecting lane existed
            assert cl.n_floor_rejects >= 1
            assert cl.floor == 5  # the floor never moved down
        finally:
            cl.close()
            srv.close()

    def test_scaled_out_replica_adopted_by_reprobe(self):
        # ISSUE 17 satellite: a replica slot that was EMPTY when the client
        # started (autopilot scale-out lands later on the pre-planned port)
        # must be adopted without a client restart, via the piggyback
        # re-probe of condemned lanes on doubling backoff.
        cfg = _fleet_config(
            inference_hedge_ms=30, inference_timeout_ms=5000,
            inference_retries=0, inference_reprobe_s=0.2,
        )
        live = _FakeReplica(BASE + 10, ver=1)
        live.start()
        # Lane 1's port has no replica yet — exactly the scale-out shape.
        cl = FleetClient(
            cfg, [("127.0.0.1", BASE + 10), ("127.0.0.1", BASE + 11)]
        )
        late = None
        try:
            obs = _obs(2, cfg)
            first = np.ones(2, np.float32)
            # Drive until the empty lane has been tried, condemned (a hedge
            # or unlucky primary pick finds only silence there), AND
            # re-probed into the void at least once — the doubling-backoff
            # probe cadence running with nobody home.
            deadline = time.monotonic() + 10.0
            while cl.lanes[1].fails == 0 or cl.n_reprobes == 0:
                assert time.monotonic() < deadline
                assert cl.act(obs, first, retries=0) is not None
                first = np.zeros(2, np.float32)
                time.sleep(0.01)
            # The replica arrives late on the pre-planned port.
            late = _FakeReplica(BASE + 11, ver=1)
            late.start()
            # Keep offering load: once the lane's backoff lapses, a probe
            # rides along, the new replica answers, the lane revives.
            deadline = time.monotonic() + 10.0
            while cl.lanes[1].fails > 0:
                assert time.monotonic() < deadline
                assert cl.act(obs, np.zeros(2, np.float32)) is not None
                time.sleep(0.02)
            assert cl.n_reprobes >= 1
            assert cl.n_live == 2  # both lanes serving — adopted, no restart
        finally:
            cl.close()
            live.close()
            if late is not None:
                late.close()

    def test_all_lanes_dead_probes_anyway(self):
        # A blip that condemned every lane must not strand the client: the
        # least-recently-condemned lane is probed regardless.
        cfg = _fleet_config(
            inference_timeout_ms=2000, inference_reprobe_s=30.0
        )
        srv = _FakeReplica(BASE + 5, ver=0)
        srv.start()
        cl = FleetClient(cfg, [("127.0.0.1", BASE + 5)])
        try:
            cl.lanes[0].dead_until = time.monotonic() + 30.0
            assert cl.n_live == 0
            got = cl.act(_obs(2, cfg), np.ones(2, np.float32), retries=0)
            assert got is not None
            assert cl.lanes[0].dead_until == 0.0  # reply resurrected it
        finally:
            cl.close()
            srv.close()


# ------------------------------------------------------ replica versioning
class TestReplicaVersioning:
    def test_ver_keyed_swap_never_rolls_back(self):
        cfg = _fleet_config()
        family = build_family(cfg)
        params = family.init_params(jax.random.key(0), seq_len=cfg.seq_len)
        svc = InferenceReplica(cfg, family, params, port=BASE + 6, version=2)
        # No start(): the swap path is pure (lock + slot), no socket needed.
        svc.set_params({"w": 1}, version=5)
        assert svc.version == 5 and svc.n_stale_sets == 0
        svc.set_params({"w": 2}, version=3)  # re-delivered old broadcast
        assert svc.version == 5 and svc.n_stale_sets == 1
        svc.set_params({"w": 3}, version=5)  # exact duplicate: also a no-op
        assert svc.version == 5 and svc.n_stale_sets == 2
        svc.set_params({"w": 4}, version=9)
        assert svc.version == 9 and svc.n_stale_sets == 2

    def test_replica_table_floor_monotonic_across_evict_and_rejoin(self):
        clock = [0.0]
        t = ReplicaTable(lease_s=10.0, clock=lambda: clock[0])
        assert t.touch(0, ver=5) is True  # join
        assert t.touch(1, ver=3) is False or True  # rid 1 joins too
        assert t.floor == 5
        assert t.min_active_version() == 3
        clock[0] = 20.0  # both leases lapse
        assert sorted(t.evict_expired()) == [0, 1]
        assert t.active == {}
        assert t.min_active_version() == -1
        assert t.floor == 5  # the ratchet survives the eviction
        # rid 0 restarts on random-init weights (ver -1): a rejoin that must
        # NOT lower the floor clients already observed.
        assert t.touch(0, ver=-1) is True
        assert t.floor == 5
        assert t.min_active_version() == -1
        t.touch(0, ver=7)
        assert t.floor == 7 and t.min_active_version() == 7


# --------------------------------------------------- continuous batching
class TestContinuousBatching:
    def test_replica_serves_real_clients(self):
        cfg = _fleet_config(inference_flush_us=10_000_000)
        family = build_family(cfg)
        params = family.init_params(jax.random.key(0), seq_len=cfg.seq_len)
        svc = InferenceReplica(
            cfg, family, params, port=BASE + 7, version=4
        ).start()
        try:
            assert svc.wait_ready(120.0) and svc.error is None, svc.error
            cl = InferenceClient(cfg, "127.0.0.1", BASE + 7, wid=0)
            try:
                obs = _obs(2, cfg)
                first = np.ones(2, np.float32)
                for i in range(5):
                    got = cl.act(obs, first if i == 0 else np.zeros(2, np.float32))
                    assert got is not None
                    assert got["act"].shape[0] == 2
                    assert got["ver"] == 4
            finally:
                cl.close()
            # Continuous admission: a 2-row tick never reaches the 8-row
            # padded capacity, and the flush deadline above is effectively
            # infinite — only the no-deadline path can have served these.
            # (Counters increment just after the send the client already
            # consumed — give the serve thread a beat to catch up.)
            deadline = time.monotonic() + 2.0
            while svc.n_replies < 5 and time.monotonic() < deadline:
                time.sleep(0.01)
            assert svc.n_flush_continuous >= 5
            assert svc.n_flush_deadline == 0
            assert svc.n_replies >= 5
        finally:
            svc.close()

    def test_fleet_client_through_real_replicas(self):
        cfg = _fleet_config(inference_hedge_ms=0)
        family = build_family(cfg)
        params = family.init_params(jax.random.key(0), seq_len=cfg.seq_len)
        svcs = [
            InferenceReplica(
                cfg, family, params, port=BASE + 8 + i, version=1
            ).start()
            for i in range(2)
        ]
        cl = FleetClient(
            cfg, [("127.0.0.1", BASE + 8), ("127.0.0.1", BASE + 9)]
        )
        try:
            for s in svcs:
                assert s.wait_ready(120.0) and s.error is None, s.error
            obs = _obs(2, cfg)
            ok = 0
            for i in range(8):
                got = cl.act(
                    obs,
                    np.ones(2, np.float32) if i == 0
                    else np.zeros(2, np.float32),
                )
                if got is not None:
                    assert got["ver"] == 1
                    ok += 1
            assert ok == 8
            assert cl.floor == 1
            # p2c spread: with equal latency both replicas should see work.
            # (n_replies increments after the send the client may already
            # have consumed — give the serve threads a beat to catch up.)
            deadline = time.monotonic() + 2.0
            while (sum(s.n_replies for s in svcs) < 8
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert sum(s.n_replies for s in svcs) >= 8
        finally:
            cl.close()
            for s in svcs:
                s.close()


# ------------------------------------------- the model broadcast, received
@pytest.mark.timeout(240)
def test_replica_main_adopts_a_broadcast_in_the_model_layout():
    """A standalone replica takes its weights from the learner's broadcast
    (``Sub.drain`` -> ``set_params``): the frame is the Model layout — a head
    and a part a leaf — and the version it carried comes back in the
    replica's own telemetry, a client's reply says it too."""
    from tpu_rl.fleet.replica import replica_main
    from tpu_rl.runtime.protocol import Codec, encode
    from tpu_rl.runtime.transport import MODEL_HWM, Pub, Sub

    cfg = _fleet_config(telemetry_port=18140, telemetry_interval_s=0.2)
    port, model_port, stat_port = BASE + 40, BASE + 41, BASE + 42
    family = build_family(cfg)
    host = jax.device_get(family.init_params(jax.random.key(3), seq_len=cfg.seq_len)["actor"])
    payload = {"actor": host, "ver": 11, "epoch": 0, "t_tx": time.time_ns()}
    frame = encode(Protocol.Model, payload)
    assert len(frame) == 2 + len(jax.tree.leaves(host)) > 3 and frame[1][3] == Codec.PARTS
    stat_sub = Sub("127.0.0.1", stat_port, bind=True)
    model_pub = Pub("127.0.0.1", model_port, bind=True, hwm=MODEL_HWM)
    stop = threading.Event()
    rt = threading.Thread(
        target=replica_main, daemon=True,
        args=(cfg, 1, port, "127.0.0.1", model_port, stat_port, stop, None),
    )
    rt.start()
    vers = []
    try:
        deadline = time.time() + 180
        while time.time() < deadline and 11 not in vers:
            model_pub.send(Protocol.Model, payload)  # re-send: slow joiner
            got = stat_sub.recv(timeout_ms=200)
            if got is not None and got[0] == Protocol.Telemetry and got[1].get("rid") == 1:
                vers.append(got[1]["ver"])
        assert 11 in vers, f"the replica never adopted the broadcast: {vers}"
        cl = InferenceClient(cfg, "127.0.0.1", port, wid=0)
        try:
            reply = cl.act(_obs(2, cfg), np.ones(2, np.float32))
            assert reply is not None and reply["ver"] == 11
        finally:
            cl.close()
    finally:
        stop.set()
        rt.join(timeout=60)
        model_pub.close()
        stat_sub.close()
    assert vers[0] in (-1, 11)  # random-init weights until the first adoption
