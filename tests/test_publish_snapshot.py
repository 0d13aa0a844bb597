"""The weight broadcast's device snapshot: one program for the whole actor tree
on the caller's lane, the D2H on the publisher's (``AsyncPublisher``,
``snapshot_tree`` in ``tpu_rl/runtime/learner_service.py``)."""

import contextlib
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_rl.obs.trace import TraceRecorder
from tpu_rl.runtime.learner_service import AsyncPublisher, snapshot_tree
from tpu_rl.runtime.protocol import Protocol


def _tree(n_leaves: int, cols: int):
    """Leaves of pairwise different shapes (a per-leaf copy would be one
    program each); ``cols`` keeps each test's programs its own, whatever ran
    before it in the process."""
    return {
        f"leaf{i:02d}": jnp.arange((i + 1) * cols, dtype=jnp.float32).reshape(i + 1, cols)
        for i in range(n_leaves)
    }


class GatedTracer:
    """``span`` that holds the named span's entry until ``gate`` is set."""

    def __init__(self, hold: str):
        self.hold = hold
        self.gate = threading.Event()

    @contextlib.contextmanager
    def span(self, name, tid="main", args=None):
        if name == self.hold:
            assert self.gate.wait(timeout=30)
        yield


class BlockedPub:
    """A ``Pub`` whose ``send`` blocks until ``gate`` is set."""

    def __init__(self):
        self.gate = threading.Event()
        self.entered = threading.Event()
        self.sent = []

    def send(self, proto, payload):
        self.entered.set()
        assert self.gate.wait(timeout=30)
        self.sent.append((proto, payload))


def _until(cond, timeout=30.0):
    """Poll ``cond`` (no assertion on how long it took)."""
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, "condition never held"
        time.sleep(0.002)


@pytest.mark.parametrize("how", ["recv", "drain"])
def test_snapshot_survives_donation_and_the_sub_receives_the_same_bits(how):
    from tpu_rl.runtime.transport import MODEL_HWM, Pub, Sub

    port = 29761 + (how == "drain")
    sub = Sub("127.0.0.1", port, bind=True)
    pub = Pub("127.0.0.1", port, bind=False)
    tracer = GatedTracer(hold="publish-d2h")
    publisher = None
    try:
        for _ in range(100):  # PUB/SUB slow joiner
            pub.send(Protocol.Stat, -1.0)
            if sub.recv(timeout_ms=100) is not None:
                break
        else:
            pytest.fail("subscription never propagated")
        actor = _tree(56, cols=3)
        # copies: on the CPU device_get's arrays alias the buffers, and an
        # aliased buffer is not donated
        want = jax.tree.map(np.array, jax.device_get(actor))
        src = {x.unsafe_buffer_pointer() for x in jax.tree.leaves(actor)}
        snap = snapshot_tree(actor)
        assert src.isdisjoint(x.unsafe_buffer_pointer() for x in jax.tree.leaves(snap))
        del snap
        publisher = AsyncPublisher(pub, tracer)
        publisher.publish(actor, ver=7, epoch=2)  # held before its D2H
        # the next train step donates the state the snapshot was taken from
        step = jax.jit(lambda t: jax.tree.map(lambda x: x * 0 - 1, t), donate_argnums=0)
        jax.block_until_ready(step(actor))
        assert all(x.is_deleted() for x in jax.tree.leaves(actor))
        tracer.gate.set()

        if how == "recv":
            while True:
                msg = sub.recv(timeout_ms=10_000)
                assert msg is not None
                if msg[0] == Protocol.Model:
                    break
        else:  # as the worker and the replica take it
            got = []
            _until(lambda: got.extend(
                m for m in sub.drain(max_msgs=MODEL_HWM) if m[0] == Protocol.Model) or got)
            msg = got[0]
        assert sub.n_rejected == 0
        got = msg[1]
        assert set(got) == {"actor", "ver", "epoch", "t_tx"}
        assert (got["ver"], got["epoch"]) == (7, 2)
        assert isinstance(got["t_tx"], int) and 0 < got["t_tx"] <= time.time_ns()
        assert jax.tree.structure(got["actor"]) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got["actor"]), jax.tree.leaves(want)):
            assert a.dtype == b.dtype and a.shape == b.shape
            assert a.tobytes() == np.asarray(b).tobytes()
    finally:
        tracer.gate.set()
        if publisher is not None:
            publisher.close()
        pub.close()
        sub.close()


def test_publish_is_one_program_and_starts_no_transfer_on_the_caller(monkeypatch):
    from jax._src.array import ArrayImpl

    compiles, counting = [], [True]

    def on_event(event, _secs, **_kw):
        if counting[0] and event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    transfers = []
    start_transfer = ArrayImpl.copy_to_host_async

    def spy(self):
        transfers.append(threading.current_thread().name)
        return start_transfer(self)

    monkeypatch.setattr(ArrayImpl, "copy_to_host_async", spy)
    pub = BlockedPub()
    pub.gate.set()
    publisher = AsyncPublisher(pub, TraceRecorder(capacity=0))
    try:
        actor = _tree(56, cols=5)
        jax.block_until_ready(actor)
        n0 = len(compiles)
        publisher.publish(actor, ver=1)
        # 56 shapes, and two executables for all of them: the snapshot into
        # new buffers and the one into a superseded snapshot's, both run here
        assert len(compiles) - n0 == 2
        other = jax.tree.map(lambda x: x + 1, actor)
        n1 = len(compiles)
        for ver in range(2, 8):  # slot empty or not: no later call compiles
            publisher.publish(other if ver % 2 else actor, ver=ver)
            time.sleep(0.01 * (ver % 3))
        assert len(compiles) == n1
    finally:
        publisher.close()
        counting[0] = False
    assert pub.sent[-1][1]["ver"] == 7
    # every transfer was started by the publisher thread, for what it sent
    assert set(transfers) == {"learner-publish"}
    assert len(transfers) == 56 * publisher.n_sent


def test_latest_wins_under_a_blocked_pub_and_close_flushes():
    pub = BlockedPub()
    publisher = AsyncPublisher(pub, TraceRecorder(capacity=0))
    n = 6
    try:
        publisher.publish(_tree(4, cols=7), ver=1)
        assert pub.entered.wait(timeout=30)  # ver 1 is in the blocked send
        for ver in range(2, n + 1):
            publisher.publish(_tree(4, cols=7), ver=ver)
        assert publisher._pending[1] == n  # 2..n-1 were superseded unsent
        assert (publisher.n_snapshots, publisher.n_sent) == (n, 0)
        pub.gate.set()
    finally:
        pub.gate.set()
        publisher.close()
    assert [p["ver"] for _, p in pub.sent] == [1, n]
    assert all(proto == Protocol.Model for proto, _ in pub.sent)
    assert publisher.n_snapshots == n and publisher.n_sent == 2
    np.testing.assert_array_equal(
        pub.sent[-1][1]["actor"]["leaf03"], np.arange(28, dtype=np.float32).reshape(4, 7)
    )


def test_every_snapshot_is_sent_when_the_publisher_is_given_time():
    """sent / snapshots is the share that reaches the wire: 1 when nothing is
    published while a send is under way. The span says what went out, and the
    bytes are counted."""
    from tpu_rl.runtime.protocol import encode, frame_args
    from tpu_rl.runtime.transport import Pub

    pub = Pub("127.0.0.1", 29763, bind=True)
    tracer = TraceRecorder(capacity=256)
    publisher = AsyncPublisher(pub, tracer)
    n = 5
    try:
        for ver in range(1, n + 1):
            publisher.publish(_tree(9, cols=19), ver=ver, epoch=1)
            _until(lambda: publisher.n_sent == ver)  # noqa: B023
    finally:
        publisher.close()
        pub.close()
    assert publisher.n_sent == publisher.n_snapshots == n
    sends = [e for e in tracer.entries()[0] if e[:2] == ["publisher", "publish-send"]]
    assert len(sends) == n
    want = frame_args(encode(Protocol.Model, {
        "actor": jax.device_get(_tree(9, cols=19)), "ver": 1, "epoch": 1, "t_tx": time.time_ns()}))
    assert want["parts"] == 2 + 9 and want["codec"] == "PARTS"
    for *_, args in sends:
        assert args == want  # bytes, parts, codec
    assert publisher.n_bytes == n * want["bytes"]
    assert want["bytes"] > sum(4 * (i + 1) * 19 for i in range(9))  # the leaves and the head


def test_superseded_snapshots_are_recycled_not_piled_up():
    pub = BlockedPub()
    publisher = AsyncPublisher(pub, TraceRecorder(capacity=0))
    shapes = {(i + 1, 17) for i in range(3)}
    try:
        publisher.publish(_tree(3, cols=17), ver=1)
        assert pub.entered.wait(timeout=30)  # the publisher is busy from here on
        for ver in range(2, 10):
            before = publisher._pending
            publisher.publish(_tree(3, cols=17), ver=ver)
            if before is not None:  # its buffers went to the new snapshot
                assert all(x.is_deleted() for x in jax.tree.leaves(before[0]))
            del before
            # the slot's snapshot only (the one being sent is on the host)
            assert sum(x.shape in shapes for x in jax.live_arrays()) == 3
        pub.gate.set()
    finally:
        pub.gate.set()
        publisher.close()
    assert [p["ver"] for _, p in pub.sent] == [1, 9]
    np.testing.assert_array_equal(
        pub.sent[-1][1]["actor"]["leaf02"], np.arange(51, dtype=np.float32).reshape(3, 17)
    )


def test_the_publisher_lets_go_of_the_device_snapshot_before_the_send():
    pub = BlockedPub()
    publisher = AsyncPublisher(pub, TraceRecorder(capacity=0))
    try:
        publisher.publish(_tree(3, cols=11), ver=1)
        assert pub.entered.wait(timeout=30)
        time.sleep(0.05)
        live = [
            x for x in jax.live_arrays()
            if x.shape in {(1, 11), (2, 11), (3, 11)}
        ]
        assert live == []  # source tree and snapshot both gone: host copy only
        pub.gate.set()
    finally:
        pub.gate.set()
        publisher.close()
    assert publisher.n_sent == 1


def test_snapshot_tree_keeps_structure_dtype_and_placement():
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("data",))
    replicated = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    tree = {
        "w": jax.device_put(jnp.ones((4, 13), jnp.bfloat16), replicated),
        "b": (jax.device_put(jnp.arange(13, dtype=jnp.int32), replicated),),
    }
    snap = snapshot_tree(tree)
    assert jax.tree.structure(snap) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(tree)):
        assert a.dtype == b.dtype and a.sharding == b.sharding
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- a tree no frame can carry
class RecordingPub:
    def __init__(self):
        self.frames = []

    def send(self, proto, payload):
        from tpu_rl.runtime.protocol import encode

        self.frames.append(encode(proto, payload))


def _service(act_mode: str, publisher=None):
    from tpu_rl.config import Config
    from tpu_rl.runtime.learner_service import LearnerService

    svc = LearnerService(Config.from_dict({"act_mode": act_mode}), None, model_port=0)
    svc._publisher = publisher
    svc.run_epoch = 3
    return svc


class _State:
    def __init__(self, actor):
        self.params = {"actor": actor}


@pytest.mark.parametrize("asynchronous", [False, True], ids=["sync", "async-publisher"])
def test_a_tree_over_the_frame_cap_is_never_snapshotted_packed_or_sent(
    monkeypatch, capsys, asynchronous
):
    from tpu_rl.runtime import learner_service, protocol

    actor = _tree(6, cols=41)  # 6 leaves, 3444 bytes
    nbytes = sum(x.nbytes for x in jax.tree.leaves(actor))
    monkeypatch.setattr(protocol, "_MAX_RAW", nbytes + protocol._FRAMING_SLACK - 1)

    def forbidden(*_a, **_k):
        raise AssertionError("an oversize tree reached the snapshot or the codec")

    monkeypatch.setattr(protocol, "pack", forbidden)
    monkeypatch.setattr(learner_service, "snapshot_tree", forbidden)
    pub = RecordingPub()

    class Publisher:
        publish = staticmethod(forbidden)

    svc = _service("remote", Publisher() if asynchronous else None)
    for ver in range(3):
        svc._publish(pub, _State(actor), ver=ver)
    assert svc.n_publish_oversize == 3 and pub.frames == []
    out = capsys.readouterr().out
    assert out.count("exceeds the broadcast frame cap") == 1  # logged once
    assert str(nbytes) in out

    # workers that act locally could never be given this policy
    with pytest.raises(ValueError, match="act_mode='remote'"):
        _service("local")._publish(pub, _State(actor), ver=0)


def test_a_tree_under_the_frame_cap_is_sent_byte_for_byte_as_before(monkeypatch):
    from tpu_rl.runtime import learner_service, protocol
    from tpu_rl.runtime.protocol import Codec, encode

    actor = _tree(6, cols=43)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(actor))
    monkeypatch.setattr(learner_service.time, "time_ns", lambda: 1234567)
    want = encode(Protocol.Model, {
        "actor": jax.device_get(actor), "ver": 9, "epoch": 3, "t_tx": 1234567})
    monkeypatch.setattr(protocol, "_MAX_RAW", nbytes + protocol._FRAMING_SLACK)  # just fits
    for mode in ("local", "remote"):
        pub = RecordingPub()
        svc = _service(mode)
        svc._publish(pub, _State(actor), ver=9)
        assert pub.frames == [want] and svc.n_publish_oversize == 0
        assert len(want) == 2 + 6 and want[1][3] == Codec.PARTS  # head + a part a leaf
    # and through the publisher thread: the same tree, version and epoch
    pub = BlockedPub()
    pub.gate.set()
    publisher = AsyncPublisher(pub, TraceRecorder(capacity=0))
    try:
        svc = _service("local", publisher)
        svc._publish(pub, _State(actor), ver=9)
    finally:
        publisher.close()
    (proto, payload), = pub.sent
    assert proto == Protocol.Model and (payload["ver"], payload["epoch"]) == (9, 3)
    for a, b in zip(jax.tree.leaves(payload["actor"]), jax.tree.leaves(actor)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
