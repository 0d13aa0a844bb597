"""Pipelined learner feed: the PrefetchPipeline contract (ordering, clean
shutdown, error propagation), the update:data ratio gate, and bit-exact
equivalence of the pipelined and synchronous LearnerService paths through the
real shm store (ISSUE: overlap the host data plane with device compute)."""

import threading
import time

import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.data.prefetch import PrefetchPipeline, SynchronousFeed, UpdateRatioGate


# ---------------------------------------------------------------- pipeline
@pytest.mark.timeout(60)
def test_prefetch_ordering_and_no_batch_loss():
    """Every fetched batch reaches the consumer, exactly once, in fetch
    order — the no-loss/no-reorder half of the pipeline contract."""
    n = 50
    counter = iter(range(n))

    def fetch():
        return next(counter, None)

    pipe = PrefetchPipeline(fetch, lambda raws: list(raws), chain=1, depth=2)
    got = []
    deadline = time.time() + 30
    while len(got) < n and time.time() < deadline:
        item = pipe.get(timeout=0.05)
        if item is not None:
            got.append(item[0][0])
    pipe.close()
    assert got == list(range(n))
    assert pipe.dispatched == n


@pytest.mark.timeout(60)
def test_prefetch_chain_accumulation():
    """chain=K hands assemble exactly K raws per dispatch, in order."""
    counter = iter(range(12))

    def fetch():
        return next(counter, None)

    pipe = PrefetchPipeline(fetch, lambda raws: list(raws), chain=3, depth=2)
    got = []
    deadline = time.time() + 30
    while len(got) < 4 and time.time() < deadline:
        item = pipe.get(timeout=0.05)
        if item is not None:
            got.append(item[0])
    pipe.close()
    assert got == [[0, 1, 2], [3, 4, 5], [6, 7, 8], [9, 10, 11]]


@pytest.mark.timeout(60)
def test_prefetch_close_joins_blocked_feeder():
    """close() must terminate the feeder even while it is blocked putting
    into a FULL queue (nobody consuming) — the shutdown-deadlock case."""
    def fetch():
        return 1

    pipe = PrefetchPipeline(fetch, lambda raws: raws, chain=1, depth=1)
    deadline = time.time() + 10
    while pipe.qsize() < 1 and time.time() < deadline:
        time.sleep(0.01)
    assert pipe.qsize() == 1  # feeder is now blocked on the next put
    pipe.close(timeout=10)
    assert not pipe._thread.is_alive()


@pytest.mark.timeout(60)
def test_prefetch_external_stop_event():
    """The shared cluster stop event halts the feeder without close()."""
    stop = threading.Event()
    pipe = PrefetchPipeline(
        lambda: 1, lambda raws: raws, chain=1, depth=1, stop_event=stop
    )
    stop.set()
    deadline = time.time() + 10
    while pipe._thread.is_alive() and time.time() < deadline:
        time.sleep(0.01)
    assert not pipe._thread.is_alive()
    pipe.close()


@pytest.mark.timeout(60)
def test_prefetch_feeder_exception_reraises_in_consumer():
    """A feeder-thread exception must surface from get(), not hang."""
    calls = {"n": 0}

    def fetch():
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("store exploded")
        return calls["n"]

    pipe = PrefetchPipeline(fetch, lambda raws: raws[0], chain=1, depth=1)
    seen_error = False
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            pipe.get(timeout=0.05)
        except RuntimeError as e:
            assert "store exploded" in str(e)
            seen_error = True
            break
    assert seen_error
    pipe.close()


def test_prefetch_rejects_bad_depth():
    with pytest.raises(ValueError):
        PrefetchPipeline(lambda: None, lambda r: r, depth=0)


@pytest.mark.timeout(60)
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_prefetch_takes_the_queue_slot_before_it_fetches(depth):
    """Placed and not yet taken never exceeds ``depth``, the batch in
    placement included: with nobody taking, the feeder builds ``depth``
    dispatches and fetches no further one until a ``get`` makes room."""
    built = []

    def assemble(raws):
        built.append(raws[0])
        return raws[0]

    counter = iter(range(100))
    pipe = PrefetchPipeline(lambda: next(counter), assemble, chain=1, depth=depth)
    deadline = time.time() + 10
    while pipe.qsize() < depth and time.time() < deadline:
        time.sleep(0.005)
    time.sleep(0.1)  # room for a feeder that would run ahead to do so
    assert built == list(range(depth)) and pipe.qsize() == depth
    assert pipe.get(timeout=1.0)[0] == 0
    deadline = time.time() + 10
    while len(built) < depth + 1 and time.time() < deadline:
        time.sleep(0.005)
    time.sleep(0.1)
    assert built == list(range(depth + 1))  # one slot freed, one more built
    pipe.close()


@pytest.mark.timeout(60)
def test_prefetch_releases_each_batch_once_it_is_queued_and_on_its_way_out():
    """``release`` (the store's, when batches are leased) follows the
    batch's put into the queue — never its assembly alone — once per
    dispatch, in order; a feeder that stops gives back what it holds."""
    log = []
    counter = iter(range(100))
    go = threading.Event()  # the feeder starts with the pipeline's __init__

    def fetch():
        if not go.is_set():
            return None
        n = next(counter)
        log.append(("fetch", n))
        return n

    def assemble(raws):
        log.append(("assemble", raws[0]))
        return raws[0]

    pipe = PrefetchPipeline(
        fetch, assemble, chain=1, depth=2, release=lambda: log.append(("release",))
    )
    put = pipe._q.put
    pipe._q.put = lambda item: (log.append(("put", item[0])), put(item))
    go.set()
    got = [pipe.get(timeout=5.0)[0] for _ in range(5)]
    assert got == list(range(5))
    pipe.close()
    for n in range(5):
        i = log.index(("fetch", n))
        assert log[i : i + 4] == [
            ("fetch", n), ("assemble", n), ("put", n), ("release",)
        ]
    assert log[-1] == ("release",)  # the way out


def test_synchronous_feed_releases_after_assembly():
    log = []
    feed = SynchronousFeed(
        lambda: log.append("fetch") or 7,
        lambda raws: log.append("assemble") or raws[0],
        release=lambda: log.append("release"),
    )
    assert feed.get()[0] == 7
    assert log == ["fetch", "assemble", "release"]


# --------------------------------------------------------- synchronous feed
def test_synchronous_feed_accumulates_chain_across_none():
    """A starving store (fetch -> None) must preserve already-accumulated
    chain members; the dispatch completes once the store recovers."""
    seq = iter([10, None, 11, None, None, 12])

    def fetch():
        return next(seq, None)

    feed = SynchronousFeed(fetch, lambda raws: list(raws), chain=3)
    results = []
    for _ in range(6):
        item = feed.get()
        if item is not None:
            results.append(item[0])
    assert results == [[10, 11, 12]]
    feed.close()  # no-op, but part of the interface


# ------------------------------------------------------------- ratio gate
def test_update_ratio_gate_arithmetic():
    gate = UpdateRatioGate(max_ratio=0.5)  # 1 update per 2 transitions
    assert not gate.ready(0)  # no data yet: never update
    assert gate.ready(2)
    gate.note_fetched()
    assert not gate.ready(2)  # 2nd update needs >= 4 transitions
    assert not gate.ready(3)
    assert gate.ready(4)
    gate.note_fetched()
    assert not gate.ready(4)
    assert gate.ready(1000)  # plenty of headroom after a data burst


def test_update_ratio_gate_rejects_nonpositive():
    with pytest.raises(ValueError):
        UpdateRatioGate(0.0)
    with pytest.raises(ValueError):
        UpdateRatioGate(-1.0)


@pytest.mark.timeout(60)
def test_learner_fetch_honors_ratio_gate_with_stubbed_store():
    """LearnerService._make_fetch wires the gate for off-policy configs:
    fetches stall at the ratio cap and resume as transitions arrive —
    verified against a stubbed ReplayStore-shaped object."""
    from tpu_rl.runtime.learner_service import LearnerService

    cfg = small_config(
        algo="SAC", batch_size=4, max_update_data_ratio=0.1,
    )  # 1 update per 10 transitions

    class StubStore:
        def __init__(self):
            self.transitions = 0
            self.samples = 0

        def transitions_received(self):
            return self.transitions

        def sample(self, batch, rng):
            self.samples += 1
            return {"stub": self.samples}

    store = StubStore()
    svc = LearnerService(cfg, handles=None, model_port=0)
    fetch = svc._make_fetch(store, np.random.default_rng(0))

    assert fetch() is None  # no data at all: gate holds
    assert store.samples == 0

    store.transitions = 25  # budget: floor(0.1 * 25) = 2 updates
    assert fetch() == {"stub": 1}
    assert fetch() == {"stub": 2}
    assert fetch() is None  # cap reached; the store was NOT sampled
    assert store.samples == 2

    store.transitions = 30  # 3 updates earned now
    assert fetch() == {"stub": 3}
    assert fetch() is None


@pytest.mark.timeout(60)
def test_learner_fetch_no_gate_when_ratio_unset():
    """max_update_data_ratio=None (default): off-policy fetch free-runs."""
    from tpu_rl.runtime.learner_service import LearnerService

    cfg = small_config(algo="SAC", batch_size=4)

    class StubStore:
        def transitions_received(self):  # pragma: no cover — must not be used
            raise AssertionError("gateless fetch must not poll the odometer")

        def sample(self, batch, rng):
            return {"stub": 1}

    svc = LearnerService(cfg, handles=None, model_port=0)
    fetch = svc._make_fetch(StubStore(), np.random.default_rng(0))
    assert svc._feed_gate is None
    for _ in range(5):
        assert fetch() == {"stub": 1}


@pytest.mark.timeout(60)
def test_a_sharded_batch_goes_from_the_host_to_each_chip():
    """With a data mesh the assembled batch stays host memory until it is
    placed with the step's sharding: no chip ever holds the whole batch."""
    import jax

    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.obs.trace import TraceRecorder
    from tpu_rl.parallel.mesh import batch_sharding, make_mesh
    from tpu_rl.runtime.learner_service import LearnerService
    from tpu_rl.types import BATCH_FIELDS

    cfg = small_config(algo="PPO", batch_size=8, seq_len=4)
    layout = BatchLayout.from_config(cfg)
    rng = np.random.default_rng(3)
    raw = {
        f: rng.standard_normal((8, 4, layout.width(f))).astype(np.float32)
        for f in BATCH_FIELDS
    }
    svc = LearnerService(cfg, handles=None, model_port=0)
    svc._place_global = svc._chain_mesh = None
    svc._tracer = TraceRecorder(capacity=0)
    svc._batch_sharding = batch_sharding(make_mesh(4))
    svc._device = jax.devices()[0]

    host = svc._to_batch(dict(raw))
    assert all(isinstance(getattr(host, f), np.ndarray) for f in BATCH_FIELDS)
    placed = svc._assemble_device([dict(raw, ver=np.zeros(8))])
    for f in BATCH_FIELDS:
        x = getattr(placed, f)
        assert x.sharding == svc._batch_sharding and x.dtype == np.float32
        assert {s.data.shape[0] for s in x.addressable_shards} == {2}
        np.testing.assert_array_equal(np.asarray(x), raw[f])

    svc._batch_sharding = None  # one chip: placed whole, as before
    one = svc._assemble_device([dict(raw)])
    assert one.obs.sharding.device_set == {svc._device}
    np.testing.assert_array_equal(np.asarray(one.obs), raw["obs"])


@pytest.mark.parametrize(
    "algo, chain, how",
    [("PPO", 1, "lease"), ("PPO", 2, "consume"), ("IMPALA", 1, "lease"),
     ("SAC", 1, "sample"), ("SAC", 2, "sample")],
)
def test_the_feed_leases_where_it_holds_one_raw_batch_at_a_time(algo, chain, how):
    """What the feed sees decides: on-policy and unchained, the store's
    hand-over is a lease (and the feed is given the release); a chained
    dispatch, which holds ``chain`` raw batches at once, and a replay sample
    are copies. The two counters say which it was."""
    from tpu_rl.runtime.learner_service import LearnerService

    calls = []

    class StubStore:
        def lease(self):
            calls.append("lease")
            return {"stub": 1}

        def consume(self):
            calls.append("consume")
            return {"stub": 1}

        def sample(self, batch, rng):
            calls.append("sample")
            return {"stub": 1}

        def release(self):
            calls.append("release")

    cfg = small_config(algo=algo, batch_size=4, learner_prefetch=0)
    svc = LearnerService(cfg, handles=None, model_port=0)
    svc._assemble_device = lambda raws: raws  # the placement is another test's
    feed = svc._make_feed(StubStore(), np.random.default_rng(0), chain)
    assert feed.get() is not None
    leased = how == "lease"
    assert calls == [how] * chain + ["release"] * leased
    assert svc.n_feed == {"leased": chain * leased, "copied": chain * (not leased)}


@pytest.mark.timeout(60)
@pytest.mark.parametrize("mesh_data", [1, 4])
def test_a_placed_batch_does_not_change_when_the_writer_refills_its_generation(
    mesh_data,
):
    """The CPU backend's ``device_put`` may alias an aligned host buffer, and
    a leased batch *is* the store's shared memory: what the learner was
    handed must stay what it was when the lease is released and the writer
    fills that generation again."""
    import jax

    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.data.shm_ring import OnPolicyStore, alloc_handles
    from tpu_rl.obs.trace import TraceRecorder
    from tpu_rl.parallel.mesh import batch_sharding, make_mesh
    from tpu_rl.runtime.learner_service import LearnerService
    from tpu_rl.types import BATCH_FIELDS

    B = 8
    # widths of 16 floats: rows of 64 bytes, which the backend may alias
    cfg = small_config(algo="PPO", batch_size=B, seq_len=4, obs_shape=(16,))
    layout = BatchLayout.from_config(cfg)
    store = OnPolicyStore(alloc_handles(layout, B), layout)
    rng = np.random.default_rng(3)

    def windows():
        return [
            {
                f: rng.standard_normal((4, layout.width(f))).astype(np.float32)
                for f in BATCH_FIELDS
            }
            for _ in range(B)
        ]

    svc = LearnerService(cfg, handles=None, model_port=0)
    svc._place_global = svc._chain_mesh = None
    svc._tracer = TraceRecorder(capacity=0)
    svc._device = jax.devices()[0]
    svc._batch_sharding = batch_sharding(make_mesh(mesh_data)) if mesh_data > 1 else None
    svc._leased = True

    placed, want = [], []
    for _ in range(6):  # each generation three times over
        first = windows()
        assert store.put_many(first, vers=list(range(B))) == B
        raw = store.lease()
        assert all(np.shares_memory(raw[f], store.views[f]) for f in BATCH_FIELDS)
        want.append({f: np.stack([w[f] for w in first]) for f in BATCH_FIELDS})
        placed.append(svc._assemble_device([raw]))
        store.release()
    for batch, was in zip(placed, want):
        for f in BATCH_FIELDS:
            np.testing.assert_array_equal(np.asarray(getattr(batch, f)), was[f])


# ------------------------------------------------- service-level equivalence
def _run_service_to_checkpoint(tmp_path, tag, port, prefetch, chain=2):
    """Run a LearnerService through the REAL OnPolicyStore shm path on a
    deterministic window stream; return the checkpointed final state."""
    import jax

    from tpu_rl.algos.registry import get_algo
    from tpu_rl.checkpoint import Checkpointer
    from tpu_rl.data.layout import BatchLayout
    from tpu_rl.data.shm_ring import OnPolicyStore, alloc_handles
    from tpu_rl.runtime.learner_service import LearnerService
    from tpu_rl.types import BATCH_FIELDS

    n_updates, B = 4, 4
    cfg = small_config(
        env="CartPole-v1",
        algo="PPO",
        batch_size=B,
        seq_len=5,
        hidden_size=16,
        learner_chain=chain,
        learner_prefetch=prefetch,
        learner_device="cpu",
        result_dir=None,
        model_dir=str(tmp_path / f"models_{tag}"),
        model_save_interval=100,
        loss_log_interval=1000,
    )
    layout = BatchLayout.from_config(cfg)
    handles = alloc_handles(layout, capacity=B)
    store = OnPolicyStore(handles, layout)

    wrng = np.random.default_rng(7)
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
        cfg, handles, model_port=port, stop_event=threading.Event(),
        max_updates=n_updates, seed=0,
    )
    svc.run()
    feeder.join(timeout=30)
    assert not feeder.is_alive()

    spec = get_algo(cfg.algo)
    template = spec.build(cfg, jax.random.key(0))[1]
    got, idx = Checkpointer(
        str(tmp_path / f"models_{tag}"), cfg.algo
    ).restore_latest(template)
    assert idx == n_updates
    return got, svc


@pytest.mark.timeout(300)
@pytest.mark.parametrize("chain", [2, 1])
def test_pipelined_matches_synchronous_bit_exact(tmp_path, chain):
    """The acceptance bar: learner_prefetch=2 and learner_prefetch=0 produce
    BIT-IDENTICAL final params on the same window stream — the pipeline
    changes timing, never data, order, or the key schedule. Unchained, both
    feeds lease their batches from the store; chained, both copy them out."""
    import jax

    sync_state, sync_svc = _run_service_to_checkpoint(
        tmp_path, "sync", port=29850 + 4 * chain, prefetch=0, chain=chain
    )
    pipe_state, pipe_svc = _run_service_to_checkpoint(
        tmp_path, "pipe", port=29851 + 4 * chain, prefetch=2, chain=chain
    )
    how = "leased" if chain == 1 else "copied"
    for svc in (sync_svc, pipe_svc):
        assert svc.n_feed[how] >= 4 and sum(svc.n_feed.values()) == svc.n_feed[how]
    want = jax.tree_util.tree_leaves(sync_state.params)
    have = jax.tree_util.tree_leaves(pipe_state.params)
    assert want and len(want) == len(have)
    for a, b in zip(want, have, strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # The new pipeline instrumentation must have populated its windows.
    scalars = pipe_svc.timer.scalars()
    assert "learner-queue-wait-time-elapsed-mean-sec" in scalars
    assert "learner-batching-time-elapsed-mean-sec" in scalars
    assert "learner-queue-depth-mean" in scalars
    assert scalars["learner-throughput-transition-per-secs"] > 0
