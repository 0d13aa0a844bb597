"""Multi-process race coverage for ``tpu_rl/data/shm_ring.py`` (ISSUE 8
satellite): the seqlock torn-read retry in ``ReplayStore.sample`` and the
generation-counter race in ``OnPolicyStore.put`` are only real when the
writer is a separate OS process scribbling into the shared arrays while this
process reads. The single-process tests in test_data_plane.py can never
produce a torn slot; these can."""

import multiprocessing as mp
import os
import time

import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.data.layout import BatchLayout
from tpu_rl.data.shm_ring import (
    OnPolicyStore,
    ReplayStore,
    alloc_handles,
)
from tpu_rl.types import BATCH_FIELDS

_CTX = mp.get_context("fork")  # fork: children inherit module state directly


def _layout() -> BatchLayout:
    return BatchLayout.from_config(small_config())


def _window(layout: BatchLayout, value: float) -> dict:
    """A trajectory window with EVERY float equal to ``value`` — any mix of
    two writes (a torn read) shows up as a non-uniform row."""
    return {
        f: np.full((layout.seq_len, layout.width(f)), value, np.float32)
        for f in BATCH_FIELDS
    }


def _row_values(batch: dict) -> np.ndarray:
    """(n, total_floats) view of a consumed/sampled batch for uniformity
    checks."""
    n = next(iter(batch.values())).shape[0]
    return np.concatenate(
        [batch[f].reshape(n, -1) for f in BATCH_FIELDS], axis=1
    )


def _assert_untorn(batch: dict) -> np.ndarray:
    rows = _row_values(batch)
    mins, maxs = rows.min(axis=1), rows.max(axis=1)
    torn = mins != maxs
    assert not torn.any(), f"torn trajectories at rows {np.nonzero(torn)[0]}"
    return mins  # the per-row write id


# --------------------------------------------------------------- ReplayStore
def _replay_writer(handles, n_puts, stop):
    layout = _layout()
    store = ReplayStore(handles, layout)
    i = 0
    while i < n_puts and not stop.is_set():
        store.put(_window(layout, float(i)))
        i += 1
    os._exit(0)


@pytest.mark.timeout(120)
def test_replay_sampler_never_returns_torn_slot_under_live_writer():
    """A child process overwrites the ring as fast as it can while this
    process samples continuously: every returned trajectory must be
    internally uniform (the seqlock re-draw), and sampling must keep
    succeeding (the retry budget isn't livelocked by a busy writer)."""
    layout = _layout()
    capacity = 16  # small ring: overwrites hit sampled slots constantly
    handles = alloc_handles(layout, capacity, ctx=_CTX)
    store = ReplayStore(handles, layout)
    stop = _CTX.Event()
    writer = _CTX.Process(
        target=_replay_writer, args=(handles, 200_000, stop), daemon=True
    )
    writer.start()
    try:
        while store.size < capacity:  # wait for the first full lap
            time.sleep(0.001)
        rng = np.random.default_rng(0)
        n_ok = n_not_ready = 0
        seen_ids = set()
        deadline = time.time() + 5.0
        while time.time() < deadline and writer.is_alive():
            got = store.sample(8, rng)
            if got is None:
                n_not_ready += 1  # retry budget exhausted this round: legal
                continue
            ids = _assert_untorn(got)
            seen_ids.update(float(v) for v in ids)
            n_ok += 1
        assert n_ok > 100, (n_ok, n_not_ready)
        assert len(seen_ids) > capacity  # samples span many writer laps
    finally:
        stop.set()
        writer.join(30)
        if writer.is_alive():
            writer.terminate()


def _torn_prober(handles, found_odd, stop):
    # Watch the version words directly: seeing an odd value proves a write
    # was in flight while we looked — i.e. the race is real, not theoretical.
    layout = _layout()
    store = ReplayStore(handles, layout)
    while not stop.is_set():
        if (store.versions % 2 == 1).any():
            found_odd.value = 1
            return
    os._exit(0)


@pytest.mark.timeout(120)
def test_replay_writer_actually_exposes_mid_write_versions():
    """Sanity for the test above: the seqlock's odd (write-in-progress) state
    is observable cross-process, so the sampler's retry path is exercised for
    real rather than vacuously."""
    layout = _layout()
    handles = alloc_handles(layout, 8, ctx=_CTX)
    found_odd = _CTX.Value("i", 0)
    stop = _CTX.Event()
    prober = _CTX.Process(
        target=_torn_prober, args=(handles, found_odd, stop), daemon=True
    )
    prober.start()
    store = ReplayStore(handles, layout)
    try:
        deadline = time.time() + 30
        i = 0
        while time.time() < deadline and not found_odd.value:
            store.put(_window(layout, float(i)))
            i += 1
        assert found_odd.value == 1, "prober never saw an in-flight write"
    finally:
        stop.set()
        prober.join(30)
        if prober.is_alive():
            prober.terminate()


# ------------------------------------------------------------- OnPolicyStore
def _onpolicy_writer(handles, stop, n_accepted):
    layout = _layout()
    store = OnPolicyStore(handles, layout)
    i = 0
    while not stop.is_set():
        if store.put(_window(layout, float(i))):
            with n_accepted.get_lock():
                n_accepted.value += 1
            i += 1
        # put() == False: generation full, consumer hasn't drained yet — spin.
    os._exit(0)


@pytest.mark.timeout(120)
def test_onpolicy_consume_never_yields_torn_window_under_live_writer():
    """A live writer in another process against consume(): a sealed
    generation is not the writer's, so every consumed batch is free of torn
    or half-written windows, and accepted puts must be conserved (consumed +
    currently-buffered == accepted)."""
    layout = _layout()
    capacity = 8
    handles = alloc_handles(layout, capacity, ctx=_CTX)
    store = OnPolicyStore(handles, layout)
    stop = _CTX.Event()
    n_accepted = _CTX.Value("q", 0)
    writer = _CTX.Process(
        target=_onpolicy_writer, args=(handles, stop, n_accepted), daemon=True
    )
    writer.start()
    try:
        n_batches = 0
        n_rows = 0
        deadline = time.time() + 5.0
        while time.time() < deadline and writer.is_alive():
            got = store.consume()
            if got is None:
                continue
            ids = _assert_untorn(got)
            assert len(ids) == capacity  # consume-all contract
            n_rows += len(ids)
            n_batches += 1
        assert n_batches > 20, "consumer never kept up with the writer"
        # Stop the writer, then drain what's left: every accepted put is
        # either already consumed or still sitting in the store — the gen
        # race loses nothing and duplicates nothing.
        stop.set()
        writer.join(30)
        assert not writer.is_alive()
        while store.size:  # a sealed generation first, then the partial one
            last = store.consume(need=min(store.size, capacity))
            _assert_untorn(last)
            n_rows += len(_row_values(last))
        assert n_rows == n_accepted.value
    finally:
        stop.set()
        writer.join(5)
        if writer.is_alive():
            writer.terminate()


@pytest.mark.timeout(120)
def test_onpolicy_generation_race_is_actually_hit():
    """Force the consume-intervenes-mid-put interleaving deterministically
    (the race the reference ignores; here only a ``consume(need=k)`` of the
    generation still being filled can start it): patch the writer-side store
    so the consume happens between the slot write and the epoch re-check.
    put() must detect the stale epoch and write again — the consumed-next
    batch sees the window."""
    layout = _layout()
    handles = alloc_handles(layout, 4, ctx=_CTX)
    writer = OnPolicyStore(handles, layout)
    reader = OnPolicyStore(handles, layout)
    for i in range(3):
        assert writer.put(_window(layout, float(i)))
    races = {"n": 0}
    orig = OnPolicyStore._write_slot

    def racy_write(self, slot, window):
        orig(self, slot, window)
        if races["n"] == 0:  # consume exactly once, mid-put
            races["n"] += 1
            got = reader.consume(need=3)  # the 3 published windows
            assert got is not None and len(_row_values(got)) == 3
    writer._write_slot = racy_write.__get__(writer)
    try:
        assert writer.put(_window(layout, 99.0))  # retried into new gen
    finally:
        writer._write_slot = orig.__get__(writer)
    assert races["n"] == 1
    assert writer.size == 1  # landed in the post-consume generation
    got = reader.consume(need=1)
    assert got is not None
    assert (_row_values(got) == 99.0).all()


# ------------------------------------------ two generations, two processes
_SPAWN = mp.get_context("spawn")  # what the runner starts its roles with


def _numbered_writer(handles, n_windows, burst, accepted, attempts, stop):
    """Windows numbered from 0 in order, each stamped with its number as its
    policy version, in bursts; a short count is tried again from where it
    stopped, as storage requeues."""
    layout = _layout()
    store = OnPolicyStore(handles, layout)
    i = 0
    while i < n_windows and not stop.is_set():
        ids = range(i, min(i + burst, n_windows))
        n = store.put_many(
            [_window(layout, float(k)) for k in ids], vers=list(ids)
        )
        i += n
        accepted.value = i
        attempts.value += 1
    os._exit(0)


def _start_numbered_writer(handles, n_windows, burst=3):
    accepted, attempts = _SPAWN.Value("q", 0), _SPAWN.Value("q", 0)
    stop = _SPAWN.Event()
    proc = _SPAWN.Process(
        target=_numbered_writer,
        args=(handles, n_windows, burst, accepted, attempts, stop),
        daemon=True,
    )
    proc.start()
    return proc, accepted, attempts, stop


def _wait(cond, timeout=60.0) -> bool:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.001)
    return cond()


def _stop(proc, stop) -> None:
    stop.set()
    proc.join(10)
    if proc.is_alive():
        proc.terminate()


@pytest.mark.timeout(180)
@pytest.mark.parametrize("how", ["lease", "consume"])
def test_two_generations_hand_every_window_over_once_in_order(how):
    """Single writer and single reader in two (spawned) processes over two
    generations: every window arrives exactly once, in the order written,
    never torn, with its ``ver`` beside it — by lease (read in place while
    the writer fills the other generation) and by copy."""
    layout = _layout()
    capacity, n_batches = 8, 60
    handles = alloc_handles(layout, capacity, ctx=_SPAWN)
    reader = OnPolicyStore(handles, layout)
    n_windows = capacity * n_batches + 5  # the last generation stays partial
    proc, accepted, _attempts, stop = _start_numbered_writer(handles, n_windows)
    try:
        expect = 0
        while expect < capacity * n_batches:
            got = reader.lease() if how == "lease" else reader.consume()
            if got is None:
                assert proc.is_alive() or reader.size, "writer died early"
                continue
            ids = _assert_untorn(got)
            want = np.arange(expect, expect + capacity)
            np.testing.assert_array_equal(ids, want)
            np.testing.assert_array_equal(got["ver"], want)
            if how == "lease":
                # the writer may be a generation ahead, never in this one
                time.sleep(0.0005)
                np.testing.assert_array_equal(_assert_untorn(got), want)
                reader.release()
            expect += capacity
        assert _wait(lambda: accepted.value == n_windows)
        tail = reader.consume(need=5)
        np.testing.assert_array_equal(
            _assert_untorn(tail), np.arange(expect, n_windows)
        )
        assert tail["ver"].tolist() == list(range(expect, n_windows))
        assert reader.size == 0
    finally:
        _stop(proc, stop)


@pytest.mark.timeout(180)
def test_writer_is_short_while_a_lease_is_out_and_goes_on_at_its_release():
    """With one generation leased and the other full the writer's process
    gets short counts — and keeps coming back for more, it is never blocked —
    and goes on the moment the lease is released."""
    layout = _layout()
    capacity = 4
    handles = alloc_handles(layout, capacity, ctx=_SPAWN)
    reader = OnPolicyStore(handles, layout)
    proc, accepted, attempts, stop = _start_numbered_writer(handles, 10**6)
    try:
        assert _wait(lambda: reader.lease() is not None)
        # generation 0 is leased: the writer fills generation 1 and no more
        assert _wait(lambda: accepted.value == 2 * capacity)
        seen = attempts.value
        assert _wait(lambda: attempts.value > seen + 50)  # short, not stuck
        assert accepted.value == 2 * capacity and reader.size == 2 * capacity
        reader.release()
        assert _wait(lambda: accepted.value == 3 * capacity, timeout=10.0)
        nxt = reader.lease()
        np.testing.assert_array_equal(
            _assert_untorn(nxt), np.arange(capacity, 2 * capacity)
        )
    finally:
        _stop(proc, stop)


def _lease_and_die(handles, took):
    store = OnPolicyStore(handles, _layout())
    while store.lease() is None:
        time.sleep(0.001)
    took.set()
    os._exit(0)  # no release: a reader that stops with the lease out


@pytest.mark.timeout(180)
def test_a_reader_process_that_dies_with_a_lease_does_not_wedge_the_writer():
    """The learner's process dies with a lease out (no release, no lock
    held): the writer keeps getting its short count at once — storage drops
    or requeues as on any full store — and the next reader over the same
    handles starts with the batch the dead one held."""
    layout = _layout()
    capacity = 4
    handles = alloc_handles(layout, capacity, ctx=_SPAWN)
    writer = OnPolicyStore(handles, layout)
    took = _SPAWN.Event()
    dead = _SPAWN.Process(target=_lease_and_die, args=(handles, took), daemon=True)
    dead.start()
    wins = [_window(layout, float(i)) for i in range(2 * capacity + 1)]
    assert writer.put_many(wins) == 2 * capacity
    assert took.wait(60)
    dead.join(30)
    assert dead.exitcode == 0
    t0 = time.monotonic()
    for _ in range(100):
        assert writer.put_many(wins[-1:]) == 0
    assert time.monotonic() - t0 < 5.0
    reader = OnPolicyStore(handles, layout)
    for first in (0, capacity):
        got = reader.lease()
        np.testing.assert_array_equal(
            _assert_untorn(got), np.arange(first, first + capacity)
        )
        reader.release()
    assert writer.put_many(wins[-1:]) == 1
