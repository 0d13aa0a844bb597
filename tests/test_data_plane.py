"""Data-plane tests: assembler splice/lag semantics and shm ring stores
(SURVEY.md §4 — assembler splicing, shm batch layout round-trip)."""

import multiprocessing as mp
import threading
import time

import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.data.assembler import RolloutAssembler, split_rollout_batch
from tpu_rl.data.layout import BatchLayout
from tpu_rl.data.shm_ring import OnPolicyStore, ReplayStore, alloc_handles, make_store
from tpu_rl.types import BATCH_FIELDS


def mk_step(layout, eid, t, done=False, is_fir=0.0):
    """A step whose obs encodes (episode, t) so tests can trace provenance."""
    step = {
        f: np.full((layout.width(f),), t, np.float32) for f in BATCH_FIELDS
    }
    step["obs"][0] = float(hash(eid) % 1000)
    step["is_fir"] = np.array([is_fir], np.float32)
    step["id"] = eid
    step["done"] = done
    return step


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.fixture
def layout():
    return BatchLayout.from_config(small_config())


# --------------------------------------------------------------- assembler
class TestAssembler:
    def test_emits_window_at_seq_len(self, layout):
        asm = RolloutAssembler(layout, clock=FakeClock())
        for t in range(layout.seq_len - 1):
            assert asm.push(mk_step(layout, "e1", t)) == 0
        assert asm.push(mk_step(layout, "e1", layout.seq_len - 1)) == 1
        win = asm.pop()
        assert win is not None and asm.pop() is None
        for f in BATCH_FIELDS:
            assert win[f].shape == (layout.seq_len, layout.width(f))
        # steps in push order
        assert list(win["rew"][:, 0]) == list(range(layout.seq_len))

    def test_split_rollout_batch_roundtrips_through_assembler(self, layout):
        """A stacked worker tick (Protocol.RolloutBatch) split into steps
        must assemble identically to the same steps pushed individually."""
        n_envs = 3
        rng = np.random.default_rng(3)
        ticks = []
        for t in range(layout.seq_len):
            ticks.append({
                **{
                    f: rng.standard_normal(
                        (n_envs, layout.width(f))
                    ).astype(np.float32)
                    for f in BATCH_FIELDS
                },
                "id": [f"e{i}" for i in range(n_envs)],
                "done": np.zeros(n_envs, np.uint8),
            })
        asm_b = RolloutAssembler(layout, clock=FakeClock())
        for tick in ticks:
            steps = split_rollout_batch(tick)
            assert len(steps) == n_envs
            for s in steps:
                asm_b.push(s)
        asm_s = RolloutAssembler(layout, clock=FakeClock())
        for tick in ticks:
            for i in range(n_envs):
                asm_s.push({
                    **{f: tick[f][i] for f in BATCH_FIELDS},
                    "id": tick["id"][i],
                    "done": False,
                })
        for _ in range(n_envs):
            wb, ws = asm_b.pop(), asm_s.pop()
            assert wb is not None and ws is not None
            for f in BATCH_FIELDS:
                np.testing.assert_array_equal(wb[f], ws[f])
        assert asm_b.pop() is None and asm_s.pop() is None

    def test_interleaved_episodes_keyed_by_id(self, layout):
        asm = RolloutAssembler(layout, clock=FakeClock())
        n = 0
        for t in range(layout.seq_len):
            n += asm.push(mk_step(layout, "a", t))
            n += asm.push(mk_step(layout, "b", 100 + t))
        assert n == 2
        w1, w2 = asm.pop(), asm.pop()
        assert {int(w1["rew"][0, 0]), int(w2["rew"][0, 0])} == {0, 100}

    def test_done_short_episode_parks_then_splices_with_seam(self, layout):
        asm = RolloutAssembler(layout, clock=FakeClock())
        # episode "a" ends after 2 steps (< seq_len)
        asm.push(mk_step(layout, "a", 0, is_fir=1.0))
        asm.push(mk_step(layout, "a", 1, done=True))
        assert asm.stats["parked"] == 1
        # new episode "b" splices onto the remnant; its first step gets
        # is_fir forced to 1.0 at the seam
        for t in range(layout.seq_len - 2):
            asm.push(mk_step(layout, "b", 10 + t, is_fir=1.0 if t == 0 else 0.0))
        win = asm.pop()
        assert win is not None
        assert asm.stats["spliced"] == 1
        # window = [a0, a1, b0, b1, b2]; seam at index 2 marked first
        assert win["is_fir"][0, 0] == 1.0  # true episode start
        assert win["is_fir"][2, 0] == 1.0  # splice seam
        assert win["rew"][2, 0] == 10.0

    def test_splices_shortest_remnant_first(self, layout):
        asm = RolloutAssembler(layout, clock=FakeClock())
        # Interleave so both episodes are created while nothing is parked
        # (a new episode always splices when a remnant exists).
        asm.push(mk_step(layout, "long", 0))
        asm.push(mk_step(layout, "long", 1))
        asm.push(mk_step(layout, "short", 50, done=True))  # parked, len 1
        asm.push(mk_step(layout, "long", 2, done=True))  # parked, len 3
        assert asm.stats["parked"] == 2
        # next new episode must pick "short" (len 1) over "long" (len 3)
        for t in range(layout.seq_len - 1):
            asm.push(mk_step(layout, "new", 100 + t))
        win = asm.pop()
        assert win is not None
        assert win["rew"][0, 0] == 50.0  # remnant came from "short"

    def test_stale_active_trajectory_dropped(self, layout):
        clock = FakeClock()
        asm = RolloutAssembler(layout, lag_sec=0.5, clock=clock)
        asm.push(mk_step(layout, "a", 0))
        clock.t = 1.0  # a is now stale
        asm.push(mk_step(layout, "b", 1))
        assert asm.stats["dropped_stale"] == 1
        assert "a" not in asm.active

    def test_activity_refreshes_staleness(self, layout):
        """Divergence from the reference: an actively-fed trajectory is NOT
        dropped (the reference ages from creation time)."""
        clock = FakeClock()
        asm = RolloutAssembler(layout, lag_sec=0.5, clock=clock)
        for t in range(layout.seq_len):
            clock.t = t * 0.4  # each push within lag of the previous
            asm.push(mk_step(layout, "a", t))
        assert asm.stats["dropped_stale"] == 0
        assert asm.pop() is not None

    def test_stale_parked_remnant_not_spliced(self, layout):
        clock = FakeClock()
        asm = RolloutAssembler(layout, lag_sec=0.5, clock=clock)
        asm.push(mk_step(layout, "a", 0, done=True))
        clock.t = 10.0
        asm.push(mk_step(layout, "b", 1))
        assert asm.stats["spliced"] == 0 and asm.stats["parked"] == 0

    def test_validate_rejects_bad_shapes(self, layout):
        asm = RolloutAssembler(layout, clock=FakeClock(), validate=True)
        bad = mk_step(layout, "a", 0)
        bad["obs"] = np.zeros((layout.obs + 1,), np.float32)
        with pytest.raises(ValueError, match="obs"):
            asm.push(bad)


# --------------------------------------------------------------- shm stores
def mk_window(layout, tag: float):
    return {
        f: np.full((layout.seq_len, layout.width(f)), tag, np.float32)
        for f in BATCH_FIELDS
    }


class TestOnPolicyStore:
    def test_fill_consume_reset_roundtrip(self, layout):
        cfg = small_config()
        B = cfg.batch_size
        store = make_store(cfg, layout)
        assert isinstance(store, OnPolicyStore) and store.generations == 2
        for i in range(B):
            assert store.consume() is None
            assert store.put(mk_window(layout, float(i)))
        # the first generation is sealed; the writer goes on in the second
        for i in range(B, 2 * B):
            assert store.put(mk_window(layout, float(i)))
        assert not store.put(mk_window(layout, 99.0))  # both full
        assert store.size == 2 * B
        for first in (0, B):  # oldest first, each whole
            out = store.consume()
            assert out is not None
            assert out["obs"].shape == (B, layout.seq_len, layout.obs)
            np.testing.assert_array_equal(
                out["rew"][:, 0, 0], np.arange(first, first + B, dtype=np.float32)
            )
        assert store.size == 0  # reset after consume
        assert store.consume() is None

    def test_generation_guard_rewrites_across_consume(self, layout):
        """A put that straddles a consume of the generation it writes into
        lands in that generation anew (the reference race: reset while
        storage is mid-make_batch). Only ``consume(need=k)`` of a generation
        still being filled can do that: a sealed one is not the writer's."""
        cfg = small_config()
        handles = alloc_handles(layout, cfg.batch_size)
        writer = OnPolicyStore(handles, layout)
        reader = OnPolicyStore(handles, layout)
        for i in range(3):
            writer.put(mk_window(layout, float(i)))

        # Simulate a straddling put: interpose a consume between the writer's
        # claim of its slot and its publish step by driving the protocol
        # manually.
        win = mk_window(layout, 777.0)
        epoch, gen, slot = writer._claim()
        assert (gen, slot) == (0, 3)
        out = reader.consume(need=3)  # takes the three, resets the generation
        assert out is not None and handles.gen.value == epoch + 1
        assert not writer._publish(epoch, slot + 1)  # the write is void...
        assert writer.put(win)  # ...and made again, at slot 0
        assert writer.size == 1
        nxt = reader.consume(need=1)
        assert nxt is not None and nxt["rew"][0, 0, 0] == 777.0

    @pytest.mark.parametrize("generations", [1, 2, 3])
    def test_writer_is_short_only_with_every_generation_sealed(
        self, layout, generations
    ):
        """The writer's short count says one thing: every generation is with
        the reader or waiting for it. A release frees one at once."""
        B = 4
        handles = alloc_handles(layout, B, generations=generations)
        writer = OnPolicyStore(handles, layout)
        reader = OnPolicyStore(handles, layout)
        wins = [mk_window(layout, float(i)) for i in range(generations * B + 3)]
        assert writer.put_many(wins) == generations * B
        leased = reader.lease()
        assert leased is not None
        assert not writer.put(wins[-1])  # leased is not free
        assert reader.size == generations * B
        np.testing.assert_array_equal(
            leased["rew"][:, 0, 0], np.arange(B, dtype=np.float32)
        )
        reader.release()
        assert writer.put_many(wins[generations * B:]) == 3
        assert writer.size == (generations - 1) * B + 3

    def test_a_lease_is_views_and_the_writer_stays_out_of_it(self, layout):
        """The leased batch is the shared memory itself (no copy), with the
        ``ver`` sidecar's rows beside it, and stays as it is while the writer
        fills the other generation and is refused at this one."""
        B = 4
        handles = alloc_handles(layout, B)
        writer = OnPolicyStore(handles, layout)
        reader = OnPolicyStore(handles, layout)
        assert reader.lease() is None  # nothing sealed yet
        writer.put_many([mk_window(layout, float(i)) for i in range(B)], vers=[5, 6, 7, 8])
        got = reader.lease()
        for f in BATCH_FIELDS:
            assert np.shares_memory(got[f], reader.views[f])
            assert got[f].shape == (B, layout.seq_len, layout.width(f))
        assert np.shares_memory(got["ver"], reader.slot_vers)
        assert got["ver"].tolist() == [5, 6, 7, 8]
        with pytest.raises(RuntimeError, match="lease"):
            reader.lease()  # one at a time
        before = {f: got[f].copy() for f in BATCH_FIELDS}
        assert writer.put_many(
            [mk_window(layout, 100.0 + i) for i in range(B + 2)]
        ) == B  # the other generation takes B, the leased one nothing
        for f in BATCH_FIELDS:
            np.testing.assert_array_equal(got[f], before[f])
        assert got["ver"].tolist() == [5, 6, 7, 8]
        reader.release()
        reader.release()  # nothing out: nothing happens
        nxt = reader.lease()
        np.testing.assert_array_equal(nxt["rew"][:, 0, 0], 100.0 + np.arange(B))
        assert nxt["ver"].tolist() == [-1] * B

    def test_a_lease_left_by_a_reader_that_stopped_is_handed_out_again(self, layout):
        """A reader that dies with a lease out leaves its generation sealed:
        the writer is short and returns at once, never blocked, and the next
        reader over the same handles gets that batch first."""
        B = 4
        handles = alloc_handles(layout, B)
        writer = OnPolicyStore(handles, layout)
        dead = OnPolicyStore(handles, layout)
        writer.put_many([mk_window(layout, float(i)) for i in range(2 * B)])
        assert dead.lease() is not None
        del dead  # no release
        t0 = time.monotonic()
        assert writer.put_many([mk_window(layout, 50.0)]) == 0
        assert time.monotonic() - t0 < 1.0
        reader = OnPolicyStore(handles, layout)
        for first in (0, B):
            out = reader.consume()
            np.testing.assert_array_equal(
                out["rew"][:, 0, 0], np.arange(first, first + B, dtype=np.float32)
            )
        assert writer.put_many([mk_window(layout, 50.0)]) == 1

    def test_handles_from_before_the_ring_say_so(self, layout):
        """``ShmHandles`` keeps constructing without the new fields (one
        generation, as a replay ring wants); an on-policy store over such
        handles says what is missing instead of running half a protocol."""
        import dataclasses

        new = alloc_handles(layout, 4, generations=1)
        old = dataclasses.replace(new, ring=None)
        fields = {f.name: getattr(new, f.name) for f in dataclasses.fields(new)}
        del fields["generations"], fields["ring"]
        assert type(new)(**fields).generations == 1
        assert ReplayStore(old, layout).capacity == 4
        with pytest.raises(ValueError, match="ring"):
            OnPolicyStore(old, layout)

    def test_cross_process_visibility(self, layout):
        cfg = small_config()
        handles = alloc_handles(layout, cfg.batch_size)
        ctx = mp.get_context("spawn")
        p = ctx.Process(
            target=_child_fill, args=(handles, cfg.batch_size), daemon=True
        )
        p.start()
        p.join(60)
        assert p.exitcode == 0
        store = OnPolicyStore(handles, layout)
        out = store.consume()
        assert out is not None
        np.testing.assert_array_equal(
            np.sort(out["rew"][:, 0, 0]),
            np.arange(cfg.batch_size, dtype=np.float32),
        )


def _child_fill(handles, n):
    from tpu_rl.data.shm_ring import OnPolicyStore
    from tpu_rl.data.layout import BatchLayout

    layout = BatchLayout.from_config(small_config())
    store = OnPolicyStore(handles, layout)
    for i in range(n):
        assert store.put(mk_window(layout, float(i)))


class TestReplayStore:
    def test_ring_overwrite_and_sample(self, layout):
        cfg = small_config(algo="SAC", buffer_size=16, batch_size=8)
        store = make_store(cfg, layout)
        assert isinstance(store, ReplayStore)
        rng = np.random.default_rng(0)
        assert store.sample(8, rng) is None  # not enough yet
        for i in range(40):  # wraps the 16-slot ring 2.5x
            store.put(mk_window(layout, float(i)))
        assert store.size == 16
        out = store.sample(8, rng)
        assert out is not None and out["obs"].shape[0] == 8
        # everything sampled must be from the surviving window [24, 40)
        tags = out["rew"][:, 0, 0]
        assert tags.min() >= 24.0 and tags.max() < 40.0
        # a slot is internally consistent across fields (no torn mix)
        np.testing.assert_array_equal(out["obs"][:, 0, 0], tags)

    def test_concurrent_writer_reader_no_torn_slots(self, layout):
        """Seqlock keeps sampled slots internally consistent while a writer
        hammers the ring from another thread."""
        cfg = small_config(algo="SAC", buffer_size=8, batch_size=4)
        store = make_store(cfg, layout)
        stop = threading.Event()

        def writer():
            i = 0
            while not stop.is_set():
                store.put(mk_window(layout, float(i % 1000)))
                i += 1

        t = threading.Thread(target=writer, daemon=True)
        t.start()
        try:
            rng = np.random.default_rng(1)
            seen = 0
            while seen < 200:
                out = store.sample(4, rng)
                if out is None:
                    continue
                # all fields of a slot carry the same tag -> read was atomic
                for f in BATCH_FIELDS:
                    np.testing.assert_array_equal(
                        out[f][:, 0, 0], out["rew"][:, 0, 0]
                    )
                seen += 4
        finally:
            stop.set()
            t.join(5)
