"""Unit tests for manager relay and storage bridging logic (the integration
test covers the wiring; these pin the behaviors: drop-oldest backpressure,
50-game stat windowing, stat mailbox relay, store-full requeue)."""

import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.data.assembler import RolloutAssembler
from tpu_rl.data.layout import BatchLayout
from tpu_rl.data.shm_ring import alloc_handles, OnPolicyStore
from tpu_rl.runtime.manager import Manager, RELAY_QUEUE_MAX, STAT_WINDOW
from tpu_rl.runtime.protocol import Protocol, decode, encode
from tpu_rl.runtime.storage import LearnerStorage, STAT_SLOTS
from tpu_rl.types import BATCH_FIELDS


class FakePub:
    def __init__(self):
        self.sent = []
        self.sent_raw = []

    def send(self, proto, payload):
        self.sent.append((proto, payload))

    def send_raw(self, parts):
        self.sent_raw.append(parts)


def _manager(cfg=None, **kw):
    cfg = cfg or small_config(**kw)
    return Manager(cfg, 0, "127.0.0.1", 0)


def _ingest_frame(m, proto, payload, pub):
    """Feed one frame through _ingest in whatever form the manager's mode
    expects: opaque wire parts (raw) or the decoded payload (decode)."""
    m._ingest(proto, encode(proto, payload) if m.raw else payload, pub)


class TestManager:
    @pytest.mark.parametrize("relay_mode", ["raw", "decode"])
    def test_rollout_queue_drops_oldest(self, relay_mode):
        m = _manager(relay_mode=relay_mode)
        pub = FakePub()
        for i in range(RELAY_QUEUE_MAX + 10):
            proto = (
                Protocol.RolloutBatch if i % 2 else Protocol.Rollout
            )  # both frame kinds share the relay queue
            _ingest_frame(m, proto, {"i": i}, pub)
        assert len(m.queue) == RELAY_QUEUE_MAX
        # the 10 shed frames are counted (silent-drop fix), one per eviction
        assert m.n_dropped == 10
        # the 10 oldest were shed (stale rollouts are least on-policy); the
        # queue holds fully-encoded wire parts in BOTH modes
        proto0, payload0 = decode(m.queue[0])
        assert payload0["i"] == 10 and proto0 == Protocol.Rollout
        # frames relay with their ORIGINAL protocol byte
        assert decode(m.queue[1])[0] == Protocol.RolloutBatch

    @pytest.mark.parametrize("relay_mode", ["raw", "decode"])
    def test_stat_window_publishes_mean_every_50(self, relay_mode):
        m = _manager(relay_mode=relay_mode)
        pub = FakePub()
        for i in range(STAT_WINDOW * 2):
            _ingest_frame(m, Protocol.Stat, float(i), pub)
        assert len(pub.sent) == 2
        proto, payload = pub.sent[0]
        assert proto == Protocol.Stat
        assert payload["n"] == STAT_WINDOW
        assert payload["mean"] == np.mean(np.arange(50.0))
        # second window is the NEWEST 50 (sliding deque)
        assert pub.sent[1][1]["mean"] == np.mean(np.arange(50.0, 100.0))
        # windowed publish carries the relay health counters (ISSUE 3)
        assert payload["relay_dropped"] == 0
        assert "forward_bytes" in payload

    def test_raw_mode_corrupt_stat_body_counted_not_crashed(self):
        m = _manager(relay_mode="raw")
        pub = FakePub()
        proto_b, frame = encode(Protocol.Stat, 1.0)
        corrupt = frame[:-1] + bytes([frame[-1] ^ 0xFF])  # CRC mismatch
        m._ingest(Protocol.Stat, [proto_b, corrupt], pub)
        assert m.n_stat_rejected == 1 and m.n_stats == 0


def _mk_window(layout, tag):
    return {
        f: np.full((layout.seq_len, layout.width(f)), tag, np.float32)
        for f in BATCH_FIELDS
    }


class TestStorage:
    def _storage(self, cfg):
        layout = BatchLayout.from_config(cfg)
        handles = alloc_handles(layout, cfg.batch_size)
        import multiprocessing as mp

        stat = mp.get_context("spawn").Array("f", STAT_SLOTS, lock=False)
        st = LearnerStorage(cfg, handles, 0, stat_array=stat)
        return st, layout, handles, stat

    def test_stat_relay_accumulates_game_count(self):
        cfg = small_config()
        st, *_rest, stat = self._storage(cfg)
        st._relay_stat({"mean": 123.0, "n": 50})
        st._relay_stat({"mean": 150.0, "n": 50})
        assert stat[0] == 100.0  # global game count accumulates
        assert stat[1] == 150.0  # newest mean wins
        assert stat[2] == 1.0  # activate flag set for the learner
        stat[2] = 0.0  # learner clears
        st._relay_stat(7.5)  # bare-float stats also accepted
        assert stat[0] == 101.0 and stat[2] == 1.0

    def test_flush_requeues_on_full_store(self):
        cfg = small_config(batch_size=2)
        st, layout, handles, _ = self._storage(cfg)
        store = OnPolicyStore(handles, layout)
        asm = RolloutAssembler(layout)
        for tag in (1.0, 2.0, 3.0, 4.0, 5.0):
            asm.ready.append(_mk_window(layout, tag))
        st._flush(asm, store)
        # two generations of capacity 2: four windows landed, the fifth was
        # REQUEUED
        assert st.n_windows == 4
        assert st.n_requeue_full == 1
        assert len(asm.ready) == 1
        assert asm.ready[0]["rew"][0, 0] == 5.0
        # after the learner consumes, the requeued window flushes
        assert store.consume() is not None
        st._flush(asm, store)
        assert st.n_windows == 5 and len(asm.ready) == 0
