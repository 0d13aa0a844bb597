"""Durability plane (PR 9): crash-atomic checkpoints, full-run resume,
run-epoch fencing, and the storage membership table.

Checkpointer tests use plain dict pytrees (orbax is structure-agnostic) so
they stay fast; the storage fence/membership tests exercise the real
``LearnerStorage`` methods on a bare instance — the helpers touch only the
durability attributes, so no sockets or shm rings are needed.
"""

import json
import os

import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.checkpoint import (
    COMMIT_MARKER,
    Checkpointer,
    is_committed,
    latest_committed,
    read_meta,
    restore_actor_params,
    resume_fingerprint,
)


def _state(val: float = 1.0):
    return {
        "params": {
            "actor": {"w": np.full((3, 2), val, np.float32)},
            "critic": {"w": np.full((2,), -val, np.float32)},
        },
        "step": np.zeros((), np.int32),
    }


def _plant_torn(model_dir: str, algo: str, idx: int) -> str:
    """Fabricate a torn save: an orbax-shaped dir with NO commit marker."""
    path = os.path.join(model_dir, f"{algo}_{idx}")
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "checkpoint"), "w") as f:
        f.write("torn mid-write")
    return path


# --------------------------------------------------------------- atomicity
def test_torn_checkpoint_invisible_to_readers(tmp_path):
    """A dir without the COMMITTED marker must be skipped by every read
    path; readers land on the previous committed index instead."""
    d = str(tmp_path)
    ck = Checkpointer(d, "PPO")
    ck.save(_state(1.0), 100)
    torn = _plant_torn(d, "PPO", 200)  # newer idx, but never committed
    assert not is_committed(torn)
    assert latest_committed(d, "PPO") == (100, os.path.join(d, "PPO_100"))
    assert ck.latest_idx() == 100
    got, idx = ck.restore_latest(_state(0.0))
    assert idx == 100
    np.testing.assert_array_equal(got["params"]["actor"]["w"], 1.0)
    actor = restore_actor_params(d, "PPO")
    np.testing.assert_array_equal(actor["actor"]["w"], 1.0)
    ck.close()


def test_init_cleans_torn_dirs(tmp_path):
    """A new Checkpointer (the respawned learner) sweeps torn debris; the
    committed dir survives."""
    d = str(tmp_path)
    ck = Checkpointer(d, "PPO")
    ck.save(_state(), 100)
    ck.close()
    _plant_torn(d, "PPO", 200)
    ck2 = Checkpointer(d, "PPO")
    assert sorted(os.listdir(d)) == ["PPO_100"]
    ck2.close()


def test_corrupt_marker_reads_as_empty_meta(tmp_path):
    d = str(tmp_path)
    ck = Checkpointer(d, "PPO")
    path = ck.save(_state(), 100)
    with open(os.path.join(path, COMMIT_MARKER), "w") as f:
        f.write("{not json")
    assert read_meta(path) == {}
    ck.close()


def test_gc_keeps_newest_and_skips_uncommitted(tmp_path):
    """GC bounds committed dirs to ``keep`` newest and never touches an
    uncommitted dir (it may be a concurrent in-flight save)."""
    d = str(tmp_path)
    ck = Checkpointer(d, "PPO", keep=2)
    torn = _plant_torn(d, "PPO", 50)
    for idx in (100, 200, 300):
        ck.save(_state(), idx)
    assert sorted(os.listdir(d)) == ["PPO_200", "PPO_300", "PPO_50"]
    assert os.path.isdir(torn)
    ck.close()


# ---------------------------------------------------------------- asynchrony
def test_async_save_equivalent_to_sync(tmp_path):
    """flush() after an async save yields the same committed bytes a sync
    save would; meta rides along."""
    d = str(tmp_path)
    ck = Checkpointer(d, "PPO", async_save=True)
    ck.save(_state(7.0), 100, meta={"epoch": 3})
    ck.flush(timeout=60.0)
    assert ck.n_saves == 1
    assert ck.pending == 0
    got, idx, meta = ck.restore_run(_state(0.0))
    assert idx == 100
    assert meta["epoch"] == 3
    assert meta["idx"] == 100  # _write defaults idx/algo/saved_at into meta
    np.testing.assert_array_equal(got["params"]["actor"]["w"], 7.0)
    assert ck.drain_save_secs()  # one duration recorded for the timer
    ck.close()


def test_async_writer_lets_go_of_a_snapshot_once_it_is_written(tmp_path, monkeypatch):
    """The device-side snapshot of an async save is a copy of the train
    state: once it is on the host it must be garbage — before the disk write,
    and not held by the writer's frame until the next save comes (and then
    beside that save's snapshot)."""
    import gc
    import weakref

    from tpu_rl import checkpoint

    seen = []
    real = checkpoint._snapshot

    def snapshot(state):
        snap = real(state)
        seen.append(weakref.ref(snap["params"]["actor"]["w"]))
        return snap

    monkeypatch.setattr(checkpoint, "_snapshot", snapshot)
    ck = Checkpointer(str(tmp_path), "PPO", async_save=True)
    write, gone_at_write = ck._write, []

    def write_seen(host_state, idx, meta):
        gc.collect()
        gone_at_write.append(seen[-1]() is None)  # on the host: let go of
        write(host_state, idx, meta)

    ck._write = write_seen
    import jax

    ck.save(jax.tree.map(jax.numpy.asarray, _state(7.0)), 100)
    ck.flush(timeout=60.0)
    gc.collect()
    assert len(seen) == 1 and seen[0]() is None
    assert gone_at_write == [True]
    ck.close()


def test_async_latest_wins_drops_stale_queue(tmp_path):
    """Saves enqueued faster than the writer drains collapse to the newest
    (n_skipped counts the drops); close() drains the tail save."""
    d = str(tmp_path)
    ck = Checkpointer(d, "PPO", async_save=True)
    # Stall the writer so the queue slot is demonstrably latest-wins.
    import threading

    gate = threading.Event()
    started = threading.Event()
    orig_write = ck._write

    def slow_write(host_state, idx, meta):
        started.set()
        gate.wait(30.0)
        orig_write(host_state, idx, meta)

    ck._write = slow_write
    ck.save(_state(1.0), 100)
    assert started.wait(10.0)  # 100 is IN FLIGHT, not merely queued
    ck.save(_state(2.0), 200)  # queued behind the stalled 100
    ck.save(_state(3.0), 300)  # replaces 200 in the queue slot
    assert ck.n_skipped == 1
    gate.set()
    ck.flush(timeout=60.0)
    ck.close()
    committed = [n for n in sorted(os.listdir(d)) if not n.startswith(".")]
    assert committed == ["PPO_100", "PPO_300"]


def test_async_error_surfaces_on_next_save(tmp_path):
    d = str(tmp_path)
    ck = Checkpointer(d, "PPO", async_save=True)

    def boom(host_state, idx, meta):
        raise OSError("disk gone")

    ck._write = boom
    ck.save(_state(), 100)
    with pytest.raises(RuntimeError, match="async checkpoint save failed"):
        ck.flush(timeout=30.0)
    ck._write = lambda *a: None  # don't re-fail on close's drain
    ck.close()


# ------------------------------------------------------------ run fingerprint
def test_resume_refuses_fingerprint_mismatch_unless_forced(tmp_path):
    d = str(tmp_path)
    cfg = small_config()
    fp = resume_fingerprint(cfg)
    ck = Checkpointer(d, "PPO")
    ck.save(_state(5.0), 100, meta={"fingerprint": fp, "epoch": 0})
    # Same structural config -> resumes.
    got = ck.restore_run(_state(0.0), fingerprint=fp)
    assert got is not None and got[1] == 100
    # Structurally different config -> a different fingerprint -> refuse.
    fp2 = resume_fingerprint(cfg.replace(hidden_size=cfg.hidden_size * 2))
    assert fp2 != fp
    with pytest.raises(RuntimeError, match="different config"):
        ck.restore_run(_state(0.0), fingerprint=fp2)
    # forced: resumes anyway (the operator's explicit override).
    got = ck.restore_run(_state(0.0), fingerprint=fp2, force=True)
    assert got is not None and got[1] == 100
    ck.close()


def test_fingerprint_ignores_runtime_knobs(tmp_path):
    """Ports / telemetry / supervision must never strand a checkpoint."""
    cfg = small_config()
    fp = resume_fingerprint(cfg)
    assert fp == resume_fingerprint(
        cfg.replace(telemetry_port=9100, max_restarts=9, ckpt_keep=2)
    )
    assert fp != resume_fingerprint(cfg.replace(n_layers=cfg.n_layers + 1))


# ------------------------------------------------------------ epoch fencing
def _bare_storage(run_epoch=-1, stat_array=None, lease_s=15.0):
    from tpu_rl.runtime.storage import LearnerStorage, MembershipTable

    st = object.__new__(LearnerStorage)
    st.run_epoch = run_epoch
    st.n_stale_epoch = 0
    st.stat_array = stat_array
    st.members = MembershipTable(lease_s)
    return st


def test_epoch_admit_fences_stale_and_ratchets():
    st = _bare_storage(run_epoch=2)
    assert st._epoch_admit({"epoch": 2})  # current epoch: in
    assert not st._epoch_admit({"epoch": 1})  # pre-crash frame: fenced
    assert st.n_stale_epoch == 1
    assert st._epoch_admit({"epoch": 5})  # frame echo ratchets the fence
    assert st.run_epoch == 5
    assert not st._epoch_admit({"epoch": 2})  # old fence value now stale
    # Unknown epochs are always admitted: fresh fleets must not stall.
    assert st._epoch_admit({"epoch": -1})
    assert st._epoch_admit({"wid": 0})
    assert st._epoch_admit(b"not-a-dict")
    assert st.n_stale_epoch == 2


def test_poll_epoch_reads_mailbox_ratchet():
    from tpu_rl.runtime.mailbox import SLOT_RUN_EPOCH, STAT_SLOTS

    sa = [0.0] * STAT_SLOTS
    st = _bare_storage(stat_array=sa)
    st._poll_epoch()
    assert st.run_epoch == -1  # 0.0 = no learner wrote yet
    sa[SLOT_RUN_EPOCH] = 3.0  # learner run_epoch 2, encoded +1
    st._poll_epoch()
    assert st.run_epoch == 2
    sa[SLOT_RUN_EPOCH] = 1.0  # never ratchets down
    st._poll_epoch()
    assert st.run_epoch == 2
    # A short legacy mailbox (pre-PR9 layout) is tolerated.
    st_short = _bare_storage(stat_array=[0.0] * 7)
    st_short._poll_epoch()
    assert st_short.run_epoch == -1


def test_new_member_raises_join_flag():
    from tpu_rl.runtime.mailbox import SLOT_JOIN_REQ, STAT_SLOTS

    sa = [0.0] * STAT_SLOTS
    st = _bare_storage(stat_array=sa)
    st._touch_member({"wid": 4})
    assert sa[SLOT_JOIN_REQ] == 1.0
    assert st.members.n_joined == 1
    sa[SLOT_JOIN_REQ] = 0.0  # learner consumed the nudge
    st._touch_member({"wid": 4})  # lease renewal, not a join
    assert sa[SLOT_JOIN_REQ] == 0.0
    st._touch_member({"no_wid": True})  # frames without wid are ignored
    assert st.members.n_joined == 1


# -------------------------------------------------------------- membership
def test_membership_lease_eviction_and_rejoin():
    from tpu_rl.runtime.storage import MembershipTable

    t = {"now": 100.0}
    m = MembershipTable(lease_s=5.0, clock=lambda: t["now"])
    assert m.touch(0) and m.touch(1)
    assert m.evict_expired() == []
    t["now"] = 104.0
    m.touch(1)  # renews
    t["now"] = 106.0
    assert m.evict_expired() == [0]  # 0 silent 6s > 5s lease
    assert sorted(m.active) == [1]
    assert m.touch(0)  # re-join after eviction counts as a join
    assert (m.n_joined, m.n_evicted) == (3, 1)


# ------------------------------------------------------------- config / CLI
def test_config_validates_durability_ranges():
    from tpu_rl.config import Config

    with pytest.raises(AssertionError):
        Config(ckpt_keep=0).validate()
    with pytest.raises(AssertionError):
        Config(model_save_interval=0).validate()
    with pytest.raises(AssertionError):
        Config(membership_lease_s=0.0).validate()
    Config(ckpt_keep=1, model_save_interval=1).validate()


def test_cli_durability_flags(tmp_path):
    from tpu_rl.__main__ import build_parser, load_config

    args = build_parser().parse_args([
        "local",
        "--result-dir", str(tmp_path / "run"),
        "--ckpt-keep", "3",
        "--model-save-interval", "25",
        "--ckpt-sync",
        "--resume-force",
    ])
    cfg, _machines = load_config(args)
    assert cfg.result_dir == str(tmp_path / "run")
    assert cfg.model_dir == os.path.join(str(tmp_path / "run"), "models")
    assert cfg.ckpt_keep == 3
    assert cfg.model_save_interval == 25
    assert cfg.ckpt_async is False
    assert cfg.resume_force is True


def test_resume_meta_roundtrips_prng_key(tmp_path):
    """The learner stores its PRNG key as raw uint32 words in the commit
    marker; wrap_key_data must reconstruct the identical stream."""
    import jax

    d = str(tmp_path)
    key = jax.random.key(42)
    words = np.asarray(jax.random.key_data(key)).tolist()
    ck = Checkpointer(d, "PPO")
    path = ck.save(_state(), 100, meta={"key": words, "epoch": 1})
    meta = read_meta(path)
    assert meta["epoch"] == 1
    restored = jax.random.wrap_key_data(
        np.asarray(meta["key"], dtype=np.uint32)
    )
    np.testing.assert_array_equal(
        jax.random.uniform(restored, (4,)), jax.random.uniform(key, (4,))
    )
    ck.close()


def test_resume_record_written(tmp_path):
    """_record_resume appends an auditable jsonl line per resume."""
    from tpu_rl.runtime.learner_service import LearnerService

    svc = object.__new__(LearnerService)
    svc.cfg = small_config(result_dir=str(tmp_path))
    svc.run_epoch = 2
    svc._record_resume(37)
    rec = json.loads(
        open(os.path.join(str(tmp_path), "learner_resume.jsonl")).read()
    )
    assert rec["idx"] == 37
    assert rec["epoch"] == 2
    assert rec["t"] > 0
