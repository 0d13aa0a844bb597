"""Equivalence pins for the columnar ingest (ISSUE 3 satellite):

- ``RolloutAssembler.push_tick`` (whole-tick columnar path) must produce
  bit-identical windows — and identical counters — to the reference
  ``split_rollout_batch`` + per-step ``push`` path over randomized multi-env,
  multi-episode streams, including splice/``is_fir`` seams and stale drops;
- the stores' ``put_many`` burst writes must leave exactly the shm contents
  sequential ``put`` calls would, including on-policy partial accepts and
  replay-ring wraparound.
"""

import numpy as np
import pytest

from tests.conftest import small_config
from tpu_rl.data.assembler import RolloutAssembler, split_rollout_batch
from tpu_rl.data.layout import BatchLayout
from tpu_rl.data.shm_ring import OnPolicyStore, ReplayStore, alloc_handles
from tpu_rl.types import BATCH_FIELDS


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _layout():
    return BatchLayout.from_config(small_config())


def _random_tick(rng, layout, ids, done_p):
    n = len(ids)
    payload = {
        f: rng.standard_normal((n, layout.width(f))).astype(np.float32)
        for f in BATCH_FIELDS
    }
    payload["id"] = list(ids)
    payload["done"] = (rng.random(n) < done_p).astype(np.uint8)
    return payload


def _drain(asm):
    out = []
    while (w := asm.pop()) is not None:
        out.append(w)
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("n_envs", [1, 5])
def test_push_tick_bit_identical_to_per_step_push(seed, n_envs):
    """Randomized stream with episode turnover (splices), stale gaps (drops),
    and interleaved multi-env ticks: the columnar path and the reference path
    must emit the same windows in the same order, bit for bit, and agree on
    every counter."""
    rng = np.random.default_rng(seed)
    layout = _layout()
    ca, cb = FakeClock(), FakeClock()
    a = RolloutAssembler(layout, lag_sec=0.5, clock=ca)  # push_tick
    b = RolloutAssembler(layout, lag_sec=0.5, clock=cb)  # split + push
    ids = [f"ep{i}" for i in range(n_envs)]
    next_id = n_envs
    wins_a, wins_b = [], []
    for _ in range(300):
        # Occasional long gap: the 0.5 s lag bound must fire identically in
        # both paths (one stale scan per tick vs per step — equivalent when
        # the clock is constant within a tick, as it is on the real storage
        # loop where one drain pass timestamps a whole frame).
        dt = 0.7 if rng.random() < 0.05 else 0.01
        ca.t += dt
        cb.t += dt
        payload = _random_tick(rng, layout, ids, done_p=0.12)
        a.push_tick(payload)
        for step in split_rollout_batch(payload):
            b.push(step)
        wins_a.extend(_drain(a))
        wins_b.extend(_drain(b))
        for i in range(n_envs):
            if payload["done"][i]:
                # Fresh episode id next tick -> exercises remnant splicing.
                ids[i] = f"ep{next_id}"
                next_id += 1
    assert a.stats == b.stats
    assert len(wins_a) == len(wins_b) > 0
    assert a.stats["spliced"] > 0, "stream never exercised a splice seam"
    assert a.stats["dropped_stale"] > 0, "stream never exercised a stale drop"
    for wa, wb in zip(wins_a, wins_b, strict=True):
        for f in BATCH_FIELDS:
            np.testing.assert_array_equal(wa[f], wb[f], err_msg=f)


def test_push_tick_seam_forces_is_fir():
    """A tick that splices onto a parked remnant re-marks is_fir=1.0 at the
    seam row even when the worker sent 0.0 (same contract as push)."""
    layout = _layout()
    clock = FakeClock()
    asm = RolloutAssembler(layout, clock=clock)
    short = _random_tick(np.random.default_rng(0), layout, ["e0"], 0.0)
    short["done"] = np.array([1], np.uint8)
    asm.push_tick(short)  # parks a 1-row remnant
    cont = _random_tick(np.random.default_rng(1), layout, ["e1"], 0.0)
    cont["is_fir"][:] = 0.0
    asm.push_tick(cont)
    tj = asm.active["e1"]
    assert tj.n == 2 and asm.n_spliced == 1
    assert tj.cols["is_fir"][1, 0] == 1.0  # seam row forced


def _mk_windows(layout, rng, k):
    return [
        {
            f: rng.standard_normal((layout.seq_len, layout.width(f))).astype(
                np.float32
            )
            for f in BATCH_FIELDS
        }
        for _ in range(k)
    ]


def test_onpolicy_put_many_matches_sequential_put():
    layout = _layout()
    rng = np.random.default_rng(7)
    cap = 8
    wins = _mk_windows(layout, rng, 2 * cap + 3)  # 3 past both generations
    s_many = OnPolicyStore(alloc_handles(layout, cap), layout)
    s_seq = OnPolicyStore(alloc_handles(layout, cap), layout)
    accepted = s_many.put_many(wins)
    seq_accepted = sum(s_seq.put(w) for w in wins)
    # Partial accept: the in-order head lands, the tail is rejected — exactly
    # like sequential puts against a filling store.
    assert accepted == seq_accepted == 2 * cap
    assert s_many.size == s_seq.size == 2 * cap
    for f in BATCH_FIELDS:
        np.testing.assert_array_equal(s_many.views[f], s_seq.views[f])
        # each window lies in its slot as it was handed in
        np.testing.assert_array_equal(
            s_many.views[f], np.stack([w[f] for w in wins[:accepted]])
        )
    # Consume frees the older generation; the rejected tail then lands at
    # its front.
    assert s_many.consume() is not None
    assert s_many.put_many(wins[accepted:]) == 3
    for i, w in enumerate(wins[accepted:]):
        np.testing.assert_array_equal(s_many.views["obs"][i], w["obs"])


def test_onpolicy_put_many_empty_and_full():
    layout = _layout()
    store = OnPolicyStore(alloc_handles(layout, 2), layout)
    assert store.put_many([]) == 0
    wins = _mk_windows(layout, np.random.default_rng(0), 4)
    assert store.put_many(wins) == 4  # two generations of two
    assert store.put_many(_mk_windows(layout, np.random.default_rng(1), 1)) == 0


@pytest.mark.parametrize("n_windows", [3, 11])  # under / over 2x capacity
def test_replay_put_many_matches_sequential_put(n_windows):
    """Ring wraparound: bursts larger than the ring must leave exactly the
    slots (and total-puts odometer) sequential puts would — later windows
    overwrite earlier ones at the same slot, and every seqlock version ends
    even (stable)."""
    layout = _layout()
    rng = np.random.default_rng(11)
    cap = 4
    wins = _mk_windows(layout, rng, n_windows)
    s_many = ReplayStore(alloc_handles(layout, cap), layout)
    s_seq = ReplayStore(alloc_handles(layout, cap), layout)
    assert s_many.put_many(wins) == n_windows
    for w in wins:
        s_seq.put(w)
    assert s_many.total_puts == s_seq.total_puts == n_windows
    for f in BATCH_FIELDS:
        np.testing.assert_array_equal(s_many.views[f], s_seq.views[f])
    assert (s_many.versions % 2 == 0).all()
    # and the ring still samples
    got = s_many.sample(2, np.random.default_rng(0))
    assert got is not None and got["obs"].shape[0] == 2
