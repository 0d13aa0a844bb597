"""A logged update's books (ISSUE 44): after a ``log-sync`` the learner
dispatches first and closes the books of the update it read back second —
``log-write``, ``diag-drain``, ``watchdog`` — unless a save is due, the loop is
stopping or no batch is ready. Wherever they are closed they are the same
books: the same ``learn.jsonl`` lines, the same ``last_losses``, each logged
update's once."""

import math
import time

import jax.numpy as jnp
import pytest

from tests.test_trace_lanes import _lanes_of, _learn_lines, _run_learner, _with_feed
from tpu_rl.obs.learn import DiagAccumulator

B = 16  # _run_learner's batch: windows an update


# ------------------------------------------------------- the hand-over, alone
def _diag(kl, norm):
    return {
        "rows": {"kl": jnp.asarray(kl, jnp.float32), "w": jnp.ones((len(kl),)),
                 "w2": jnp.ones((len(kl),))},
        "scalars": {"param-norm": jnp.asarray(norm, jnp.float32)},
    }


def test_the_accumulator_hands_its_sums_over_and_starts_afresh():
    acc, twin = DiagAccumulator(), DiagAccumulator()
    assert acc.take() is None and DiagAccumulator.read(None) is None
    for a in (acc, twin):
        a.add(_diag([0.1, 0.3], 10.0), jnp.zeros((2,)))
        a.add(_diag([0.5, 0.7], 14.0), jnp.full((2,), 3.0))
    sums = acc.take()  # no read-back: the device's arrays as they stand
    assert acc._acc is None and float(sums["n-updates"]) == 2.0
    # what is folded next is a new line's, from zeros as the first fold was
    acc.add(_diag([0.9, 0.9], 2.0), jnp.zeros((2,)))
    # read whenever: the handed-over sums derive to the document drain() gives
    assert DiagAccumulator.read(sums) == twin.drain(idx=2)
    later = acc.drain(idx=3)
    assert later["n_updates"] == 1.0
    assert later["global"]["approx-kl"] == pytest.approx(0.9)
    assert later["global"]["param-norm"] == pytest.approx(2.0)
    assert set(later["buckets"]) == {"0"}
    assert acc.take() is None and twin.drain(idx=4) is None


# ------------------------------------------------- the same books, both orders
def _lines(tmp):
    rows = _learn_lines(tmp / "run")
    for r in rows:
        del r["ts"], r["t"]  # the record's clock and the writer's
    return rows


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """Three seeded runs of one learner on one window: ``inline`` saves at
    every logged update (today's order throughout), ``behind`` saves never
    inside the run (every crossing but the last can hide behind a dispatch),
    ``between`` is ``behind`` with a budget that ends between two crossings."""
    out = {}
    for name, port, n, save in (
        ("inline", 29781, 12, 2), ("behind", 29783, 12, 1000), ("between", 29785, 11, 1000),
    ):
        tmp = tmp_path_factory.mktemp(name)
        svc, _cfg = _run_learner(
            tmp, port, n_updates=n, loss_log_interval=2, model_save_interval=save
        )
        out[name] = (svc, tmp)
    return out


@pytest.mark.timeout(300)
def test_every_logged_update_has_its_books_closed_once(runs):
    inline, behind, between = (runs[k][0] for k in ("inline", "behind", "between"))
    assert inline.n_log_behind_dispatch == 0
    assert inline.n_log_inline == {"save": 6, "stop": 0, "empty feed": 0}
    # the twelfth update ends the budget: read, verify, stop
    assert behind.n_log_inline["save"] == 0 and behind.n_log_inline["stop"] == 1
    assert behind.n_log_behind_dispatch >= 1
    assert behind.n_log_behind_dispatch + behind.n_log_inline["empty feed"] == 5
    # update 10's books closed behind dispatch 11, and nothing left over
    assert between.n_log_inline["save"] == between.n_log_inline["stop"] == 0
    assert between.n_log_behind_dispatch + between.n_log_inline["empty feed"] == 5


@pytest.mark.timeout(300)
def test_lines_closed_behind_a_dispatch_equal_lines_closed_in_line(runs):
    a, b, c = (_lines(runs[k][1]) for k in ("inline", "behind", "between"))
    assert [r["idx"] for r in a] == [r["idx"] for r in b] == [2, 4, 6, 8, 10, 12]
    assert all(r["n_updates"] == 2.0 for r in a + b + c)
    assert a == b  # index by index: counts, derived values, buckets
    assert c == b[:5]  # a budget that ends between crossings leaves its last line


@pytest.mark.timeout(300)
def test_last_losses_are_the_last_logged_update_s(runs):
    inline, behind, between = (runs[k][0] for k in ("inline", "behind", "between"))
    assert inline.last_losses == behind.last_losses
    assert set(inline.last_losses) >= {"loss", "grad-norm", "nonfinite-updates"}
    assert all(math.isfinite(v) for v in between.last_losses.values())
    assert between.last_losses != behind.last_losses  # update 10's, not 12's
    assert inline.n_nonfinite_updates == behind.n_nonfinite_updates == 0.0


# --------------------------------------------------- a feed with nothing ready
@pytest.mark.timeout(300)
def test_a_starved_feed_gets_its_line_without_waiting_for_a_batch(tmp_path):
    seen = {}

    def indices():
        return [r["idx"] for r in _learn_lines(tmp_path / "run")]

    def produce(store, window, stop):
        put = 0
        while put < 4 * B and not stop.is_set():  # four updates' windows
            if store.put(window):
                put += 1
            else:
                time.sleep(0.0005)
        # paused: update 4's line must come with no batch behind it
        deadline = time.monotonic() + 120
        while 4 not in indices() and time.monotonic() < deadline:
            time.sleep(0.01)
        seen["indices"] = indices()
        stop.set()

    svc, _cfg = _run_learner(
        tmp_path, 29787, n_updates=None, produce=produce,
        loss_log_interval=2, model_save_interval=1000,
    )
    assert seen["indices"] == [2, 4]
    assert svc.n_log_inline["empty feed"] >= 1 and svc.n_log_inline["save"] == 0
    assert svc.n_log_behind_dispatch + sum(svc.n_log_inline.values()) == 2


@pytest.mark.timeout(300)
def test_books_set_aside_are_closed_before_an_idle_poll(tmp_path):
    """The feed said a batch was ready and then had none: the books are not
    held for one — they are closed in that iteration, before the poll."""

    def at_crossing(feed, _n):
        if feed.crossings == 2:
            feed.hold = True
        return 1

    svc, _cfg = _run_learner(
        tmp_path, 29789, n_updates=8, prepare=_with_feed(at_crossing),
        loss_log_interval=2, model_save_interval=1000,
    )
    main = sorted(_lanes_of(tmp_path / "run")["main"], key=lambda e: e["ts"])
    names = [e["name"] for e in main]
    sync = next(
        i for i, e in enumerate(main)
        if e["name"] == "log-sync" and e["args"]["update"] == 4
    )
    assert names[sync + 1 : sync + 5] == ["feed-wait", "log-write", "diag-drain", "idle-poll"]
    assert svc.n_log_inline == {"save": 0, "stop": 1, "empty feed": 1}
    assert svc.n_log_behind_dispatch == 2
    assert [r["idx"] for r in _lines(tmp_path)] == [2, 4, 6, 8]


@pytest.mark.timeout(300)
def test_books_still_set_aside_when_the_loop_stops_are_closed_after_it(tmp_path):
    """The stop falls between a crossing and the dispatch its books would
    have been closed behind: they are closed after the loop, so the last
    line, ``last_losses`` and the final telemetry are what they were."""
    holder = {}

    def at_crossing(feed, _n):
        if feed.crossings == 3:
            holder["svc"].stop_event.set()
        return 1

    def prepare(svc):
        holder["svc"] = svc
        _with_feed(at_crossing)(svc)

    svc, _cfg = _run_learner(
        tmp_path, 29791, n_updates=None, prepare=prepare,
        loss_log_interval=2, model_save_interval=1000,
    )
    assert [r["idx"] for r in _lines(tmp_path)] == [2, 4, 6]
    assert svc.n_log_inline == {"save": 0, "stop": 1, "empty feed": 0}
    assert svc.n_log_behind_dispatch == 2
    assert svc.last_losses and all(math.isfinite(v) for v in svc.last_losses.values())
    main = sorted(_lanes_of(tmp_path / "run")["main"], key=lambda e: e["ts"])
    assert [e["name"] for e in main][-3:] == ["log-sync", "log-write", "diag-drain"]
    assert [e["args"]["update"] for e in main if e["name"] == "dispatch"][-1] == 6
