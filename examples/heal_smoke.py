"""Self-healing smoke: prove the heal plane (tpu_rl.heal) end to end.

Three phases, exits nonzero on any failure — the ``make heal-smoke`` CI
gate:

1. **In-process guard math** — with clean data, guard-on training is
   bit-identical to guard-off (every leaf's select takes the applied
   value, which the pre-guard update computed); with a NaN in the batch, guard-on leaves params
   bitwise untouched and counts every skipped sub-update.
2. **NaN chaos run** — the smallest real cluster under a data-fault plan
   that poisons one worker's rollout values (``nan:``/``spike:`` on obs/
   rew, contained at the storage ingress edge) and the OTHER worker's
   log_prob column (deliberately NOT ingress-checked — it rides into
   training and must be contained by the in-jit guards, then tripped on
   by the watchdog).
   Asserts: the learner rolled back to a committed checkpoint at least
   once and bumped the run epoch (``learner_rollback.jsonl``), the
   poisoned worker was quarantined AND later un-quarantined on clean
   re-probe, every rollout-channel injection is accounted
   (injected == storage-poisoned-frames, exactly), the guards skipped at
   least one nonfinite update, the fleet kept producing episodes, and the
   run still completed cleanly.
3. **Clean run** — same healing config, no chaos: zero rollbacks, zero
   quarantines, zero poisoned frames, zero nonfinite updates.

Run:
  JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python examples/heal_smoke.py \
      [--updates 10] [--base-port 29200]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# The rollout-value faults target ONLY wid=1 (worker-0-1): their NaN/spike
# obs+rew are caught at the storage ingress edge and quarantine that worker.
# The window closes mid-run (for=6s) so wid 1's clean re-probe
# un-quarantines it and its final chaos counters are exported well before
# shutdown (exact injected==poisoned accounting). The logp fault rides
# wid=0 — the worker that STAYS in the fleet — because quarantine drops
# every frame from wid 1, poisoned or not; a logp fault there would never
# reach the learner. On wid 0 it passes ingress (log_prob is deliberately
# unvalidated) and must be contained by the in-jit guards; the long window
# keeps poison flowing while the learner is past its first-compile stall.
DEFAULT_SPEC = (
    "nan:rollout@p=0.4@t+4s@for=6s@wid=1,"
    "spike:rollout@p=0.2@t+4s@for=6s@wid=1,"
    "nan:logp@p=0.5@t+2s@for=25s@wid=0"
)


def _counter(source: dict, name: str) -> float:
    return sum(
        v for n, _labels, v in source.get("counters", ()) if n == name
    )


def _role_total(tele: dict, role: str, name: str) -> float:
    return sum(
        _counter(s, name) for s in tele["sources"] if s.get("role") == role
    )


def _tree_equal(a, b) -> bool:
    import jax
    import numpy as np

    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb)
    )


def check_guard_math() -> list[str]:
    """Phase 1: in-jit guard semantics, no cluster needed."""
    import jax
    import jax.numpy as jnp

    from tests.conftest import small_config
    from tests.test_algos import make_batch
    from tpu_rl.algos.registry import get_algo

    failures: list[str] = []
    cfg_on = small_config(algo="PPO", update_guard=True)
    cfg_off = small_config(algo="PPO", update_guard=False)
    fam, s_on, step_on = get_algo("PPO").build(cfg_on, jax.random.PRNGKey(0))
    _, s_off, step_off = get_algo("PPO").build(cfg_off, jax.random.PRNGKey(0))
    batch = make_batch(cfg_on, fam)
    k = jax.random.PRNGKey(1)
    s_on1, m_on = jax.jit(step_on)(s_on, batch, k)
    s_off1, _ = jax.jit(step_off)(s_off, batch, k)
    if not _tree_equal(s_on1.params, s_off1.params):
        failures.append("guard-on clean step is not bit-identical to guard-off")
    if float(m_on["nonfinite-updates"]) != 0.0:
        failures.append(
            f"clean step counted {float(m_on['nonfinite-updates'])} "
            "nonfinite updates, expected 0"
        )

    # Poison log_prob (what nan:logp injects): every K_epoch sub-update
    # must be skipped, params bitwise untouched.
    bad = batch.replace(log_prob=batch.log_prob.at[0, 0, 0].set(jnp.nan))
    s_bad, m_bad = jax.jit(step_on)(s_on, bad, k)
    if not _tree_equal(s_bad.params, s_on.params):
        failures.append("guard let a NaN update touch params")
    if float(m_bad["nonfinite-updates"]) != float(cfg_on.K_epoch):
        failures.append(
            f"NaN step counted {float(m_bad['nonfinite-updates'])} skips, "
            f"expected K_epoch={cfg_on.K_epoch}"
        )
    if not failures:
        print("[heal-smoke] guard math: bit-identical clean, contained NaN",
              flush=True)
    return failures


def run_phase(
    name: str,
    chaos_spec: str | None,
    base_port: int,
    updates: int,
    timeout: float,
):
    """One cluster run with the healing plane armed; returns
    (telemetry dict, rollback records, storage exitcode, failures)."""
    from tests.conftest import small_config
    from tpu_rl.config import MachinesConfig, WorkerMachine
    from tpu_rl.runtime.runner import local_cluster

    run_dir = tempfile.mkdtemp(prefix=f"heal_smoke_{name}_")
    cfg = small_config(
        env="CartPole-v1",
        algo="PPO",
        worker_step_sleep=0.0,
        learner_device="cpu",
        rollout_lag_sec=30.0,
        time_horizon=100,
        loss_log_interval=2,
        result_dir=run_dir,
        model_dir=os.path.join(run_dir, "ckpt"),
        model_save_interval=2,
        ckpt_keep=4,
        telemetry_interval_s=0.5,
        telemetry_stale_s=120.0,
        supervise_poll_s=0.5,
        # The healing plane under test:
        update_guard=True,
        watchdog_enabled=True,
        watchdog_nonfinite=2,
        max_rollbacks=10,
        rollback_window_s=600.0,
        ingress_validate=True,
        quarantine_strikes=3,
        quarantine_clear_s=2.0,
        chaos_spec=chaos_spec,
        chaos_seed=11,
    )
    machines = MachinesConfig(
        learner_ip="127.0.0.1",
        learner_port=base_port,
        workers=[WorkerMachine(
            num_p=2, manager_ip="127.0.0.1", ip="127.0.0.1",
            port=base_port + 5,
        )],
    )
    failures: list[str] = []
    print(
        f"[heal-smoke] {name}: cluster up; run_dir={run_dir} "
        f"spec={chaos_spec!r}", flush=True,
    )
    sup = local_cluster(cfg, machines, max_updates=updates)
    loop_thread = threading.Thread(target=sup.loop, daemon=True)
    loop_thread.start()
    try:
        if not sup.stop_event.wait(timeout):
            failures.append(
                f"{name}: fleet did not complete within {timeout:.0f}s"
            )
        loop_thread.join(10.0)
        learner = next(c for c in sup.children if c.name == "learner")
        learner.proc.join(30.0)
        if learner.proc.is_alive() or learner.proc.exitcode != 0:
            failures.append(
                f"{name}: learner did not complete cleanly "
                f"(alive={learner.proc.is_alive()}, "
                f"exitcode={learner.proc.exitcode})"
            )
    finally:
        sup.stop()

    storage = next(c for c in sup.children if c.name == "storage")
    tele = {"sources": []}
    try:
        tele = json.loads(open(os.path.join(run_dir, "telemetry.json")).read())
    except (OSError, ValueError) as e:
        failures.append(
            f"{name}: telemetry.json invalid: {type(e).__name__}: {e}"
        )
    rollbacks: list[dict] = []
    rb_path = os.path.join(run_dir, "learner_rollback.jsonl")
    if os.path.exists(rb_path):
        try:
            with open(rb_path) as f:
                rollbacks = [json.loads(line) for line in f if line.strip()]
        except (OSError, ValueError) as e:
            failures.append(
                f"{name}: learner_rollback.jsonl invalid: "
                f"{type(e).__name__}: {e}"
            )
    return tele, rollbacks, storage.proc.exitcode, failures


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--updates", type=int, default=10)
    p.add_argument("--base-port", type=int, default=29200)
    p.add_argument("--chaos-spec", default=DEFAULT_SPEC)
    p.add_argument("--timeout", type=float, default=300.0)
    args = p.parse_args()
    failures: list[str] = []

    # ---- phase 1: in-jit guard semantics --------------------------------
    failures += check_guard_math()

    # ---- phase 2: NaN chaos — contain, roll back, quarantine, recover ---
    tele, rollbacks, _exit, errs = run_phase(
        "chaos", args.chaos_spec, args.base_port, args.updates, args.timeout
    )
    failures += errs

    if not rollbacks:
        failures.append("chaos: no rollback recorded — the watchdog never "
                        "tripped (or no committed checkpoint existed)")
    else:
        epochs = [r.get("epoch", 0) for r in rollbacks]
        print(
            f"[heal-smoke] chaos: {len(rollbacks)} rollback(s), run epoch "
            f"-> {max(epochs)}", flush=True,
        )
        if max(epochs) < 1:
            failures.append(
                f"chaos: rollback never bumped the run epoch: {epochs}"
            )
    n_rb = _role_total(tele, "learner", "learner-rollbacks")
    if n_rb < 1:
        failures.append(f"chaos: learner-rollbacks={n_rb}, expected >= 1")
    nf = _role_total(tele, "learner", "learner-nonfinite-updates")
    if nf < 1:
        failures.append(
            f"chaos: learner-nonfinite-updates={nf}, expected >= 1 — the "
            "logp poison never reached (or never tripped) the in-jit guards"
        )

    # Fault accounting: DataChaos injects at most one rollout-channel fault
    # per frame and ingress classifies BEFORE the epoch fence, so the
    # worker-side injection counters must equal storage's poisoned-frame
    # drops exactly (logp injections are a separate, unvalidated channel).
    injected = _role_total(tele, "worker", "chaos-nan-injected") + _role_total(
        tele, "worker", "chaos-spike-injected"
    )
    poisoned = _role_total(tele, "storage", "storage-poisoned-frames")
    if injected < 1:
        failures.append("chaos: zero rollout-value injections — the data "
                        "fault plan never fired")
    if injected != poisoned:
        failures.append(
            f"chaos: fault accounting mismatch: injected {injected} "
            f"rollout-value faults but storage poisoned {poisoned}"
        )
    else:
        print(
            f"[heal-smoke] chaos: {injected:.0f} injected == "
            f"{poisoned:.0f} poisoned", flush=True,
        )
    if _role_total(tele, "worker", "chaos-logp-nan-injected") < 1:
        failures.append("chaos: zero logp injections — the guard-channel "
                        "fault never fired")

    nq = _role_total(tele, "storage", "storage-quarantines")
    nuq = _role_total(tele, "storage", "storage-unquarantines")
    if nq < 1:
        failures.append(f"chaos: storage-quarantines={nq}, expected >= 1")
    if nuq < 1:
        failures.append(
            f"chaos: storage-unquarantines={nuq}, expected >= 1 — the "
            "poisoned worker never cleared on clean re-probe"
        )
    if nq >= 1 and nuq >= 1:
        print(
            f"[heal-smoke] chaos: quarantines={nq:.0f} "
            f"unquarantines={nuq:.0f} "
            f"dropped-clean={_role_total(tele, 'storage', 'storage-quarantined-frames'):.0f}",
            flush=True,
        )
    # Loose learning bar: the fleet kept producing episodes throughout
    # (logp poison skews training, not acting; quarantine drops frames,
    # not the worker's env loop).
    episodes = _role_total(tele, "worker", "worker-episodes")
    if episodes < 1:
        failures.append(f"chaos: worker-episodes={episodes}, fleet starved")

    # ---- phase 3: clean run — the healing plane must be invisible -------
    tele, rollbacks, _exit, errs = run_phase(
        "clean", None, args.base_port + 20, max(4, args.updates // 2),
        args.timeout,
    )
    failures += errs
    for metric, role in (
        ("learner-rollbacks", "learner"),
        ("learner-nonfinite-updates", "learner"),
        ("storage-poisoned-frames", "storage"),
        ("storage-quarantines", "storage"),
        ("storage-quarantined-frames", "storage"),
    ):
        v = _role_total(tele, role, metric)
        if v != 0:
            failures.append(f"clean: {metric}={v}, expected 0")
    if rollbacks:
        failures.append(f"clean: {len(rollbacks)} rollback(s) recorded")

    if failures:
        for f in failures:
            print(f"[heal-smoke] FAIL: {f}", file=sys.stderr, flush=True)
        return 1
    print("[heal-smoke] OK", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
