"""A deployment as a user starts it: ``python -m tpu_rl local`` with generated
``--params`` / ``--machines`` files, watched from outside.

This process never imports JAX: the chip belongs to the program's own learner
(or colocated loop), a child of the CLI's supervisor; every other role is a CPU
process. The benchmark follows ``result_dir/learn.jsonl`` for the window,
samples ``telemetry.json`` for the layers' counters, and afterwards reads the
chip owner's ``backend-<role>.json``, the final telemetry and the log. The run
ends with SIGTERM to the supervisor, which stops its children cooperatively;
whatever is left of the process group is then killed and waited for.

The parity check needs the chip, so it runs as a child once the program has
released it (``benchmarks/parity.py``).

A traced run asks the program for a capture the way its role offers it:
``"via": "config"`` writes the learner's profiler window into the generated
params (``profile_dir / profile_start / profile_steps``); ``"via": "http"``
calls ``GET /prof?ms=N`` on ``--telemetry-port`` (the colocated loop reads no
profiler window from its config).
"""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

from benchmarks import harness


def wait_for(path: str, alive, timeout_s: float) -> None:
    t_end = time.monotonic() + timeout_s
    while not os.path.exists(path):
        if not alive():
            raise harness.RunFailed(f"the program stopped before writing {path}")
        if time.monotonic() > t_end:
            raise harness.RunFailed(f"no {os.path.basename(path)} in {timeout_s:.0f} s")
        time.sleep(0.05)


class TelemetryWatch:
    """Every ``telemetry.json`` document written while the window runs (the
    storage process rewrites the file every ``telemetry_interval_s``)."""

    def __init__(self, path: str):
        self.path = path
        self.docs: list[dict] = []
        self._mtime = None
        self._n = 0

    def poll(self) -> None:
        self._n += 1
        if self._n % 50:  # every ~0.1 s of the 2 ms poll
            return
        try:
            mtime = os.stat(self.path).st_mtime_ns
        except FileNotFoundError:
            return
        if mtime == self._mtime:
            return
        self._mtime = mtime
        try:
            self.docs.append(harness.load_json(self.path))
        except (OSError, ValueError):
            self._mtime = None  # replaced under us; the next poll reads it


def role_ts(doc: dict, role: str) -> float:
    """When the newest source of ``role`` in ``doc`` took its snapshot."""
    return max(
        (float(s["ts"]) for s in doc.get("sources", []) if s.get("role") == role),
        default=0.0,
    )


def stop_group(proc: subprocess.Popen, grace_s: float = 60.0) -> None:
    """SIGTERM to the supervisor, then whatever the group still holds is
    killed; returns once the supervisor has been waited for."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=grace_s)
        except subprocess.TimeoutExpired:
            pass
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    proc.wait()


def run_parity(spec: harness.Spec, params: dict, work: str) -> dict:
    """``benchmarks/parity.py`` as a child: it owns the chip while it runs."""
    spec_path, out = os.path.join(work, "parity-spec.json"), os.path.join(work, "parity.json")
    with open(spec_path, "w") as f:
        json.dump({"params": params, "parity": spec.config["parity"], "seed": spec.seed}, f)
    with open(os.path.join(work, "parity.log"), "w") as log:
        rc = subprocess.run(
            [sys.executable, os.path.join(harness.HERE, "parity.py"),
             "--spec", spec_path, "--out", out],
            cwd=harness.ROOT, stdout=log, stderr=subprocess.STDOUT, timeout=600,
        ).returncode
    if rc != 0:
        with open(os.path.join(work, "parity.log"), errors="replace") as f:
            raise harness.RunFailed(f"parity child exited {rc}\n{f.read()[-2000:]}")
    return harness.load_json(out)


def run(spec: harness.Spec) -> harness.Run:
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        harness.check_device("cpu", 0, spec.chips)  # the children would inherit it

    from tpu_rl.config import Config
    from tpu_rl.data.layout import BatchLayout

    t = spec.traffic
    role = t["role"]
    work = tempfile.mkdtemp(prefix="bench-cli-")
    params = dict(spec.params)
    argv = [
        sys.executable, "-m", "tpu_rl", "local",
        "--params", os.path.join(work, "params.json"),
        "--machines", os.path.join(work, "machines.json"),
        "--result-dir", work, "--seed", str(spec.seed),
        "--publish-interval", str(spec.config["publish_interval"]),
        *t.get("cli_args", []),
    ]
    tr = t["trace"]
    prof_dir = os.path.join(work, "prof")
    telemetry_port = None
    if spec.trace and tr["via"] == "config":
        params.update(profile_dir=prof_dir, profile_start=int(tr["start_update"]),
                      profile_steps=int(tr["updates"]))
    base = harness.free_port_block(5)  # learner, model, (inference), manager, telemetry
    if spec.trace and tr["via"] == "http":
        telemetry_port = base + 4
        argv += ["--telemetry-port", str(telemetry_port)]
    with open(os.path.join(work, "params.json"), "w") as f:
        json.dump(params, f)
    with open(os.path.join(work, "machines.json"), "w") as f:
        json.dump({
            "learner": {"ip": "127.0.0.1", "port": base},
            "workers": [{
                "num_p": int(t.get("fleet", {}).get("workers", 1)),
                "manager_ip": "127.0.0.1", "ip": "127.0.0.1", "port": base + 3,
            }],
        }, f)

    # What the chip owner trains on: in colocated mode the env batch is the
    # train batch (runtime/colocated.resolve_colocated_config).
    shaped = dict(params)
    if "colocated" in t.get("cli_args", []) and params.get("colocated_envs"):
        shaped["batch_size"] = params["colocated_envs"]
        shaped["buffer_size"] = max(params.get("buffer_size", 10240), shaped["batch_size"])
    cfg = Config.from_dict(shaped)
    layout = BatchLayout.from_config(cfg)

    log_path = os.path.join(work, "cli.log")
    tail = harness.LearnTail(os.path.join(work, "learn.jsonl"))
    watch = TelemetryWatch(os.path.join(work, "telemetry.json"))
    backend_path = os.path.join(work, f"backend-{role}.json")
    captured: dict = {}
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            argv, cwd=harness.ROOT, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
    try:
        alive = lambda: proc.poll() is None  # noqa: E731
        wait_for(backend_path, alive, float(t["warmup_timeout_s"]))
        backend = harness.load_json(backend_path)
        harness.check_device(backend["platform"], backend["device_count"], spec.chips)
        phases = {"chip_owner_up": time.monotonic() - spec.t_start}  # where set-up goes

        def ask_for_capture() -> None:
            if telemetry_port is not None:
                threading.Thread(
                    target=http_capture, args=(telemetry_port, tr, captured),
                    daemon=True,
                ).start()

        window = harness.measure(
            tail, int(t["warmup_pairs"]), spec.seconds, alive,
            float(t["warmup_timeout_s"]), ask_for_capture, watch.poll,
        )
    except BaseException:
        stop_group(proc, grace_s=5.0)
        keep(spec, work)
        raise
    finally:
        tail.close()
    phases["first_line"] = tail.rows[0].mono - spec.t_start
    t_stop = time.monotonic()
    stop_group(proc)
    phases["shutdown_took"] = time.monotonic() - t_stop
    try:
        verdict = run_parity(spec, shaped, work)
        phases["parity_took"] = time.monotonic() - t_stop - phases["shutdown_took"]
        harness.check_device(verdict["platform"], verdict["device_count"], spec.chips)
        final = harness.load_json(os.path.join(work, "telemetry.json"))
        backend = harness.load_json(backend_path)
        with open(log_path, errors="replace") as f:
            text = f.read()
        losses = [float(x) for x in re.findall(rf"\[{role}\] update .*?  loss (\S+)", text)]
        # Snapshots the chip owner took inside the window, by its own clock.
        t0, t1 = (float(r.row["ts"]) for r in (window.start, window.end))
        inside = [d for d in watch.docs if t0 <= role_ts(d, role) <= t1]
        first, last = (inside[0], inside[-1]) if inside else (final, final)
        total = lambda doc, name: harness.gauge_max(doc, role, f"{role}-{name}") or 0.0  # noqa: E731
        reduced = None
        if spec.trace:
            from benchmarks import trace

            reduced = trace.load(captured.get("dir", prof_dir))
        return harness.Run(
            spec=spec,
            window=window,
            transitions_per_update=cfg.batch_size * cfg.seq_len,
            # the colocated program's batch never leaves the device
            bytes_per_update=0 if role == "colocated"
            else layout.traj_floats * 4 * cfg.batch_size,
            device={
                "platform": backend["platform"],
                "kind": backend["device_kind"],
                "count": backend["device_count"],
                "memory_peak_bytes": int(total(final, "device-mem-peak-bytes")),
            },
            parity=verdict,
            losses_finite=bool(losses) and all(math.isfinite(x) for x in losses),
            failed_updates=int(
                total(final, "nonfinite-updates") + total(final, "rollbacks")
                + sum(float(x) for x in re.findall(r"nonfinite-updates (\S+)", text)
                      if role != "learner")
            ),
            recompiles=int(total(final, "xla-recompiles") - total(first, "xla-recompiles")),
            paths=backend,
            timers={
                name: harness.gauge_max(last, role, f"{name}-elapsed-mean-sec")
                for name in ("learner-queue-wait-time", "learner-batching-time",
                             "learner-step-time")
            },
            telemetry=(watch.docs[0], watch.docs[-1]) if len(watch.docs) > 1 else None,
            trace=reduced,
            notes={"checks": checks(t, final), "window": {"phases_s": phases}},
        )
    finally:
        keep(spec, work)


def checks(t: dict, final: dict) -> dict:
    """Cell-specific conditions of ``correct``, named in the traffic file."""
    out = {}
    if t.get("require_broadcast_policy"):
        version = harness.gauge_max(final, "worker", "worker-policy-version")
        out["worker_acted_on_broadcast_policy"] = (version or 0) > 0
    return out


def http_capture(port: int, tr: dict, captured: dict) -> None:
    """``GET /prof?ms=N`` once the window is ``after_s`` old."""
    time.sleep(float(tr.get("after_s", 2.0)))
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/prof?ms={int(tr['ms'])}", timeout=60
        ) as r:
            captured["dir"] = json.loads(r.read()).get("trace_dir")
    except (OSError, ValueError) as e:
        captured["error"] = repr(e)


def keep(spec: harness.Spec, work: str) -> None:
    if spec.artifacts:
        shutil.copytree(
            work, spec.artifacts, dirs_exist_ok=True,
            ignore=shutil.ignore_patterns("models", "history", "telemetry"),
        )
    shutil.rmtree(work, ignore_errors=True)
