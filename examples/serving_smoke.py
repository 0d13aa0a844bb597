"""Serving fast-path smoke: a real two-replica fleet serving QUANTIZED
(bf16) params through the shape-bucketed batching path, proving the ISSUE 16
composition end to end on CPU:

- two ``replica_main`` processes boot with ``inference_dtype="bf16"`` and
  ``inference_buckets=8`` — every bucket program compiles BEFORE the socket
  binds, and the post-warm recompile count must stay exactly 0 across a
  flush-size sweep (the PR 11 ratchet through the quantized+bucketed path);
- a live model PUB bumps the policy version mid-run, so the sweep crosses
  ver-keyed re-quantizing swaps;
- client threads drive mixed-width requests (1..12 rows) through real
  DEALER sockets: zero failures allowed;
- LIVE PARITY SPOT-CHECK: a fresh client sends ``first=1`` (zero carry) and
  the reply's logits are compared against the local f32 reference act on
  the same observations — argmax must agree on every row and the logits
  must match within bf16 tolerance, proving the quantized serving path
  answers with the same policy, not just quickly.

Exits nonzero on any failure — this is the ``make serving-smoke`` CI gate.

Run:
  JAX_PLATFORMS=cpu PYTHONPATH=/root/repo python examples/serving_smoke.py \
      [--base-port 31300]
"""

from __future__ import annotations

import argparse
import multiprocessing as mp
import os
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--base-port", type=int, default=31300)
    p.add_argument("--acts", type=int, default=60,
                   help="timed acts per client thread")
    args = p.parse_args()

    # This parent and its two replica processes all run jax, and one process
    # owns a chip: the smoke is a CPU one unless the caller says otherwise.
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpu_rl.config import Config
    from tpu_rl.fleet import replica_main
    from tpu_rl.loadgen import probe_ready
    from tpu_rl.models.families import build_family
    from tpu_rl.runtime.inference_service import InferenceClient
    from tpu_rl.runtime.protocol import Protocol
    from tpu_rl.runtime.transport import MODEL_HWM, Pub, Sub

    model_port = args.base_port + 10
    stat_port = args.base_port + 11
    result_dir = tempfile.mkdtemp(prefix="serving-smoke-")
    cfg = Config.from_dict(dict(
        algo="IMPALA", obs_shape=(4,), action_space=2, hidden_size=32,
        worker_num_envs=16, act_mode="remote",
        inference_replicas=2, inference_base_port=args.base_port,
        inference_batch=16, inference_flush_us=500,
        inference_timeout_ms=3000, inference_hedge_ms=500,
        inference_retries=1,
        # The fast path under test: bf16 serving params + bucket ladder
        # [8, 16]; telemetry installs the per-bucket recompile watches.
        inference_dtype="bf16", inference_buckets=8,
        result_dir=result_dir, telemetry_interval_s=0.5,
    ))
    ports = [args.base_port, args.base_port + 1]
    endpoints = [("127.0.0.1", prt) for prt in ports]

    family = build_family(cfg)
    params = family.init_params(jax.random.key(0), seq_len=cfg.seq_len)
    actor_host = jax.device_get(params["actor"])
    pub = Pub("*", model_port, bind=True, hwm=MODEL_HWM)
    stop_pub = threading.Event()

    def _publish() -> None:
        ver = 0
        while not stop_pub.is_set():
            ver += 1
            pub.send(Protocol.Model, {"actor": actor_host, "ver": ver})
            stop_pub.wait(1.0)

    # Stat tap: bind the SUB end of the replicas' stat PUBs and keep each
    # replica's latest snapshot — the recompile ratchet's evidence.
    stat_sub = Sub("*", stat_port, bind=True)
    latest: dict[int, dict] = {}
    stop_stats = threading.Event()

    def _collect_stats() -> None:
        while not stop_stats.is_set():
            for proto, snap in stat_sub.drain(max_msgs=256):
                if proto == Protocol.Telemetry and isinstance(snap, dict):
                    latest[int(snap.get("rid", -1))] = snap
            stop_stats.wait(0.1)

    ctx = mp.get_context("spawn")
    replicas = [
        ctx.Process(
            target=replica_main,
            args=(cfg, i, ports[i], "127.0.0.1", model_port,
                  stat_port, None, None),
            kwargs={"seed": 0},
            daemon=True,
        )
        for i in range(2)
    ]

    failures: list[str] = []
    try:
        for proc in replicas:
            proc.start()
        print(f"[serving] fleet booting on {ports} (bf16 + buckets [8, 16])",
              flush=True)
        if not probe_ready(endpoints, cfg, timeout_s=180.0):
            print("[serving] FAIL: fleet never became ready", flush=True)
            return 1
        threading.Thread(target=_publish, daemon=True).start()
        threading.Thread(target=_collect_stats, daemon=True).start()

        # ---- mixed-width sweep: both replicas, every bucket program
        fail_counts = [0, 0]

        def drive(k: int) -> None:
            cl = InferenceClient(cfg, "127.0.0.1", ports[k % 2], wid=k)
            try:
                rng = np.random.default_rng(k)
                widths = [1, 2, 4, 7, 9, 12]
                for i in range(args.acts):
                    n = widths[i % len(widths)]
                    obs = rng.standard_normal((n, 4)).astype(np.float32)
                    first = (
                        np.ones(n, np.float32) if i == 0
                        else np.zeros(n, np.float32)
                    )
                    if cl.act(obs, first) is None:
                        fail_counts[k % 2] += 1
            finally:
                cl.close()

        threads = [
            threading.Thread(target=drive, args=(k,), daemon=True)
            for k in range(4)
        ]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        n_acts = 4 * args.acts
        print(f"[serving] sweep: {n_acts} mixed-width acts in {dt:.1f}s, "
              f"failures {sum(fail_counts)}", flush=True)
        if sum(fail_counts):
            failures.append(f"{sum(fail_counts)} client acts failed")

        # ---- live parity spot-check against the local f32 reference
        rng = np.random.default_rng(1234)
        obs = rng.standard_normal((8, 4)).astype(np.float32)
        cl = InferenceClient(cfg, "127.0.0.1", ports[0], wid=99)
        try:
            reply = cl.act(obs, np.ones(8, np.float32))  # first=1: zero carry
        finally:
            cl.close()
        if reply is None:
            failures.append("parity probe got no reply")
        else:
            if int(reply.get("ver", -1)) < 1:
                failures.append(
                    f"parity reply served pre-broadcast weights "
                    f"(ver {reply.get('ver')})"
                )
            hw, cw = family.carry_widths
            _a, ref_logits, _lp, _h2, _c2 = family.act(
                params, jnp.asarray(obs), jnp.zeros((8, hw)),
                jnp.zeros((8, cw)), jax.random.key(0),
            )
            ref = np.asarray(ref_logits)
            got = np.asarray(reply["logits"])
            maxdiff = float(np.abs(got - ref).max())
            agree = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
            print(f"[serving] parity: logits maxdiff {maxdiff:.2e}, "
                  f"argmax agreement {agree:.0%}, ver {reply['ver']}",
                  flush=True)
            if maxdiff > 5e-2:
                failures.append(f"bf16 logits drifted {maxdiff} > 5e-2")
            if agree < 1.0:
                failures.append(f"argmax disagreement ({agree:.0%})")

        # ---- the ratchet: both replicas' live counters must report 0
        t_wait = time.monotonic() + 30.0
        while len(latest) < 2 and time.monotonic() < t_wait:
            time.sleep(0.2)
        if len(latest) < 2:
            failures.append("replica telemetry never arrived")
        for rid, snap in sorted(latest.items()):
            val = next(
                (v for name, _lbls, v in snap.get("counters", [])
                 if name == "inference-xla-recompiles"),
                None,
            )
            print(f"[serving] replica {rid}: recompiles {val}", flush=True)
            if val is None:
                failures.append(f"replica {rid} published no recompile count")
            elif val != 0:
                failures.append(f"replica {rid} recompiled {val}x post-warm")
    finally:
        stop_pub.set()
        stop_stats.set()
        pub.close()
        stat_sub.close()
        for proc in replicas:
            if proc.is_alive():
                proc.kill()
            proc.join(timeout=10)

    if failures:
        for f in failures:
            print(f"[serving] FAIL: {f}", flush=True)
        return 1
    print("[serving] OK: bf16+bucketed fleet served every flush shape with "
          "0 recompiles, 0 failures, and live f32 parity", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
