"""Subprocess "virtual pod host" body for tests/test_colocated_multihost.py.

Each invocation is one host of a CPU pod: XLA_FLAGS pins the per-host
device count BEFORE jax imports, and ``Config.multihost`` brings the host
into the jax.distributed runtime (gloo collectives) inside
``ColocatedLoop.__init__`` — the production bring-up path, not a test
shim.

    python colocated_multihost_child.py <mode> <pid> <nprocs> <ndev> \
        <port> <workdir> <max_updates>

Modes:
    parity  — run the fused pod-Anakin loop for <max_updates> updates with
              no checkpointing, then dump every train-state leaf to
              ``<workdir>/params_<nprocs>_<pid>.npz`` and print
              ``CHILD_PARAMS sha=...`` (sha256 over the leaf bytes).
    train   — run with two-phase checkpointing into <workdir>; meant to be
              SIGKILLed mid-run by the parent test.
    resume  — same config as train; restores the newest committed
              checkpoint, prints ``CHILD_RESUME pid=.. start_it=..
              epoch=..``, and runs to <max_updates>.

Every successful exit prints CHILD_OK.
"""

import hashlib
import os
import sys

mode = sys.argv[1]
pid = int(sys.argv[2])
nprocs = int(sys.argv[3])
ndev = int(sys.argv[4])
port = sys.argv[5]
workdir = sys.argv[6]
max_updates = int(sys.argv[7])

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={ndev}"
)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from tpu_rl.config import Config  # noqa: E402
from tpu_rl.runtime.colocated import ColocatedLoop  # noqa: E402


def build_config(model_dir: str | None) -> Config:
    mh = None
    if nprocs > 1:
        mh = {
            "coordinator": f"127.0.0.1:{port}",
            "num_processes": nprocs,
            "process_id": pid,
        }
    return Config.from_dict(
        dict(
            env="CartPole-v1", env_mode="colocated", algo="PPO",
            hidden_size=32, seq_len=8, batch_size=32,
            lr=3e-4, entropy_coef=0.001, reward_scale=0.1,
            time_horizon=100, loss_log_interval=10**9,
            mesh_data=nprocs * ndev,
            multihost=mh,
            model_dir=model_dir,
            model_save_interval=5,
        )
    )


def main() -> None:
    model_dir = None if mode == "parity" else os.path.join(workdir, "ckpt")
    loop = ColocatedLoop(build_config(model_dir), seed=0,
                         max_updates=max_updates)
    # log=True in resume mode: the chief's "resumed from committed
    # checkpoint" line is part of what the parent test pins (and the loop
    # itself silences every non-chief process).
    out = loop.run(log=(mode == "resume"))

    if mode == "parity":
        leaves = [
            np.asarray(x)
            for x in jax.tree_util.tree_leaves(jax.device_get(loop.state))
        ]
        h = hashlib.sha256()
        for leaf in leaves:
            h.update(leaf.tobytes())
        np.savez(
            os.path.join(workdir, f"params_{nprocs}_{pid}.npz"),
            *leaves,
        )
        print(f"CHILD_PARAMS sha={h.hexdigest()}", flush=True)
    elif mode == "resume":
        print(
            f"CHILD_RESUME pid={pid} start_it={loop._start_it} "
            f"epoch={loop.run_epoch}",
            flush=True,
        )
    print(
        f"CHILD_OK mode={mode} pid={pid} updates={out['updates']} "
        f"episodes={out['episodes']}",
        flush=True,
    )


if __name__ == "__main__":
    main()
