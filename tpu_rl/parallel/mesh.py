"""Device mesh construction for the data-parallel learner.

The reference trains on exactly one GPU picked at process start
(``/root/reference/main.py:66-68``, ``utils/utils.py:106-117``) and has no
collective backend at all (no NCCL/torch.distributed — SURVEY.md §2.2). The
TPU-native design replaces that with a 1-D ``jax.sharding.Mesh`` over a
``"data"`` axis: batches are sharded along their leading dimension, parameters
are replicated, and XLA/GSPMD inserts the gradient all-reduce over ICI.

Nothing here requires TPU hardware — on CPU hosts a virtual multi-device mesh
is available via ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (set
before ``import jax``; see ``tests/conftest.py``).
"""

from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"


def make_mesh(
    n_data: int | None = None, devices: Sequence[jax.Device] | None = None
) -> Mesh:
    """1-D data-parallel mesh over the first ``n_data`` visible devices
    (all of them by default)."""
    devs = list(devices) if devices is not None else jax.devices()
    n = len(devs) if n_data is None else int(n_data)
    if n < 1:
        raise ValueError(f"mesh size must be >= 1, got {n}")
    if n > len(devs):
        raise ValueError(f"requested {n} devices, only {len(devs)} visible")
    return Mesh(np.asarray(devs[:n]), (DATA_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard along the leading (batch) dimension."""
    return NamedSharding(mesh, P(DATA_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def check_divisible(batch_size: int, mesh: Mesh) -> None:
    n = mesh.shape[DATA_AXIS]
    if batch_size % n != 0:
        raise ValueError(
            f"batch_size={batch_size} not divisible by mesh data axis ({n})"
        )
