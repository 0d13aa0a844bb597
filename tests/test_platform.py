"""Bring-up contract (``tpu_rl.utils.platform``, the supervisor and the CLI):
no fallback hides the device. An accelerator-owning role on a CPU backend
nobody asked for raises; the CLI exits nonzero when a child failed; the
compile cache lives at ``$JAX_COMPILATION_CACHE_DIR`` or at one fixed
in-checkout path; one process owns a chip."""

import json
import os
import subprocess
import sys

import pytest

from tpu_rl.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_FORCE_CHILD = """
import os
os.environ.pop("JAX_PLATFORMS", None)
os.environ["XLA_FLAGS"] = ""  # drop conftest's forced device count
import jax
jax.config.update("jax_platforms", "cpu")  # stay off a real chip in CI
assert len(jax.devices()) == 1  # a backend is live, with the wrong count
from tpu_rl.utils.platform import force_cpu
force_cpu(8)
devs = jax.devices()
assert len(devs) == 8 and all(d.platform == "cpu" for d in devs), devs
import jax.numpy as jnp
assert float(jnp.ones(8).sum()) == 8.0  # the new backend actually computes
force_cpu(8)  # re-forcing is idempotent, not a raise
assert len(jax.devices()) == 8, jax.devices()
print("FORCED_OK")
"""


def test_force_cpu_wins_after_backend_init():
    """``__graft_entry__.dryrun_multichip`` runs in a process that may
    already hold a backend: ``force_cpu(n)`` must re-size it."""
    r = subprocess.run(
        [sys.executable, "-c", _FORCE_CHILD],
        capture_output=True, text=True, timeout=300, cwd=REPO,
    )
    assert r.returncode == 0, r.stderr
    assert "FORCED_OK" in r.stdout


def test_accelerator_role_refuses_implicit_cpu(monkeypatch, tmp_path, capsys):
    """learner_device="auto" means the accelerator: JAX's silent CPU
    fallback raises, while a CPU that was asked for (learner_device="cpu",
    or JAX_PLATFORMS=cpu) runs and records its backend."""
    import jax

    from tpu_rl.utils import platform

    cfg = Config(result_dir=str(tmp_path))
    # The backend is the CPU (conftest); pretend nobody asked for it.
    monkeypatch.setattr(platform, "cpu_requested", lambda: False)
    with pytest.raises(RuntimeError, match="no accelerator"):
        platform.BackendRecord("learner", cfg)
    platform.require_accelerator("learner", cpu_ok=True)  # explicit: runs

    rec = platform.BackendRecord(
        "learner", cfg.replace(learner_device="cpu")
    )
    rec.close()
    line = capsys.readouterr().out
    assert "[learner] backend cpu" in line and "devices 8" in line
    with open(tmp_path / "backend-learner.json") as f:
        doc = json.load(f)
    assert doc["platform"] == "cpu" and doc["device_count"] == 8
    assert doc["device_kind"] == jax.devices()[0].device_kind
    assert {"compile_s", "cache_hits", "cache_misses"} <= set(doc)


def test_explicit_cpu_env_is_a_request():
    """JAX_PLATFORMS=cpu in the environment (tests, smokes, make ci, every
    cpu_only supervisor child) is an explicit request."""
    from tpu_rl.utils import platform

    assert platform.cpu_requested()  # conftest pinned this process
    platform.require_accelerator("learner", cpu_ok=False)  # does not raise


def _fake_supervisor(monkeypatch):
    """A Supervisor whose children never start (spawn bookkeeping only)."""
    from tpu_rl.runtime.runner import Supervisor

    sup = Supervisor()
    monkeypatch.setattr(Supervisor, "_start", lambda self, child: None)
    return sup


def test_one_accelerator_owner_per_supervisor(monkeypatch):
    """One process per chip: a second accelerator-owning child (PBT members
    with learner_device="auto") is refused with a message naming the owner;
    CPU children are unlimited. Restarts no longer re-target the child."""
    sup = _fake_supervisor(monkeypatch)
    sup.spawn("member-0", print, cpu_only=False)
    sup.spawn("worker-0", print)
    sup.spawn("inference-1", print, cpu_only=True)
    with pytest.raises(RuntimeError, match="member-0.*one process per chip"):
        sup.spawn("member-1", print, cpu_only=False)
    assert [c.name for c in sup.children] == [
        "member-0", "worker-0", "inference-1",
    ]
    import functools

    for c in sup.children:  # no probe/degrade plumbing on the target
        assert isinstance(c.target, functools.partial)
        assert not c.target.keywords


def test_fleet_replica_children_are_cpu_pinned(monkeypatch):
    """Inference replicas 1..N-1 sit beside the learner, which owns the chip
    (and serves replica 0 on it): they are CPU children whatever
    learner_device says, and the warm-start restore no longer runs in the
    supervising parent."""
    from tpu_rl.config import MachinesConfig
    from tpu_rl.runtime import runner

    sup = _fake_supervisor(monkeypatch)
    monkeypatch.delenv("JAX_PLATFORMS")  # a machine whose learner gets the chip
    cfg = Config(act_mode="remote", inference_replicas=3, model_dir="/nonexistent")
    monkeypatch.setattr(
        "tpu_rl.checkpoint.restore_actor_params",
        lambda *a: pytest.fail("parent restored actor params"),
    )
    runner.learner_role(cfg, MachinesConfig(), supervisor=sup)
    runner.worker_role(cfg, MachinesConfig(), supervisor=sup)
    pinned = {c.name: c.cpu_only for c in sup.children}
    assert pinned["learner"] is False
    assert pinned["inference-1"] and pinned["inference-2"]
    assert pinned["storage"] and pinned["worker-0-0"]


def test_population_refuses_to_share_one_accelerator(monkeypatch, tmp_path):
    """K PBT members are K chip owners: refused up front with a clear
    message unless they run on the CPU on purpose."""
    from tpu_rl.population import PopulationController

    cfg = Config(
        env_mode="colocated", result_dir=str(tmp_path),
        pop_spec="lr:log[1e-4,1e-3] interval=50u k=2",
    )
    PopulationController(cfg, log=False)  # JAX_PLATFORMS=cpu: members on CPU
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(ValueError, match="share one accelerator"):
        PopulationController(cfg, log=False)
    PopulationController(cfg.replace(learner_device="cpu"), log=False)


def test_cli_exits_nonzero_after_an_exhausted_child(monkeypatch):
    """``python -m tpu_rl`` used to return 0 unconditionally after
    ``sup.loop()``, including when a child had exhausted its restart budget
    — e.g. a learner that found no accelerator."""
    from tpu_rl import __main__ as cli
    from tpu_rl.runtime import runner

    class _Proc:
        def __init__(self, exitcode):
            self.exitcode = exitcode

    def fake_cluster(exhausted, exitcode):
        def build(cfg, machines, **kw):
            sup = runner.Supervisor()
            sup.children.append(runner.Child(
                name="learner", target=None, args=(), proc=_Proc(exitcode),
                heartbeat=None, cpu_only=False, exhausted=exhausted,
            ))
            sup.loop = lambda: None
            sup.stop = lambda: None
            return sup
        return build

    argv = ["local", "--no-result-dir"]
    monkeypatch.setattr(runner, "local_cluster", fake_cluster(True, 1))
    assert cli.main(argv) == 1
    monkeypatch.setattr(runner, "local_cluster", fake_cluster(False, 3))
    assert cli.main(argv) == 1  # ended by itself with a nonzero code
    monkeypatch.setattr(runner, "local_cluster", fake_cluster(False, 0))
    assert cli.main(argv) == 0
    monkeypatch.setattr(runner, "local_cluster", fake_cluster(False, -15))
    assert cli.main(argv) == 0  # our own terminate() at stop


_CACHE_CHILD = """
import os, sys
import jax
from tpu_rl.utils.platform import enable_compile_cache
print(os.getpid(), enable_compile_cache(), jax.config.jax_compilation_cache_dir,
      jax.config.jax_persistent_cache_min_compile_time_secs)
"""


def _cache_probe(cwd, **env):
    base = {k: v for k, v in os.environ.items()
            if k not in ("JAX_PLATFORMS", "JAX_COMPILATION_CACHE_DIR")}
    r = subprocess.run(
        [sys.executable, "-c", _CACHE_CHILD], capture_output=True, text=True,
        timeout=120, cwd=cwd, env={**base, "PYTHONPATH": REPO, **env},
    )
    assert r.returncode == 0, r.stderr
    return r.stdout.split()


def test_compile_cache_placement(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code. Otherwise one
    fixed in-checkout directory, the same from any cwd and pid; CPU-pinned
    processes are left alone."""
    pid_a, ret_a, cfg_a, min_a = _cache_probe(REPO)
    pid_b, ret_b, cfg_b, _ = _cache_probe(str(tmp_path))
    assert pid_a != pid_b
    assert ret_a == ret_b == cfg_a == cfg_b == os.path.join(REPO, ".jax_cache")
    assert float(min_a) == 0.0  # sub-second @ref programs are cached too

    outside = str(tmp_path / "outside")
    _, ret, cfg, min_s = _cache_probe(REPO, JAX_COMPILATION_CACHE_DIR=outside)
    assert ret == cfg == outside  # JAX read the env var itself...
    assert float(min_s) == 1.0  # ...and the helper touched no config

    _, ret, cfg, _ = _cache_probe(REPO, JAX_PLATFORMS="cpu")
    assert ret == cfg == "None"


@pytest.mark.parametrize("mode, width, found", [
    ("interpret", 128, True), ("off", 128, False), ("auto", 128, False), ("interpret", 96, False),
], ids=["kernel", "off", "a-cpu", "a-width-the-kernel-refuses"])
def test_a_lowered_walk_names_the_row_add_kernel_where_the_gate_took_it(monkeypatch, mode, width, found):
    """``moe_row_add_pallas`` is in a lowered module's paths exactly when
    ``ops/moe.py``'s gate handed the walk's row-add to the Pallas kernel."""
    import jax
    import jax.numpy as jnp

    from tpu_rl.models import cells
    from tpu_rl.ops import moe
    from tpu_rl.utils.platform import program_paths

    monkeypatch.setattr(cells, "_PALLAS_MODE", mode)
    n, k, held, f = 64, 2, 2, 64
    choice = jnp.arange(n * k, dtype=jnp.int32).reshape(n, k) % 4

    def loss(u, weight, w_in, w_out):
        return jnp.sum(moe.routed_experts(u, choice, weight, w_in, w_out, 0, chunk=256))

    lowered = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
        jnp.zeros((n, width)), jnp.ones((n, k)), jnp.zeros((held, width, f)),
        jnp.zeros((held, f, width)))
    assert ("moe_row_add_pallas" in program_paths(lowered)["paths"]) is found


def test_a_file_named_after_a_scope_is_no_path():
    """``program_paths`` reads name stacks: ``ops/kda.py`` among a module's
    file locations (a cached trace of a jnp helper keeps the call site it was
    first traced from, in whatever program lowers it next) is not the scope
    ``kda``."""
    import types

    from tpu_rl.utils.platform import program_paths

    text = '\n'.join([
        '%0 = stablehlo.add %a, %b : tensor<f32> loc(#loc3)',
        '#loc1 = loc("/root/repo/tpu_rl/ops/kda.py":136:15 to :50)',
        '#loc2 = loc("/root/repo/tpu_rl/ops/pallas_kda.py":552:11 to 553:44)',
        '#loc3 = loc("jit(step)/lstm_pallas/add"(#loc1))',
    ])
    module = types.SimpleNamespace(as_text=lambda debug_info=False: text)
    assert program_paths(module) == {"paths": ["lstm_pallas"], "mosaic_calls": 0}
    scoped = text.replace("lstm_pallas", "kda/kda_scan/kda_pallas")
    module = types.SimpleNamespace(as_text=lambda debug_info=False: scoped)
    assert program_paths(module)["paths"] == ["kda", "kda_pallas", "kda_scan"]
