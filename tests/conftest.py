"""Test harness: run JAX on a virtual 8-device CPU mesh so all sharding /
collective logic is exercised without TPU hardware (SURVEY.md §4)."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # tests run on the virtual CPU mesh
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import signal  # noqa: E402
import threading  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from tpu_rl.config import Config  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timeout(seconds): fail the test if it exceeds the deadline "
        "(SIGALRM-based; pytest-timeout is not in this image)",
    )
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 run (-m 'not slow'); exercised by "
        "make ci's smoke targets or an explicit -m slow invocation",
    )


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Honor @pytest.mark.timeout without the pytest-timeout plugin: a hung
    cluster test must fail at its deadline, not hang the suite forever."""
    marker = item.get_closest_marker("timeout")
    seconds = int(marker.args[0]) if marker and marker.args else 0
    usable = (
        seconds > 0
        and hasattr(signal, "SIGALRM")
        and threading.current_thread() is threading.main_thread()
    )
    if not usable:
        return (yield)

    def _expired(signum, frame):
        raise TimeoutError(f"test exceeded timeout marker ({seconds}s)")

    old = signal.signal(signal.SIGALRM, _expired)
    signal.alarm(seconds)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, devs
    return devs


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def dot_operand_dtypes(closed_jaxpr) -> list[tuple[str, str]]:
    """Every ``dot_general``'s (lhs, rhs) operand dtypes across the WHOLE
    jaxpr tree, by structural traversal into sub-jaxprs (scan bodies,
    custom-VJP calls, cond branches). Used by the mixed-precision structure
    tests: text/regex parsing of ``str(jaxpr)`` is unsound — sub-jaxprs
    restart variable naming at ``a, b, c...``, so a flat name->dtype lookup
    is last-wins, and dots without a ``preferred_element_type`` marker are
    easy to miss."""
    out: list[tuple[str, str]] = []

    def walk_param(v):
        if hasattr(v, "jaxpr"):  # ClosedJaxpr
            walk(v.jaxpr)
        elif hasattr(v, "eqns"):  # Jaxpr
            walk(v)
        elif isinstance(v, (tuple, list)):
            for item in v:
                walk_param(item)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                a, b = eqn.invars[0].aval.dtype, eqn.invars[1].aval.dtype
                out.append((str(a), str(b)))
            for v in eqn.params.values():
                walk_param(v)

    walk(closed_jaxpr.jaxpr)
    return out


def small_config(**kw) -> Config:
    base = dict(
        hidden_size=16,
        seq_len=5,
        batch_size=8,
        buffer_size=32,
        obs_shape=(4,),
        action_space=2,
        time_horizon=32,
    )
    base.update(kw)
    return Config.from_dict(base)


@pytest.fixture
def cfg():
    return small_config()
