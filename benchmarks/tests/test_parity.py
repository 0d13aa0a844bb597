"""System against plain reference at tiny sizes, on the CPU in float32: the two
are then the same arithmetic and agree to rounding, so a wrong gate order,
mask, position or loss term in either shows as an O(1) error."""

import pytest

from benchmarks import parity

WINDOWS = {"pool": 8, "episode_len_mean": 4, "obs_scale": 1.0, "rew_scale": 0.1,
           "carry_scale": 0.3}
TINY = {
    "transformer": dict(algo="PPO", model="transformer", hidden_size=32, n_heads=2,
                        n_layers=2, seq_len=16, batch_size=8, obs_shape=[6],
                        action_space=3, attention_impl="flash"),
    "lstm": dict(algo="IMPALA", hidden_size=16, seq_len=5, batch_size=8,
                 obs_shape=[4], action_space=2),
}


def block(reference: str, tol: float) -> dict:
    return {"reference": reference, "rows": 4, "chunk_rows": 3, "windows": WINDOWS,
            "tol": {"logits": tol, "value": tol, "loss": tol}}


@pytest.mark.parametrize("family", sorted(TINY))
def test_system_matches_reference(family):
    verdict = parity.check(TINY[family], block(family, 2e-5), seed=5)
    assert verdict["ok"], verdict
    assert verdict["platform"] == "cpu"  # a rehearsal, never a device number


def test_data_parallel_system_matches_reference():
    params = dict(TINY["transformer"], mesh_data=4)
    assert parity.check(params, block("transformer", 2e-5), seed=6)["ok"]


@pytest.mark.parametrize(
    "family, key, value",
    [("transformer", "lmbda", 0.5), ("lstm", "rho_bar", 0.5), ("lstm", "gamma", 0.9)],
)
def test_a_different_loss_is_told_apart(family, key, value, monkeypatch):
    """Give the reference another hyper-parameter than the system: the loss
    must leave the tolerance the chip check uses, the forward must not."""
    from benchmarks.reference import losses

    algo = TINY[family]["algo"]
    real = losses.LOSSES[algo]
    monkeypatch.setitem(
        losses.LOSSES, algo, lambda lg, v, b, p: real(lg, v, b, {**p, key: value})
    )
    verdict = parity.check(TINY[family], block(family, 1e-2), seed=5)
    assert not verdict["ok"]
    assert verdict["err"]["loss"] > 1e-2 > verdict["err"]["logits"]
