"""The one generator of synthetic trajectory windows.

A traffic mix is a data file (``benchmarks/traffic/<name>.json``); this module
turns its ``windows`` block and a seed into host arrays shaped like what the
storage process writes into the shared-memory store: one ``(seq, width)``
float32 array per batch field. The same seed gives the same windows.

    "windows": {
      "pool": 64,                  distinct windows cycled through the store
      "episode_len_mean": 512,     geometric episode lengths -> is_fir seams
      "obs_scale": 1.0,            obs ~ N(0, obs_scale)
      "rew_scale": 0.1,
      "carry_scale": 0.1           pre-step LSTM carries ~ N(0, carry_scale)
    }

The behaviour policy is uniform: ``logits`` is its log-softmax, ``log_prob``
the log-probability of the drawn action, so importance ratios start near 1
and no operation of the loss can fail.
"""

from __future__ import annotations

import math

import numpy as np


def firsts(rng: np.random.Generator, seq: int, mean_len: float) -> np.ndarray:
    """Episode-first flags of one window cut from an endless stream of
    episodes with geometric lengths: each step starts a new episode with
    probability ``1 / mean_len``."""
    return (rng.random(seq) < 1.0 / mean_len).astype(np.float32)


def make_windows(widths: dict[str, int], seq: int, n_actions: int,
                 spec: dict, seed: int) -> list[dict[str, np.ndarray]]:
    """``spec["pool"]`` windows; ``widths`` maps every batch field to its
    feature width (the program's ``BatchLayout``)."""
    rng = np.random.default_rng(seed)
    f32 = np.float32
    out = []
    for _ in range(int(spec["pool"])):
        w = {
            "obs": rng.standard_normal((seq, widths["obs"])).astype(f32)
            * f32(spec.get("obs_scale", 1.0)),
            "act": rng.integers(0, n_actions, (seq, widths["act"])).astype(f32),
            "rew": rng.standard_normal((seq, widths["rew"])).astype(f32)
            * f32(spec.get("rew_scale", 0.1)),
            "logits": np.full((seq, widths["logits"]), -math.log(n_actions), f32),
            "log_prob": np.full((seq, widths["log_prob"]), -math.log(n_actions), f32),
            "is_fir": firsts(rng, seq, float(spec["episode_len_mean"]))[:, None],
        }
        for f in ("hx", "cx"):
            w[f] = rng.standard_normal((seq, widths[f])).astype(f32) * f32(
                spec.get("carry_scale", 0.1)
            )
        out.append(w)
    return out


def stack(windows: list[dict[str, np.ndarray]], rows: int) -> dict[str, np.ndarray]:
    """The first ``rows`` windows (cycled) as one ``(rows, seq, width)``
    batch — what ``OnPolicyStore.consume`` hands the learner."""
    picked = [windows[i % len(windows)] for i in range(rows)]
    return {f: np.stack([w[f] for w in picked]) for f in picked[0]}
