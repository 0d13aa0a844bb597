"""Plain float32 forward of the reference's MLP + LSTM actor-critic.

Written from the model's description (the upstream project's ``MlpLSTMBase``:
one ReLU layer, one LSTM cell with torch gate order i, f, g, o, a logits head
and a value head on the shared hidden state), not from ``tpu_rl/models``: a
Python loop over the steps, no kernel, no scan, no flax. It reads only the
parameter tree. Callers wrap it in ``jax.default_matmul_precision("highest")``.

    x_t  = relu(obs_t W_b + b_b)
    h, c = 0 where is_fir_t else h, c        (reset_carry_on_first, the default)
    z    = x_t W_x + b_x + h W_h
    c    = sigmoid(z_f) c + sigmoid(z_i) tanh(z_g);  h = sigmoid(z_o) tanh(c)
    logits_t = log_softmax(h W_pi + b_pi);  value_t = h W_v + b_v

The window starts from the carry the actor stored before its first step
(``hx[:, 0]``, ``cx[:, 0]``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def forward(actor_params, batch: dict, params: dict):
    """``batch``: field -> (B, S, width) float32. Returns log-softmax logits
    (B, S, A) and value (B, S, 1)."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), actor_params["params"])
    H = int(params["hidden_size"])
    reset = bool(params.get("reset_carry_on_first", True))
    h, c = batch["hx"][:, 0], batch["cx"][:, 0]
    hs = []
    for t in range(batch["obs"].shape[1]):
        x = jax.nn.relu(batch["obs"][:, t] @ p["body"]["kernel"] + p["body"]["bias"])
        if reset:
            keep = 1.0 - batch["is_fir"][:, t]
            h, c = h * keep, c * keep
        z = (
            x @ p["cell"]["x_proj"]["kernel"]
            + p["cell"]["x_proj"]["bias"]
            + h @ p["cell"]["recurrent_kernel"]
        )
        i, f, g, o = (z[:, k * H : (k + 1) * H] for k in range(4))
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        hs.append(h)
    hs = jnp.stack(hs, axis=1)
    logits = jax.nn.log_softmax(hs @ p["logits"]["kernel"] + p["logits"]["bias"])
    return logits, hs @ p["value"]["kernel"] + p["value"]["bias"]
