"""Quantized serving params + act-step kernel dispatch (the serving fast
path's precision layer).

Serving moves every actor param byte from HBM to the compute units once per
flushed batch, so serving bandwidth — not FLOPs — bounds small-batch acting
throughput. Training precision is none of this module's business: the
learner keeps float32 master params; :func:`quantize_tree` casts ONE copy at
``set_params`` time (``Config.inference_dtype``), and the jitted act step
dequantizes on the way into the matmuls:

- ``"f32"``  — identity; the A/B baseline (bit-for-bit PR 12 behavior).
- ``"bf16"`` — every float leaf cast to bfloat16 (half the bytes moved per
  step); the step casts back to f32, so all math runs at full precision on
  rounded weights.
- ``"int8"`` — per-tensor symmetric quantization of every >=2-D float leaf
  (the matmul weights; biases and other vectors stay f32): ``scale =
  max|w| / 127``, stored as a ``{"q8": int8, "scale": f32}`` subtree —
  the same per-tensor map shape as the llama int8 serving sharding maps
  (SNIPPETS.md [3]), so a sharding rule that matched the f32 leaf matches
  the quantized pair too.

The quantized tree is still one ordinary pytree: the PR 12 ver-keyed
replica swap stays a single atomic reference assignment, and GSPMD
``in_shardings`` replication applies leaf-wise exactly as before.

:func:`make_act_fn` is the other half of the fast path: it resolves
``Config.act_kernel`` to the act callable every serving consumer jits —
``"xla"`` is the generic ``family.act``, ``"pallas"`` the fused
torso→LSTM→head kernel (:mod:`tpu_rl.ops.pallas_act`) where the family
supports it (discrete LSTM actor-critic), falling back to XLA elsewhere.
"""

from __future__ import annotations

import re
from typing import Any

QUANT_MODES = ("f32", "bf16", "int8")

# Keys of an int8-quantized leaf subtree. A dict with exactly these keys IS
# a quantized tensor (treated as a leaf by dequantize/spec walks).
_Q8_KEYS = frozenset({"q8", "scale"})


def is_q8_leaf(node: Any) -> bool:
    return isinstance(node, dict) and frozenset(node.keys()) == _Q8_KEYS


def _is_float_leaf(leaf: Any) -> bool:
    dtype = getattr(leaf, "dtype", None)
    if dtype is None:
        return False
    import jax.numpy as jnp

    return jnp.issubdtype(dtype, jnp.floating)


def quantize_tree(tree: Any, mode: str) -> Any:
    """Cast a param pytree to the serving precision. Idempotent: leaves that
    already carry the target representation pass through, so a re-applied
    swap (learner update after the serve thread quantized the boot params)
    never double-scales."""
    assert mode in QUANT_MODES, mode
    if mode == "f32":
        return tree
    import jax
    import jax.numpy as jnp

    if mode == "bf16":

        def _cast(leaf):
            if _is_float_leaf(leaf):
                return jnp.asarray(leaf, jnp.bfloat16)
            return leaf

        return jax.tree_util.tree_map(_cast, tree)

    def _quant(leaf):
        if is_q8_leaf(leaf):
            return leaf
        if not _is_float_leaf(leaf) or getattr(leaf, "ndim", 0) < 2:
            # Biases / vectors / scalars: a few bytes each, and symmetric
            # int8 would cost real accuracy on them. They stay f32.
            return leaf
        w = jnp.asarray(leaf, jnp.float32)
        # Per-tensor symmetric scale; the max(|w|) floor keeps an all-zero
        # tensor (freshly initialized biases-as-matrices) from dividing by 0.
        scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-12) / 127.0
        q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
        return {"q8": q, "scale": scale.astype(jnp.float32)}

    return jax.tree_util.tree_map(_quant, tree, is_leaf=is_q8_leaf)


def dequantize_tree(tree: Any) -> Any:
    """Inverse cast, traced INSIDE the jitted act step: int8 leaves become
    ``q8 * scale``, bf16 leaves cast back to f32 — the compiled program
    reads the narrow bytes from HBM and widens in registers/VMEM."""
    import jax
    import jax.numpy as jnp

    def _dequant(leaf):
        if is_q8_leaf(leaf):
            return leaf["q8"].astype(jnp.float32) * leaf["scale"]
        if getattr(leaf, "dtype", None) == jnp.bfloat16:
            return leaf.astype(jnp.float32)
        return leaf

    return jax.tree_util.tree_map(_dequant, tree, is_leaf=is_q8_leaf)


def quant_spec(tree: Any) -> dict[str, tuple[str, tuple[int, ...]]]:
    """Per-tensor serving map ``{"actor.params.cell.x_proj.kernel":
    ("int8", (64, 256)), ...}`` — layer indices wildcarded to ``*`` like the
    llama serving sharding maps (SNIPPETS.md [3]), so stacked/repeated
    modules collapse to one row. Debug/observability only."""
    import jax

    out: dict[str, tuple[str, tuple[int, ...]]] = {}
    flat = jax.tree_util.tree_flatten_with_path(tree, is_leaf=is_q8_leaf)[0]
    for path, leaf in flat:
        name = ".".join(
            re.sub(r"^\d+$", "*", str(getattr(k, "key", getattr(k, "idx", k))))
            for k in path
        )
        if is_q8_leaf(leaf):
            out[name] = ("int8", tuple(leaf["q8"].shape))
        else:
            out[name] = (
                str(getattr(leaf, "dtype", type(leaf).__name__)),
                tuple(getattr(leaf, "shape", ())),
            )
    return out


def tree_bytes(tree: Any) -> int:
    """Total param bytes the act step moves per dispatch (metadata only — no
    device sync). The ``inference-param-bytes`` gauge."""
    import jax

    flat = jax.tree_util.tree_leaves(tree)
    return int(sum(getattr(leaf, "nbytes", 0) for leaf in flat))


# ------------------------------------------------------- act-step dispatch
def make_act_fn(cfg, family, n_devices: int = 1):
    """Resolve ``Config.act_kernel`` to the act callable serving consumers
    jit (``InferenceService._step_fn``, the worker's local act path).
    ``n_devices`` is the width of the program the caller will jit it into.

    ``"xla"`` -> ``family.act`` unchanged. ``"pallas"`` -> the fused
    torso→LSTM-cell→policy-head kernel where the family supports it;
    unsupported families (transformer, SAC, continuous), multi-device GSPMD
    programs and non-TPU backends without interpret mode fall back to
    ``family.act`` — the knob is a fast path, never a correctness gate."""
    if getattr(cfg, "act_kernel", "xla") != "pallas" or n_devices > 1:
        return family.act
    from tpu_rl.ops.pallas_act import make_fused_act

    fused = make_fused_act(family)
    return fused if fused is not None else family.act
