"""The casts with which the control computes the reference's pass in the
nearest precision below a configuration's (a family's ``block`` and
``head_logits`` apply ``quant`` to both operands of every projection)."""

from __future__ import annotations

import jax.numpy as jnp


def fp8_cast(x):
    """Round to float8 e4m3 under a per-tensor scale, as fp8 inference does
    (the control for a bfloat16 configuration)."""
    amax = jnp.maximum(jnp.abs(x).max(), 1e-30)
    scale = amax / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
