"""Lower precisions for the control: a tensor passed through an 8-bit type
and back, one scale along the given axes. The plain references compute in
float32; put through one of these they stand for the precision below the
bfloat16 that the configurations state."""
from __future__ import annotations

import jax.numpy as jnp


def _scaled(x, axes, top):
    scale = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / top
    return jnp.where(scale == 0, 1.0, scale)


def through_int8(x, axes):
    scale = _scaled(x, axes, 127.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def through_fp8(x, axes):
    scale = _scaled(x, axes, 448.0)  # the largest float8_e4m3fn
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


QUANT = {"none": None, "int8": through_int8, "fp8": through_fp8}
