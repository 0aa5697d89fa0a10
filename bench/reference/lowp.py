"""Rounding of operands to a lower precision, for the control runs.

``None`` keeps float32. ``"bfloat16"`` rounds without scaling. A one-byte
float (``"float8_e4m3fn"``) rounds as an fp8 training path does: each
tensor scaled so its largest magnitude meets the format's largest finite
value, forward operands in the named format and, on the way back, the
cotangent in e5m2 with its own scale (the usual split: range for
gradients, precision for activations and weights).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _scaled(x, dt):
    top = float(jnp.finfo(dt).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dt).astype(F32) * scale


@jax.custom_vjp
def _fp8(x):
    return _scaled(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _fp8(x), None


def _fp8_bwd(_, g):
    return (_scaled(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def rnd(x, dtype):
    if dtype is None:
        return x
    dt = jnp.dtype(dtype)
    if dt.itemsize == 1:
        return _fp8(x)
    return x.astype(dt).astype(F32)
