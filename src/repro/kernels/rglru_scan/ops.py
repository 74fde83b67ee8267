"""Jitted wrapper: computes the RG-LRU gate coefficients from raw inputs
and dispatches the linear recurrence to the Pallas kernel (interpret mode
off the TPU), padding ragged seq/channel dims to block multiples (a=1, b=0
padding is the identity element of the recurrence)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.rglru_scan.kernel import rglru_scan_kernel


@functools.partial(jax.jit, static_argnames=("block_s", "block_c",
                                             "interpret"))
def rglru_scan(a, b, *, block_s=128, block_c=128, interpret=None):
    """a, b: (B, S, C); returns (y (B,S,C) f32, h_final (B,C) f32)."""
    if interpret is None:
        interpret = default_interpret()
    B, S, C = a.shape
    bs = min(block_s, S)
    bc = min(block_c, C)
    pad_s = (-S) % bs
    pad_c = (-C) % bc
    if pad_s or pad_c:
        a = jnp.pad(a, ((0, 0), (0, pad_s), (0, pad_c)),
                    constant_values=1.0)           # identity decay
        b = jnp.pad(b, ((0, 0), (0, pad_s), (0, pad_c)))
    y = rglru_scan_kernel(a, b, block_s=bs, block_c=bc, interpret=interpret)
    y = y[:, :S, :C]
    return y, y[:, -1, :]
