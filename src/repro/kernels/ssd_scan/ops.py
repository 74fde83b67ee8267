"""Jitted public wrapper for the SSD scan kernel: handles the model-layout
(b, s, h, p) <-> kernel-layout (b*h, s, p) rearrangement, group-to-head
broadcast of B/C, and the dt scaling, then dispatches to Pallas."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.ssd_scan.kernel import ssd_scan_kernel


@functools.partial(jax.jit, static_argnames=("chunk_size", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk_size=128, interpret=None):
    """Same contract as models.mamba2.ssd_chunked: x (b,s,h,p), dt (b,s,h),
    A (h,), B/C (b,s,g,n) -> (y (b,s,h,p) x.dtype, state (b,h,p,n) f32)."""
    if interpret is None:
        interpret = default_interpret()
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    chunk = min(chunk_size, s)
    assert s % chunk == 0, (s, chunk)

    xdt = (x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None])
    a = dt.astype(jnp.float32) * A[None, None, :]
    Bh = jnp.repeat(B, rep, axis=2).astype(jnp.float32)
    Ch = jnp.repeat(C, rep, axis=2).astype(jnp.float32)

    def to_bh(t):   # (b,s,h,...) -> (b*h, s, ...)
        return jnp.moveaxis(t, 2, 1).reshape((b * h, s) + t.shape[3:])

    y, state = ssd_scan_kernel(to_bh(xdt), to_bh(a[..., None])[..., 0],
                               to_bh(Bh), to_bh(Ch), chunk=chunk,
                               interpret=interpret)
    y = jnp.moveaxis(y.reshape(b, h, s, p), 1, 2).astype(x.dtype)
    return y, state.reshape(b, h, p, n)
