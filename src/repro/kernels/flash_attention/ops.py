"""Jitted public wrapper for the flash-attention kernel: pads ragged
sequence lengths up to block multiples, dispatches to the Pallas kernel
(compiled on a TPU, run in the Pallas interpreter elsewhere), and slices the
padding back off."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.flash_attention.kernel import flash_attention_kernel


def _pad_to(x, mult, axis):
    s = x.shape[axis]
    rem = (-s) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "block_q", "block_k",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q=128, block_k=128, interpret=None):
    """Public entry. q: (B, Sq, nh, hd); k, v: (B, Sk, nkv, hd)."""
    if interpret is None:
        interpret = default_interpret()
    Sq, Sk = q.shape[1], k.shape[1]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    qp = _pad_to(q, bq, 1)
    kp = _pad_to(k, bk, 1)
    vp = _pad_to(v, bk, 1)
    # padded keys must never be attended: they sit at positions >= Sk, and
    # with causal masking qpos < Sk keeps them invisible; for non-causal use
    # an explicit finite window over real keys only.
    out = flash_attention_kernel(qp, kp, vp, causal=causal, window=window,
                                 scale=scale, block_q=bq, block_k=bk,
                                 interpret=interpret)
    return out[:, :Sq]
