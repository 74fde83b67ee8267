"""Jitted public wrapper for paged-attention decode.

``kernel="pallas"`` dispatches to the Pallas kernel (compiled on a TPU;
elsewhere it runs in the Pallas interpreter, ``repro.kernels.
default_interpret``, so the same grid, DMAs and semaphores are exercised
everywhere);
``kernel="reference"`` runs the dense-gather oracle (ref.py), which is the
pre-kernel production path and the CPU fallback of record.

The paged layout is position-addressed (a page's gather index IS its
absolute position), so sliding-window ring semantics cannot be expressed
over a block table — window must be None on the pallas path; the reference
path accepts a window for completeness (layers guards it upstream).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import default_interpret
from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.kernels.paged_attention.ref import paged_attention_ref

KERNELS = ("pallas", "reference")


@functools.partial(jax.jit, static_argnames=("scale", "window", "kernel",
                                             "interpret"))
def _dispatch(q, kpool, vpool, table, pos, *, scale, window, kernel,
              interpret):
    if kernel == "pallas":
        return paged_attention_kernel(q, kpool, vpool, table, pos,
                                      scale=scale, interpret=interpret)
    return paged_attention_ref(q, kpool, vpool, table, pos, scale=scale,
                               window=window)


def paged_attention(q, kpool, vpool, table, pos, *, scale=None, window=None,
                    kernel="reference", interpret=None):
    """Public entry. q: (B, nh, hd) single query token per slot;
    kpool/vpool: (P, nkv, bs, hd); table: (B, nb); pos: (B,).
    Returns (B, nh, hd). The default matches the stack above it
    (engine/gateway/launcher): "reference" everywhere until a TPU is the
    target — interpret-mode pallas is for oracle tests, not speed."""
    if kernel not in KERNELS:
        raise ValueError(f"kernel must be one of {KERNELS}, got {kernel!r}")
    if kernel == "pallas" and window is not None:
        raise ValueError("paged-attention pallas kernel supports window="
                         "None only (paged chains are position-addressed, "
                         "not a ring); use kernel='reference' or the dense "
                         "layout for sliding-window decode")
    if interpret is None:
        interpret = default_interpret()
    return _dispatch(q, kpool, vpool, table, pos, scale=scale,
                     window=window, kernel=kernel, interpret=interpret)
