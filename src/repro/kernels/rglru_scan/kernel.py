"""RG-LRU scan Pallas TPU kernel (RecurrentGemma/Griffin, arXiv:2402.19427).

    h_t = a_t * h_{t-1} + b_t          (per channel; a_t, b_t precomputed
                                        by ops.py from the gates)

The GPU reference runs a per-channel sequential loop in a fused kernel; the
TPU adaptation tiles channels onto the VPU lanes: grid = (batch,
channel_blocks, seq_blocks) with the running state for one (1, block_c)
channel tile carried in VMEM scratch across the (innermost, sequential)
seq-block axis. Inside a tile the recurrence over block_s steps is a
`fori_loop` of fully vectorized (block_c,)-wide ops — sequential in time,
parallel across channels, which matches the VPU's 8x128 vector shape
(block_c a multiple of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _rglru_kernel(a_ref, b_ref, y_ref, h_ref, *, block_s: int):
    isb = pl.program_id(2)

    @pl.when(isb == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def step(t, h):
        # one (1, block_c) row of the tile per time step, read and written
        # through the refs at a dynamic sublane offset
        row = pl.ds(t, 1)
        h = a_ref[0, row, :].astype(jnp.float32) * h + \
            b_ref[0, row, :].astype(jnp.float32)
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s, step, h_ref[...])


def rglru_scan_kernel(a, b, *, block_s=128, block_c=128, interpret=False):
    """a, b: (B, S, C) -> y: (B, S, C) f32 with y_t = a_t y_{t-1} + b_t."""
    B, S, C = a.shape
    block_s = min(block_s, S)
    block_c = min(block_c, C)
    assert S % block_s == 0 and C % block_c == 0, (S, block_s, C, block_c)
    kern = functools.partial(_rglru_kernel, block_s=block_s)
    return pl.pallas_call(
        kern,
        grid=(B, C // block_c, S // block_s),
        in_specs=[
            pl.BlockSpec((1, block_s, block_c),
                         lambda ib, ic, isb: (ib, isb, ic)),
            pl.BlockSpec((1, block_s, block_c),
                         lambda ib, ic, isb: (ib, isb, ic)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_c),
                               lambda ib, ic, isb: (ib, isb, ic)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_c), jnp.float32)],
        interpret=interpret,
    )(a, b)
