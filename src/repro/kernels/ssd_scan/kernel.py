"""Mamba2 SSD chunked-scan Pallas TPU kernel [arXiv:2405.21060].

The CUDA SSD kernel tiles over (chunk, head) thread-blocks with the running
state in shared memory; the TPU adaptation makes the chunk axis the
innermost (sequential) grid dimension so the running state lives in a VMEM
scratch accumulator across chunk iterations, and expresses both the
intra-chunk quadratic term and the state update as (chunk x N) @ (N x P)
matmuls for the MXU. Grid = (batch*heads, n_chunks).

Inputs are pre-arranged head-major: xdt (BH, S, P) [x already scaled by
dt], a (BH, S) [log decay dt*A], B, C (BH, S, N) [group-broadcast]. The
wrapper turns ``a`` into its within-chunk cumulative sum, blocked as
(chunk, 1) and (1, chunk) tiles.
Outputs: y (BH, S, P) and the final state (BH, P, N).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _ssd_kernel(xdt_ref, acs_col_ref, acs_row_ref, b_ref, c_ref, y_ref,
                state_out_ref, state_ref, *, chunk: int, n_chunks: int):
    ic = pl.program_id(1)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    xdt = xdt_ref[0].astype(jnp.float32)            # (q, P)
    acs = acs_col_ref[0]                            # (q, 1) inclusive
    acs_row = acs_row_ref[0]                        # (1, q) same values
    B = b_ref[0].astype(jnp.float32)                # (q, N)
    C = c_ref[0].astype(jnp.float32)                # (q, N)

    # intra-chunk: scores[i,j] = C_i.B_j * exp(acs_i - acs_j), i >= j
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(tri, jnp.exp(acs - acs_row), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # carried-state contribution: (C * exp(acs)) @ state^T : (q,N)@(N,P)
    state = state_ref[...]                          # (P, N)
    y += jax.lax.dot_general(C * jnp.exp(acs), state,
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # state update: S' = exp(acs_last)*S + sum_j exp(acs_last-acs_j) xdt_j B_j^T
    # acs[-1] as a masked lane sum: the TPU compiler refuses to broadcast
    # a (1, 1) slice taken at lane offset chunk - 1
    last = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) == chunk - 1
    acs_last = jnp.sum(jnp.where(last, acs_row, 0.0), axis=1,
                       keepdims=True)               # (1, 1)
    upd = jax.lax.dot_general(xdt * jnp.exp(acs_last - acs), B,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (P, N)
    state_ref[...] = jnp.exp(acs_last) * state + upd

    @pl.when(ic == n_chunks - 1)
    def _emit_state():
        state_out_ref[0] = state_ref[...]


def ssd_scan_kernel(xdt, a, B, C, *, chunk: int, interpret=False):
    """xdt: (BH, S, P); a: (BH, S); B, C: (BH, S, N).
    Returns (y (BH, S, P) f32, state (BH, P, N) f32)."""
    BH, S, P = xdt.shape
    N = B.shape[-1]
    assert S % chunk == 0, (S, chunk)
    n_chunks = S // chunk
    # the within-chunk cumulative log decay, once as a column and once as a
    # row, so the kernel forms exp(acs_i - acs_j) by broadcasting alone
    acs = jnp.cumsum(a.astype(jnp.float32).reshape(BH, n_chunks, chunk),
                     axis=-1).reshape(BH, S)
    kern = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=n_chunks)
    y, state = pl.pallas_call(
        kern,
        grid=(BH, n_chunks),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, 1), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ic: (bh, 0, ic)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ic: (bh, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ic: (bh, ic, 0)),
            pl.BlockSpec((1, P, N), lambda bh, ic: (bh, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xdt, acs[:, :, None], acs[:, None, :], B, C)
    return y, state
