"""Paged-attention decode Pallas TPU kernel: single-token attention *in
place over the KV block pool*.

The dense paged-decode path materializes every slot's whole page chain as a
(B, nb*bs, nkv, hd) gather before the attention einsum — three passes over
the chain's bytes (pool read, dense write, dense read) for one token of
FLOPs. This kernel instead streams KV page-by-page straight from the pool:
the BlockSpec ``index_map`` walks ``table[slot, j]`` (a scalar-prefetch
operand, so the block id is known before the page's DMA is issued) and the
online-softmax recurrence (flash-style m/l/acc VMEM scratch, exactly as in
``flash_attention/kernel.py``) folds each page into the running attention
state. Every chain byte is read once, no dense view is ever built.

Grid: (batch, n_kv_heads, n_pages) with pages innermost so the scratch
accumulators persist across a slot's chain. GQA grouping is by *KV* head —
each program holds the full ``rep = nh // nkv`` query-head group as rows of
one (rep, hd) tile, so a KV page is fetched once per group without
materializing the head repeat (the decode-shaped transpose of the
``h // rep`` index-map trick in flash_attention).

``@pl.when`` skips pages carrying no attendable tokens: pages past the
causal frontier (``j * bs > pos``) and pages mapped to the reserved null
block 0 (retired/empty slots' all-zero table rows; also every beyond-
frontier entry the engine zero-fills). A fully-skipped slot row finalizes
with l == 0 and emits zeros — the engine never reads those rows.

Sliding-window (ring) chains are not representable in a paged table; the
wrapper in ops.py guards window=None.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                       acc_ref, m_ref, l_ref, *, scale: float,
                       block_size: int, n_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(2)
    pos = pos_ref[b]

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Page-level skip: no attendable tokens past the causal frontier
    # (pos // bs), and block id 0 is the reserved null page (empty slots,
    # zero-filled table tails) — visited by the grid but never computed.
    needed = jnp.logical_and(j * block_size <= pos, tbl_ref[b, j] != 0)

    @pl.when(needed)
    def _compute():
        rep = q_ref.shape[2]
        q = q_ref[0, 0].astype(jnp.float32)                  # (rep, hd)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bs, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # a page's gather index IS its absolute position: token o of page j
        # sits at j*bs + o, so the causal mask needs no stored positions
        kv_pos = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, (rep, block_size), 1)
        mask = kv_pos <= pos
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[...]                                  # (rep, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + \
            jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(j == n_pages - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


def paged_attention_kernel(q, kpool, vpool, table, pos, *, scale=None,
                           interpret=False):
    """q: (B, nh, hd) one query token per slot; kpool/vpool: (P, nkv, bs,
    hd) block-pool pages; table: (B, nb) int32 block ids per slot; pos:
    (B,) int32 absolute position of the query token. Returns (B, nh, hd).
    """
    B, nh, hd = q.shape
    _, nkv, bs, _ = kpool.shape
    nb = table.shape[1]
    rep = nh // nkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    # (B, nh, hd) -> (B, nkv, rep, hd): one program owns a KV head's whole
    # query group, so each page is streamed once per group
    qr = q.reshape(B, nkv, rep, hd)

    kern = functools.partial(_paged_attn_kernel, scale=scale,
                             block_size=bs, n_pages=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # table, pos
        grid=(B, nkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1, rep, hd),
                         lambda b, h, j, tbl, pos: (b, h, 0, 0)),
            # the table walk: page j of slot b lives at pool row tbl[b, j];
            # the block's last two dims are one head's whole (bs, hd) page
            pl.BlockSpec((1, 1, bs, hd),
                         lambda b, h, j, tbl, pos: (tbl[b, j], h, 0, 0)),
            pl.BlockSpec((1, 1, bs, hd),
                         lambda b, h, j, tbl, pos: (tbl[b, j], h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, hd),
                               lambda b, h, j, tbl, pos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, hd), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
            pltpu.VMEM((rep, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, rep, hd), q.dtype),
        interpret=interpret,
        name="paged_attention_decode",
    )(table.astype(jnp.int32), pos.astype(jnp.int32), qr, kpool, vpool)
    return out.reshape(B, nh, hd)
