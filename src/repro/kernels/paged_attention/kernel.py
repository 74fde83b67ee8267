"""Paged-attention decode Pallas TPU kernel: single-token attention *in
place over the KV block pool*.

The dense paged-decode path materializes every slot's whole page chain as a
(B, nb*bs, nkv, hd) gather before the attention einsum — three passes over
the chain's bytes (pool read, dense write, dense read) for one token of
FLOPs. This kernel instead copies each slot's live pages straight from the
pool and folds them into an online-softmax state (flash-style m/l/acc VMEM
scratch, as in ``flash_attention/kernel.py``). Every live chain byte is
read once, no dense view is ever built.

Grid: (batch, n_blocks). One step covers every KV head of a *block* of
``ppb`` consecutive table pages of one slot (``pages_per_block``: as many
pages as fit a fixed VMEM budget), so a call costs B * ceil(nb / ppb) grid
steps, not one per head and page. The pool layout (P, nkv, bs, hd) keeps a
page's heads contiguous, so one DMA per page moves all of its K (and one
its V).

K and V stay in HBM (``memory_space=ANY``). Each live page of a block is
copied by hand into a double-buffered (2, ppb, nkv, bs, hd) VMEM scratch;
a step starts the copies of the *next* live block — the next slot's first
block included, so the pipeline does not drain at slot boundaries — before
it waits for and computes its own. A page carries attendable tokens only
inside the causal frontier (``j * bs <= pos``) and off the reserved null
block 0 (empty/retired slots' all-zero table rows, zero-filled table
tails): other pages get no copy, and a block wholly past the frontier no
copy and no compute. Their buffer rows hold stale bytes, so the token
mask applies to V as well as to the scores. A slot with no live page
finalizes with l == 0 and emits zeros — the engine never reads those rows.

GQA grouping is by *KV* head: the (nkv, rep, hd) query tile holds each KV
head's ``rep = nh // nkv`` query heads, and scores, softmax and output are
computed for all heads at once, batched over heads.

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

# VMEM the double-buffered K/V page scratch may take: 2 buffers x (K + V)
# x ppb pages x (nkv, bs, hd). 2 MiB gives 16 pages per block at the
# served geometries (8 heads of 16 x 128 bf16), far under v5e's scoped
# VMEM, and leaves room for the f32 copies of a block the compute makes.
KV_BUFFER_BYTES = 2 << 20


def pages_per_block(nb: int, bs: int, nkv: int, hd: int,
                    itemsize: int) -> int:
    """Pages per grid step: the most whose double-buffered K and V stay
    within KV_BUFFER_BYTES, at least one and at most the table's nb."""
    page = 2 * 2 * nkv * bs * hd * itemsize
    return max(1, min(nb, KV_BUFFER_BYTES // page))


def _paged_attn_kernel(tbl_ref, pos_ref, q_ref, k_hbm, v_hbm, o_ref,
                       kbuf, vbuf, sems, slot_ref, acc_ref, m_ref, l_ref, *,
                       scale: float, block_size: int, n_pages: int,
                       ppb: int):
    b, i = pl.program_id(0), pl.program_id(1)
    n_slots, n_blocks = pl.num_programs(0), pl.num_programs(1)
    bs, nb = block_size, n_pages
    span = ppb * bs                          # token positions per block

    def page_at(s, j):
        return tbl_ref[s * nb + jnp.minimum(j, nb - 1)]

    def page_live(s, j):
        """Page j of slot s holds attendable tokens: inside the table (the
        last block may be partial), inside the causal frontier, and not
        the null block."""
        return (j < nb) & (j * bs <= pos_ref[s]) & (page_at(s, j) != 0)

    def copies(s, blk, buf):
        """(live, K copy, V copy) per page of block blk of slot s."""
        out = []
        for p in range(ppb):
            j = blk * ppb + p
            page = page_at(s, j)
            out.append((page_live(s, j),
                        pltpu.make_async_copy(k_hbm.at[page], kbuf.at[buf, p],
                                              sems.at[0, buf]),
                        pltpu.make_async_copy(v_hbm.at[page], vbuf.at[buf, p],
                                              sems.at[1, buf])))
        return out

    def start(s, blk, buf):
        for live, ck, cv in copies(s, blk, buf):
            @pl.when(live)
            def _():
                ck.start()
                cv.start()

    def wait(s, blk, buf):
        for live, ck, cv in copies(s, blk, buf):
            @pl.when(live)
            def _():
                ck.wait()
                cv.wait()

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # the first step of the call fetches its own block; every later live
    # block was started by the live step before it
    @pl.when((b == 0) & (i == 0))
    def _first():
        slot_ref[0] = 0
        start(b, i, 0)

    pos = pos_ref[b]

    # block 0 always runs, so the chain of prefetches reaches every slot
    @pl.when((i == 0) | (i * span <= pos))
    def _live_block():
        cur = slot_ref[0]
        # the next block to run: this slot's next one inside the
        # frontier, else the next slot's first
        more = (i + 1 < n_blocks) & ((i + 1) * span <= pos)
        nxt_b = jnp.where(more, b, b + 1)
        nxt_i = jnp.where(more, i + 1, 0)

        @pl.when(nxt_b < n_slots)
        def _prefetch():
            start(nxt_b, nxt_i, 1 - cur)
            slot_ref[0] = 1 - cur

        wait(b, i, cur)
        live = [page_live(b, i * ppb + p) for p in range(ppb)]

        @pl.when(functools.reduce(jnp.logical_or, live))
        def _compute():
            q = q_ref[...]                              # (nkv, rep, hd)
            nkv, rep, hd = q.shape
            # (ppb, nkv, bs, hd) -> (nkv, ppb * bs, hd): token t of the
            # block sits at absolute position i * span + t (a page's gather
            # index IS its position)
            k = jnp.swapaxes(kbuf[cur], 0, 1).reshape(nkv, span, hd)
            v = jnp.swapaxes(vbuf[cur], 0, 1).reshape(nkv, span, hd)

            def token_mask(shape, axis):
                """Tokens inside the causal frontier and in a live page."""
                t = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
                ok = i * span + t <= pos
                for p in range(ppb):
                    ok &= live[p] | (t < p * bs) | (t >= (p + 1) * bs)
                return ok[None]

            cd = jnp.promote_types(q.dtype, k.dtype)
            s = jnp.einsum("hrd,htd->hrt", q.astype(cd), k.astype(cd),
                           preferred_element_type=jnp.float32) * scale
            mask = token_mask((rep, span), 1)
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[...]                         # (nkv, rep, 1)
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            w = jnp.where(mask, jnp.exp(s - m_cur), 0.0)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(w, axis=2,
                                                      keepdims=True)
            # a dead page's buffer rows hold stale bytes, NaN even, which
            # a weight of 0 would not cancel: zero them
            v = jnp.where(token_mask((span, hd), 0), v.astype(jnp.float32),
                          0.0)
            acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
                "hrt,htd->hrd", w, v, preferred_element_type=jnp.float32)
            m_ref[...] = m_cur

    @pl.when(i == n_blocks - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l > 0, l, 1.0)
        o_ref[...] = (acc_ref[...] / safe).astype(o_ref.dtype)


def paged_attention_kernel(q, kpool, vpool, table, pos, *, scale=None,
                           interpret=False):
    """q: (B, nh, hd) one query token per slot; kpool/vpool: (P, nkv, bs,
    hd) block-pool pages; table: (B, nb) int32 block ids per slot; pos:
    (B,) int32 absolute position of the query token. Returns (B, nh, hd).
    ``interpret=True`` runs the body in the Pallas interpreter, DMAs and
    semaphores included; the kernel's tests run it in the slower, more
    faithful TPU interpreter (``pltpu.force_tpu_interpret_mode``).
    """
    B, nh, hd = q.shape
    _, nkv, bs, _ = kpool.shape
    nb = table.shape[1]
    rep = nh // nkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    ppb = pages_per_block(nb, bs, nkv, hd, kpool.dtype.itemsize)
    n_blocks = pl.cdiv(nb, ppb)

    # (B, nh, hd) -> (B, nkv, rep, hd): a KV head's query group is one
    # (rep, hd) tile, so each page is fetched once for the whole group
    qr = q.reshape(B, nkv, rep, hd)
    row = pl.BlockSpec((None, nkv, rep, hd),
                       lambda b, i, tbl, pos: (b, 0, 0, 0))
    kern = functools.partial(_paged_attn_kernel, scale=scale,
                             block_size=bs, n_pages=nb, ppb=ppb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,              # table (flattened), pos
        grid=(B, n_blocks),
        in_specs=[row, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, nkv, bs, hd), kpool.dtype),
            pltpu.VMEM((2, ppb, nkv, bs, hd), vpool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),    # (K, V) x buffer
            pltpu.SMEM((1,), jnp.int32),        # buffer of the live block
            pltpu.VMEM((nkv, rep, hd), jnp.float32),
            pltpu.VMEM((nkv, rep, 1), jnp.float32),
            pltpu.VMEM((nkv, rep, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nkv, rep, hd), q.dtype),
        # a step prefetches for the next slot: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="paged_attention_decode",
    )(table.astype(jnp.int32).reshape(-1), pos.astype(jnp.int32), qr,
      kpool, vpool)
    return out.reshape(B, nh, hd)
