"""Pure-jnp oracle for paged-attention decode: the dense-gather path.

This is exactly the computation the Pallas kernel replaces — materialize
each slot's page chain as a dense (B, nb*bs, nkv, hd) view via ``jnp.take``
over the block table of the (P, nkv, bs, hd) pool, mask, softmax, weighted sum — stated as the kernel's
functional contract: positions beyond the query (causal), outside the
optional window, or belonging to pages mapped to the reserved null block 0
are masked out, and a fully-masked slot row (empty slot: all-zero table)
yields zeros, matching the kernel's skipped-page finalize.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def paged_attention_ref(q, kpool, vpool, table, pos, *, scale=None,
                        window=None):
    """q: (B, nh, hd); kpool/vpool: (P, nkv, bs, hd); table: (B, nb) int32
    block ids; pos: (B,) int32 query positions. Returns (B, nh, hd)."""
    B, nh, hd = q.shape
    _, nkv, bs, _ = kpool.shape
    nb = table.shape[1]
    rep = nh // nkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    k = jnp.take(kpool, table, axis=0).transpose(0, 1, 3, 2, 4) \
        .reshape(B, nb * bs, nkv, hd)
    v = jnp.take(vpool, table, axis=0).transpose(0, 1, 3, 2, 4) \
        .reshape(B, nb * bs, nkv, hd)
    kv_pos = jnp.arange(nb * bs)[None, :]
    valid = kv_pos <= pos[:, None]
    if window is not None:
        valid &= kv_pos > (pos[:, None] - window)
    valid &= jnp.repeat(table != 0, bs, axis=1)     # reserved null page
    qr = q.reshape(B, nkv, rep, hd)
    logits = jnp.einsum("bkrh,bskh->bkrs", qr.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = jnp.where(valid[:, None, None, :], logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)             # fully-masked rows -> 0
    out = jnp.einsum("bkrs,bskh->bkrh", w, v.astype(jnp.float32))
    return out.reshape(B, nh, hd).astype(q.dtype)
