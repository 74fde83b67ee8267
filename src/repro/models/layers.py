"""Core composable layers: norms, RoPE, GQA attention (train/prefill/decode),
gated MLPs. Pure functions over parameter pytrees (dicts of jnp arrays) —
no framework dependency, so the sweep engine can stack/vmap params freely.

Attention supports:
  * grouped-query (n_kv_heads <= n_heads)
  * optional per-head RMS qk-norm (qwen3)
  * causal, sliding-window and cross (non-causal) masking
  * decode against a (possibly ring-buffered) KV cache
  * impl = "xla" (einsum; what the dry-run lowers) or "pallas"
    (kernels/flash_attention; interpret mode off the TPU)
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

# ----------------------------------------------------------------------------- init

def uniform_init(key, shape, scale, dtype):
    return jax.random.uniform(key, shape, dtype, -scale, scale)


def dense_init(key, d_in, d_out, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    return uniform_init(key, (d_in, d_out), scale, dtype)


# ----------------------------------------------------------------------------- norms

def rms_norm(x, weight, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + weight.astype(jnp.float32))).astype(dt)


def init_rms_norm(d, dtype):
    return {"scale": jnp.zeros((d,), dtype)}  # stored as (1 + scale)


def apply_rms_norm(params, x, eps=1e-6):
    return rms_norm(x, params["scale"], eps)


# ----------------------------------------------------------------------------- acts

ACTIVATIONS = {
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "tanh": jnp.tanh,
    "sigmoid": jax.nn.sigmoid,
    "identity": lambda x: x,
}


# ----------------------------------------------------------------------------- rope

def rope_frequencies(head_dim: int, theta: float, dtype=jnp.float32):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta: float):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    inv = rope_frequencies(hd, theta)
    ang = positions[..., None].astype(jnp.float32) * inv          # (..., S, hd/2)
    ang = ang[..., None, :]                                       # (..., S, 1, hd/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------- attention

def init_attention(key, cfg):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    nh, nkv = cfg.n_heads, cfg.n_kv_heads
    ks = jax.random.split(key, 4)
    pdt = cfg.parameter_dtype
    p = {
        "wq": dense_init(ks[0], d, nh * hd, pdt),
        "wk": dense_init(ks[1], d, nkv * hd, pdt),
        "wv": dense_init(ks[2], d, nkv * hd, pdt),
        "wo": dense_init(ks[3], nh * hd, d, pdt),
    }
    if cfg.qk_norm:
        p["q_norm"] = init_rms_norm(hd, pdt)
        p["k_norm"] = init_rms_norm(hd, pdt)
    return p


def _attn_mask(q_pos, k_pos, causal: bool, window: Optional[int]):
    """Boolean mask (..., Sq, Sk): True = attend."""
    m = jnp.ones(q_pos.shape[:-1] + (q_pos.shape[-1], k_pos.shape[-1]), bool)
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= kp > qp - window
    return m


def _sdpa_xla(q, k, v, mask, scale):
    """q:(B,Sq,nh,hd) k,v:(B,Sk,nkv,hd). GQA by reshaping q to (nkv, rep).

    Inputs stay in their storage dtype (bf16 on TPU) with f32 MXU
    accumulation (preferred_element_type) — casting inputs up to f32 doubled
    every backward-pass collective payload (§Perf iteration 2). Softmax is
    computed in f32; probabilities are cast back before the PV matmul.
    """
    B, Sq, nh, hd = q.shape
    nkv = k.shape[2]
    rep = nh // nkv
    qr = q.reshape(B, Sq, nkv, rep, hd)
    logits = jnp.einsum("bqkrh,bskh->bkrqs", qr, k,
                        preferred_element_type=jnp.float32) * scale
    neg = jnp.finfo(jnp.float32).min
    logits = jnp.where(mask[:, None, None, :, :], logits, neg)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrqs,bskh->bqkrh", w.astype(q.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, Sq, nh, hd).astype(q.dtype)


def attention(params, cfg, x, positions, *, kv=None, kv_positions=None,
              causal=True, window=None, rope=True, constrain_kv=False):
    """Full-sequence attention (train / prefill / encoder / cross).

    x: (B, S, d). kv: optional (B, Sk, d) source for cross-attention.
    Returns (out, (k, v)) so prefill can populate a cache. With
    ``constrain_kv`` the emitted k/v are constrained to the prefill-cache
    layout (head_dim over "model") so the cache write needs no reshard
    (§Perf iteration 1 — the naive seq-sharded cache spec made XLA
    replicate-then-slice every layer's k/v).
    """
    from repro.sharding.rules import constrain
    B, S, d = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    src = x if kv is None else kv
    kv_positions = positions if kv_positions is None else kv_positions
    q = (x @ params["wq"]).reshape(B, S, nh, hd)
    k = (src @ params["wk"]).reshape(B, src.shape[1], nkv, hd)
    v = (src @ params["wv"]).reshape(B, src.shape[1], nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"]["scale"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, kv_positions, cfg.rope_theta)
    if constrain_kv:
        k = constrain(k, ("batch", None, None, "model"))
        v = constrain(v, ("batch", None, None, "model"))
    scale = 1.0 / math.sqrt(hd)
    if cfg.attention_impl == "pallas" and kv is None and causal:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(q, k, v, causal=True, window=window)
    else:
        mask = _attn_mask(jnp.broadcast_to(positions, (B, S)),
                          jnp.broadcast_to(kv_positions, (B, src.shape[1])),
                          causal, window)
        out = _sdpa_xla(q, k, v, mask, scale)
    return out.reshape(B, S, nh * hd) @ params["wo"], (k, v)


def attention_decode(params, cfg, x, pos, cache_k, cache_v, cache_pos, *,
                     window=None, rope=True, cross=False):
    """Single-token decode. x: (B, 1, d); cache_{k,v}: (B, Sc, nkv, hd);
    cache_pos: (B, Sc) int32 positions held in each cache slot (-1 = empty).
    Returns (out, new_k_cache, new_v_cache, new_cache_pos).

    For ring-buffer (windowed) caches the write slot is pos % Sc; for full
    caches Sc >= max_seq and slot = pos. Cross-attention reads the cache only.
    """
    B, _, d = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    Sc = cache_k.shape[1]
    q = (x @ params["wq"]).reshape(B, 1, nh, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
    if not cross:
        k_new = (x @ params["wk"]).reshape(B, 1, nkv, hd)
        v_new = (x @ params["wv"]).reshape(B, 1, nkv, hd)
        if cfg.qk_norm:
            k_new = rms_norm(k_new, params["k_norm"]["scale"], cfg.norm_eps)
        if rope:
            k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)
        slot = pos % Sc
        oh = jax.nn.one_hot(slot, Sc, dtype=cache_k.dtype)           # (B, Sc)
        cache_k = cache_k * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * k_new
        cache_v = cache_v * (1 - oh)[:, :, None, None] + oh[:, :, None, None] * v_new
        cache_pos = jnp.where(jnp.arange(Sc)[None] == slot[:, None],
                              pos[:, None], cache_pos)
    valid = cache_pos >= 0
    if not cross:
        valid &= cache_pos <= pos[:, None]
        if window is not None:
            valid &= cache_pos > (pos[:, None] - window)
    scale = 1.0 / math.sqrt(hd)
    rep = nh // nkv
    qr = q.reshape(B, nkv, rep, hd)
    logits = jnp.einsum("bkrh,bskh->bkrs", qr.astype(jnp.float32),
                        cache_k.astype(jnp.float32)) * scale
    logits = jnp.where(valid[:, None, None, :], logits, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkrs,bskh->bkrh", w, cache_v.astype(jnp.float32))
    out = out.reshape(B, 1, nh * hd).astype(x.dtype) @ params["wo"]
    return out, cache_k, cache_v, cache_pos


def _pool_write(pool, blk, off, kv):
    """Scatter K or V rows kv (..., nkv, hd) into a (P, nkv, bs, hd) pool:
    row i lands in page blk[i] at offset off[i], every head at once."""
    return pool.at[blk, :, off].set(kv)


def _pool_chain(pool, table):
    """Dense view of the page chains named by table (B, nb): returns
    (B, nb*bs, nkv, hd), whose index along axis 1 IS the absolute
    position."""
    g = jnp.swapaxes(jnp.take(pool, table, axis=0), -3, -2)
    return g.reshape(g.shape[:-4] + (-1,) + g.shape[-2:])


def attention_decode_paged(params, cfg, x, pos, kpool, vpool, table, *,
                           window=None, rope=True, kernel="reference"):
    """Single-token decode over a *paged* KV cache (block tables).

    x: (B, 1, d); pos: (B,) absolute position of the new token.
    kpool/vpool: (P, nkv, bs, hd) — pool row b holds the bs-token KV page of
    block id b for this layer. table: (B, nb) int32 block ids per slot; page
    j of slot s holds positions [j*bs, (j+1)*bs). Returns
    (out, new_kpool, new_vpool).

    Scatter: the new token's k/v land in pool row table[s, pos//bs] at
    offset pos % bs. Slots must never share their frontier block (the
    engine's allocator guarantees it via copy-on-write); inactive slots
    carry an all-zero table and scatter harmlessly into the reserved null
    block 0.

    The attention read is kernel-switched: ``kernel="reference"`` gathers
    each slot's pages into a dense (nb*bs) view whose index IS the absolute
    position (the CPU oracle path); ``kernel="pallas"`` streams pages
    straight from the pool with online softmax, never materializing the
    dense view (kernels/paged_attention; window must be None).
    """
    B, _, d = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    bs = kpool.shape[2]
    q = (x @ params["wq"]).reshape(B, 1, nh, hd)
    k_new = (x @ params["wk"]).reshape(B, 1, nkv, hd)
    v_new = (x @ params["wv"]).reshape(B, 1, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"], cfg.norm_eps)
        k_new = rms_norm(k_new, params["k_norm"]["scale"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, pos[:, None], cfg.rope_theta)
        k_new = apply_rope(k_new, pos[:, None], cfg.rope_theta)
    blk = jnp.take_along_axis(table, (pos // bs)[:, None], axis=1)[:, 0]
    off = pos % bs
    kpool = _pool_write(kpool, blk, off, k_new[:, 0])
    vpool = _pool_write(vpool, blk, off, v_new[:, 0])
    from repro.kernels.paged_attention import ops as pa_ops
    out = pa_ops.paged_attention(q[:, 0], kpool, vpool, table, pos,
                                 window=window, kernel=kernel)
    out = out.reshape(B, 1, nh * hd).astype(x.dtype) @ params["wo"]
    return out, kpool, vpool


def attention_verify_paged(params, cfg, x, pos, kpool, vpool, table, *,
                           window=None, rope=True):
    """Multi-token batched decode over a paged cache — the speculative-
    decoding verify forward. Every slot advances T positions at once:
    slot s's tokens sit at absolute positions pos[s] + [0, T), their K/V
    are scattered into the slot's pages first, then all T queries attend
    the full chain (causal by absolute position, so draft token j sees the
    resident prefix plus drafts 0..j — one forward replaces T sequential
    decode steps).

    x: (B, T, d); pos: (B,) absolute position of each slot's first token.
    kpool/vpool: (P, nkv, bs, hd); table: (B, nb). Returns
    (out (B, T, d), new_kpool, new_vpool).

    Positions that overflow the slot's table span (a draft burst near the
    request's token budget) scatter into the reserved null block 0 instead
    of clamping onto a live page; their outputs are garbage the caller's
    acceptance mask never reads. Uses the dense-gather read (the oracle
    path) — a multi-query Pallas verify kernel is a named follow-up.
    """
    B, T, d = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    bs = kpool.shape[2]
    nb = table.shape[1]
    q = (x @ params["wq"]).reshape(B, T, nh, hd)
    k = (x @ params["wk"]).reshape(B, T, nkv, hd)
    v = (x @ params["wv"]).reshape(B, T, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"]["scale"], cfg.norm_eps)
    q_pos = pos[:, None] + jnp.arange(T)[None, :]                # (B, T)
    if rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    in_span = q_pos < nb * bs
    page = jnp.clip(q_pos // bs, 0, nb - 1)
    blk = jnp.where(in_span, jnp.take_along_axis(table, page, axis=1), 0)
    off = jnp.where(in_span, q_pos % bs, 0)
    kpool = _pool_write(kpool, blk, off, k)
    vpool = _pool_write(vpool, blk, off, v)
    kall = _pool_chain(kpool, table)
    vall = _pool_chain(vpool, table)
    kv_pos = jnp.arange(nb * bs)
    mask = kv_pos[None, None, :] <= q_pos[:, :, None]            # (B, T, Sk)
    if window is not None:
        mask &= kv_pos[None, None, :] > (q_pos[:, :, None] - window)
    mask &= jnp.repeat(table != 0, bs, axis=1)[:, None, :]       # null pages
    scale = 1.0 / math.sqrt(hd)
    out = _sdpa_xla(q, kall, vall, mask, scale)
    return out.reshape(B, T, nh * hd) @ params["wo"], kpool, vpool


def attention_prefill_paged(params, cfg, x, q_pos, n_tok, kpool, vpool,
                            table, *, window=None, rope=True):
    """Suffix prefill over a paged cache: run `n_tok` real tokens (of the
    S=x.shape[1] bucketed batch, rest padding) whose absolute positions are
    `q_pos`, attending to everything already resident in this slot's pages
    (the reused prefix) plus themselves, and scatter their K/V into the
    pool. Single-sequence (B=1) — the engine prefills one slot at a time.

    x: (1, S, d); q_pos: (S,) absolute positions (start + arange(S));
    table: (nb,) this slot's block ids. Padded positions (index >= n_tok)
    scatter into null block 0 and their outputs are garbage the caller
    ignores. Returns (out, new_kpool, new_vpool).
    """
    B, S, d = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    bs = kpool.shape[2]
    nb = table.shape[0]
    q = (x @ params["wq"]).reshape(B, S, nh, hd)
    k = (x @ params["wk"]).reshape(B, S, nkv, hd)
    v = (x @ params["wv"]).reshape(B, S, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"]["scale"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, q_pos[None, :], cfg.rope_theta)
        k = apply_rope(k, q_pos[None, :], cfg.rope_theta)
    real = jnp.arange(S) < n_tok
    blk = jnp.where(real, jnp.take(table, q_pos // bs, axis=0), 0)
    off = jnp.where(real, q_pos % bs, 0)
    kpool = _pool_write(kpool, blk, off, k[0])
    vpool = _pool_write(vpool, blk, off, v[0])
    kall = _pool_chain(kpool, table[None])
    vall = _pool_chain(vpool, table[None])
    kv_pos = jnp.arange(nb * bs)
    mask = kv_pos[None, :] <= q_pos[:, None]             # causal, absolute
    if window is not None:
        mask &= kv_pos[None, :] > (q_pos[:, None] - window)
    scale = 1.0 / math.sqrt(hd)
    out = _sdpa_xla(q, kall, vall, mask[None], scale)
    return out.reshape(B, S, nh * hd) @ params["wo"], kpool, vpool


def attention_mixed_paged(params, cfg, x, pos, n_chunk, kpool, vpool, table,
                          ctable, *, window=None, rope=True,
                          kernel="reference"):
    """Mixed decode+chunk attention over a paged cache in ONE pass — the
    per-layer unit of the chunked-prefill scheduler's mixed step.

    x: (1, B + C, d) — the first B rows are one decode token per slot
    (B == table.shape[0]), the last C rows are one prompt's prefill chunk
    (right-padded; `n_chunk` of them real). pos: (B + C,) absolute
    positions of every row. All rows' K/V are projected and scattered in
    ONE combined pool update (the pool copy a functional cache update
    pays is per-program, so splitting decode and chunk into separate
    updates doubles the dominant cost); then the two reads run from the
    same updated pool:

      * decode rows attend their own chains through `table`
        (kernel-switched exactly like `attention_decode_paged`);
      * chunk rows attend the chunk slot's chain through `ctable` —
        truncated by the caller to the pages the chunk can causally see —
        causal by absolute position against the resident prefix plus
        themselves (same contract as `attention_prefill_chunk_paged`,
        the chunk-only oracle).

    The decode slots and the chunk slot never share a frontier page (CoW
    guarantee), so scatter order between the row groups is irrelevant.
    Pad chunk rows (index >= n_chunk) and masked decode slots (all-zero
    table rows) scatter into the reserved null block 0. Returns
    (out (1, B + C, d_attn_out), new_kpool, new_vpool).
    """
    R = x.shape[1]
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    bs = kpool.shape[2]
    B = table.shape[0]
    C = R - B
    nbc = ctable.shape[0]
    q = (x[0] @ params["wq"]).reshape(R, nh, hd)
    k = (x[0] @ params["wk"]).reshape(R, nkv, hd)
    v = (x[0] @ params["wv"]).reshape(R, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"]["scale"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    # one combined scatter: decode rows land in their slots' frontier
    # pages, chunk rows in the chunk chain at their absolute offsets
    dec_blk = jnp.take_along_axis(table, (pos[:B] // bs)[:, None],
                                  axis=1)[:, 0]
    cpos = pos[B:]
    real = (jnp.arange(C) < n_chunk) & (cpos < nbc * bs)
    chk_blk = jnp.where(real,
                        jnp.take(ctable, jnp.clip(cpos // bs, 0, nbc - 1)),
                        0)
    blk = jnp.concatenate([dec_blk, chk_blk])
    off = jnp.concatenate([pos[:B] % bs, jnp.where(real, cpos % bs, 0)])
    kpool = _pool_write(kpool, blk, off, k)
    vpool = _pool_write(vpool, blk, off, v)
    # read 1: per-slot decode attention (kernel-switched, as decode_paged)
    from repro.kernels.paged_attention import ops as pa_ops
    out_dec = pa_ops.paged_attention(q[:B], kpool, vpool, table, pos[:B],
                                     window=window, kernel=kernel)
    # read 2: the chunk attends its truncated chain, causal by position
    kall = _pool_chain(kpool, ctable[None])
    vall = _pool_chain(vpool, ctable[None])
    kv_pos = jnp.arange(nbc * bs)
    mask = kv_pos[None, :] <= cpos[:, None]
    if window is not None:
        mask &= kv_pos[None, :] > (cpos[:, None] - window)
    mask &= jnp.repeat(ctable != 0, bs)[None, :]
    out_chk = _sdpa_xla(q[B:][None], kall, vall, mask[None],
                        1.0 / math.sqrt(hd))[0]
    out = jnp.concatenate([out_dec.reshape(B, nh * hd),
                           out_chk.reshape(C, nh * hd)])
    return (out @ params["wo"])[None], kpool, vpool


def attention_prefill_chunk_paged(params, cfg, x, start, n_tok, kpool, vpool,
                                  table, *, window=None, rope=True):
    """One bounded *chunk* of a prompt's prefill over a paged cache — the
    unit of work the chunked-prefill scheduler slices per engine step.
    This standalone form is the chunk half's ORACLE: the production mixed
    step fuses it with the lockstep decode into one pool update
    (`attention_mixed_paged`); tests pin the two paths against each other.

    Unlike `attention_prefill_paged` (which runs a prompt's whole uncached
    suffix in one variable-bucket forward), the chunk has a FIXED shape
    S = x.shape[1] == chunk_budget, so one jit trace serves every chunk of
    every prompt: the first `n_tok` positions are real tokens at absolute
    positions start..start+n_tok-1, the rest right-pad. The chunk's K/V
    are scattered into the slot's block table at their absolute offsets,
    and the chunk attends causally against everything already committed
    below `start` (earlier chunks + reused radix prefix) plus itself.

    x: (1, S, d); start/n_tok: scalars; table: (nb,) this slot's block
    ids. Pad positions (and any position beyond the table span, which can
    happen only through padding past the last chunk) scatter into the
    reserved null block 0; their outputs are garbage the caller ignores.
    Returns (out (1, S, d), new_kpool, new_vpool).
    """
    B, S, d = x.shape
    hd, nh, nkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    bs = kpool.shape[2]
    nb = table.shape[0]
    q = (x @ params["wq"]).reshape(B, S, nh, hd)
    k = (x @ params["wk"]).reshape(B, S, nkv, hd)
    v = (x @ params["wv"]).reshape(B, S, nkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"]["scale"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"]["scale"], cfg.norm_eps)
    q_pos = start + jnp.arange(S)
    if rope:
        q = apply_rope(q, q_pos[None, :], cfg.rope_theta)
        k = apply_rope(k, q_pos[None, :], cfg.rope_theta)
    real = (jnp.arange(S) < n_tok) & (q_pos < nb * bs)
    page = jnp.clip(q_pos // bs, 0, nb - 1)
    blk = jnp.where(real, jnp.take(table, page, axis=0), 0)
    off = jnp.where(real, q_pos % bs, 0)
    kpool = _pool_write(kpool, blk, off, k[0])
    vpool = _pool_write(vpool, blk, off, v[0])
    kall = _pool_chain(kpool, table[None])
    vall = _pool_chain(vpool, table[None])
    kv_pos = jnp.arange(nb * bs)
    mask = kv_pos[None, :] <= q_pos[:, None]             # causal, absolute
    if window is not None:
        mask &= kv_pos[None, :] > (q_pos[:, None] - window)
    # the slot's own pages are trustworthy up to the chunk frontier, but a
    # null table row must never contribute keys (fresh pages past the
    # frontier are zero-filled and sit beyond the causal mask anyway)
    mask &= jnp.repeat(table != 0, bs)[None, :]
    scale = 1.0 / math.sqrt(hd)
    out = _sdpa_xla(q, kall, vall, mask[None], scale)
    return out.reshape(B, S, nh * hd) @ params["wo"], kpool, vpool


# ----------------------------------------------------------------------------- mlp

def init_mlp(key, d_model, d_ff, dtype, gated=True):
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[1], d_model, d_ff, dtype),
        "w_down": dense_init(ks[2], d_ff, d_model, dtype),
    }
    if gated:
        p["w_gate"] = dense_init(ks[0], d_model, d_ff, dtype)
    return p


def mlp(params, x, act="silu"):
    a = ACTIVATIONS[act]
    if "w_gate" in params:        # SwiGLU-style
        return (a(x @ params["w_gate"]) * (x @ params["w_up"])) @ params["w_down"]
    return a(x @ params["w_up"]) @ params["w_down"]


# ----------------------------------------------------------------------------- embed

def init_embedding(key, vocab, d, dtype):
    return {"table": jax.random.normal(key, (vocab, d), dtype) * 0.02}


def embed(params, tokens, scale=None):
    e = params["table"][tokens]
    if scale is not None:
        e = e * scale
    return e


def unembed(params, x, table=None):
    t = table if table is not None else params["table"]
    return x @ t.T
