"""Composable decoder / encoder-decoder LM covering every assigned family.

A model is a stack of *blocks*; a block is ``cfg.block_pattern`` layers (e.g.
("rglru","rglru","attn") for RecurrentGemma). Block parameters are stacked on
a leading n_blocks axis and executed with ``jax.lax.scan`` so HLO size (and
therefore dry-run compile time) is depth-independent; layers that don't fit
the pattern (``cfg.tail_pattern``) run unrolled after the scan.

Entry points:
    init_lm(key, cfg)                        -> params
    forward_train(params, cfg, batch)        -> (logits, aux)
    forward_prefill(params, cfg, batch, cache_len) -> (logits, cache)
    decode_step(params, cfg, tokens, pos, cache, window=None) -> (logits, cache)
    init_cache(cfg, batch, cache_len)        -> cache pytree

Batch dict keys: "tokens" (B,S) int32; optional "embeds" (B,P,d) modality
prefix (vlm/audio stub); optional "enc_embeds" (B,Se,d) encoder input for
enc-dec models (the audio-frontend stub per the carve-out).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models import mamba2 as M2
from repro.models import moe as MOE
from repro.models import rglru as RG
from repro.sharding.rules import constrain

# ============================================================== per-layer init

def _init_layer(key, cfg, ltype: str, with_cross: bool = False):
    pdt = cfg.parameter_dtype
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    if ltype == "attn":
        p = {"norm1": L.init_rms_norm(d, pdt),
             "attn": L.init_attention(ks[0], cfg),
             "norm2": L.init_rms_norm(d, pdt)}
        if cfg.moe:
            p["ffn"] = MOE.init_moe(ks[1], cfg)
        else:
            p["ffn"] = L.init_mlp(ks[1], d, cfg.d_ff, pdt, cfg.mlp_gated)
        if with_cross:
            p["norm_cross"] = L.init_rms_norm(d, pdt)
            p["cross_attn"] = L.init_attention(ks[2], cfg)
        return p
    if ltype == "rglru":
        p = {"norm1": L.init_rms_norm(d, pdt),
             "rglru": RG.init_rglru_block(ks[0], cfg),
             "norm2": L.init_rms_norm(d, pdt)}
        p["ffn"] = (MOE.init_moe(ks[1], cfg) if cfg.moe
                    else L.init_mlp(ks[1], d, cfg.d_ff, pdt, cfg.mlp_gated))
        return p
    if ltype == "ssm":
        return {"norm1": L.init_rms_norm(d, pdt),
                "mamba": M2.init_mamba2(ks[0], cfg)}
    raise ValueError(ltype)


def init_lm(key, cfg):
    ks = jax.random.split(key, 8)
    params = {"embed": L.init_embedding(ks[0], cfg.padded_vocab_size,
                                        cfg.d_model, cfg.parameter_dtype)}
    cross = cfg.is_encdec

    def init_block(bkey):
        sub = jax.random.split(bkey, len(cfg.block_pattern))
        return tuple(_init_layer(sub[i], cfg, t, with_cross=cross)
                     for i, t in enumerate(cfg.block_pattern))

    if cfg.n_blocks > 0:
        params["blocks"] = jax.vmap(init_block)(
            jax.random.split(ks[1], cfg.n_blocks))
    params["tail"] = tuple(
        _init_layer(jax.random.fold_in(ks[2], i), cfg, t, with_cross=cross)
        for i, t in enumerate(cfg.tail_pattern))
    params["final_norm"] = L.init_rms_norm(cfg.d_model, cfg.parameter_dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[3], cfg.d_model,
                                         cfg.padded_vocab_size,
                                         cfg.parameter_dtype)
    if cfg.is_encdec:
        def init_enc_layer(k):
            return _init_layer(k, cfg, "attn", with_cross=False)
        params["encoder"] = {
            "blocks": jax.vmap(init_enc_layer)(
                jax.random.split(ks[4], cfg.n_enc_layers)),
            "final_norm": L.init_rms_norm(cfg.d_model, cfg.parameter_dtype),
        }
    return params


# ============================================================== full-seq apply

def _zero_aux():
    return {"load_balance": jnp.zeros((), jnp.float32),
            "router_z": jnp.zeros((), jnp.float32),
            "dropped_frac": jnp.zeros((), jnp.float32)}


def _ffn_apply(lp, cfg, h):
    if cfg.moe:
        return MOE.moe_ffn(lp["ffn"], cfg, h)
    return L.mlp(lp["ffn"], h, cfg.act), _zero_aux()


def _layer_full(lp, cfg, ltype, x, positions, window, enc_out, enc_pos,
                want_cache=False):
    """One layer, full sequence. Returns (x, aux, cache)."""
    aux = _zero_aux()
    if ltype == "attn":
        h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
        att, (k, v) = L.attention(lp["attn"], cfg, h, positions,
                                  causal=True, window=window,
                                  constrain_kv=want_cache)
        x = x + att
        if enc_out is not None:
            h = L.apply_rms_norm(lp["norm_cross"], x, cfg.norm_eps)
            catt, (ck, cv) = L.attention(lp["cross_attn"], cfg, h, positions,
                                         kv=enc_out, kv_positions=enc_pos,
                                         causal=False, rope=False,
                                         constrain_kv=want_cache)
            x = x + catt
        else:
            ck = cv = None
        h = L.apply_rms_norm(lp["norm2"], x, cfg.norm_eps)
        ff, aux = _ffn_apply(lp, cfg, h)
        x = x + ff
        cache = {"k": k, "v": v,
                 "pos": jnp.broadcast_to(positions, x.shape[:2]).astype(jnp.int32)}
        if ck is not None:
            cache["cross_k"], cache["cross_v"] = ck, cv
        return x, aux, cache
    if ltype == "rglru":
        h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
        out, rcache = RG.rglru_block_forward(lp["rglru"], cfg, h)
        x = x + out
        h = L.apply_rms_norm(lp["norm2"], x, cfg.norm_eps)
        ff, aux = _ffn_apply(lp, cfg, h)
        x = x + ff
        return x, aux, rcache
    if ltype == "ssm":
        h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
        out, scache = M2.mamba2_forward(lp["mamba"], cfg, h)
        return x + out, aux, scache
    raise ValueError(ltype)


def _accum_aux(a, b):
    return jax.tree.map(lambda u, v: u + v, a, b)


def _run_stack(params, cfg, x, positions, window, enc_out, enc_pos,
               want_cache: bool):
    """Scan blocks + unrolled tail. Returns (x, aux, caches)."""
    aux0 = _zero_aux()

    def block_fn(carry, bp):
        h, aux = carry
        caches = []
        for i, t in enumerate(cfg.block_pattern):
            h, a, c = _layer_full(bp[i], cfg, t, h, positions, window,
                                  enc_out, enc_pos, want_cache=want_cache)
            aux = _accum_aux(aux, a)
            caches.append(c)
        if cfg.seq_parallel:
            # Megatron-SP: residual seq-sharded between TP regions, turning
            # the TP all-reduces into reduce-scatter + all-gather pairs and
            # shrinking norm/residual working sets 1/model (§Perf-6)
            h = constrain(h, ("batch", "model", None))
        return (h, aux), tuple(caches) if want_cache else None

    if cfg.remat:
        block_fn = jax.checkpoint(block_fn)

    block_caches = None
    if cfg.n_blocks > 0 and "blocks" in params:
        if cfg.scan_layers:
            (x, aux), block_caches = jax.lax.scan(block_fn, (x, aux0),
                                                  params["blocks"])
        else:
            aux = aux0
            ys = []
            for i in range(cfg.n_blocks):
                bp = jax.tree.map(lambda a: a[i], params["blocks"])
                (x, aux), y = block_fn((x, aux), bp)
                ys.append(y)
            if want_cache:
                block_caches = jax.tree.map(
                    lambda *a: jnp.stack(a), *ys)
    else:
        aux = aux0
    tail_caches = []
    for i, t in enumerate(cfg.tail_pattern):
        x, a, c = _layer_full(params["tail"][i], cfg, t, x, positions, window,
                              enc_out, enc_pos, want_cache=want_cache)
        aux = _accum_aux(aux, a)
        tail_caches.append(c)
    caches = {"blocks": block_caches, "tail": tuple(tail_caches)}
    return x, aux, caches


def _encode(params, cfg, enc_embeds):
    """Encoder stack (non-causal attention over stub embeddings)."""
    enc_pos = jnp.arange(enc_embeds.shape[1])[None, :]
    x = enc_embeds.astype(cfg.activation_dtype)

    def enc_block(h, lp):
        y = L.apply_rms_norm(lp["norm1"], h, cfg.norm_eps)
        att, _ = L.attention(lp["attn"], cfg, y, enc_pos, causal=False)
        h = h + att
        y = L.apply_rms_norm(lp["norm2"], h, cfg.norm_eps)
        ff, _ = _ffn_apply(lp, cfg, y)
        return h + ff, None

    if cfg.scan_layers:
        x, _ = jax.lax.scan(enc_block, x, params["encoder"]["blocks"])
    else:
        for i in range(cfg.n_enc_layers):
            lp = jax.tree.map(lambda a: a[i], params["encoder"]["blocks"])
            x, _ = enc_block(x, lp)
    return L.apply_rms_norm(params["encoder"]["final_norm"], x, cfg.norm_eps), enc_pos


def _inputs_to_x(params, cfg, batch):
    """Token embedding + optional modality prefix. Returns (x, positions,
    n_prefix)."""
    tok = batch["tokens"]
    x = L.embed(params["embed"], tok).astype(cfg.activation_dtype)
    n_prefix = 0
    if "embeds" in batch and batch["embeds"] is not None:
        pre = batch["embeds"].astype(cfg.activation_dtype)
        n_prefix = pre.shape[1]
        x = jnp.concatenate([pre, x], axis=1)
    positions = jnp.arange(x.shape[1])[None, :]
    x = constrain(x, ("batch", None, None))
    return x, positions, n_prefix


def _logits(params, cfg, x):
    out = L.unembed(params["embed"], x) if cfg.tie_embeddings \
        else x @ params["lm_head"]
    if cfg.padded_vocab_size != cfg.vocab_size:
        # padded vocab entries can never win argmax / contribute to lse
        pad_iota = jnp.arange(cfg.padded_vocab_size)
        out = jnp.where(pad_iota[None, None, :] < cfg.vocab_size, out, -1e30)
    # vocab-sharded logits: keeps the (B,S,V) f32 xent intermediate on-chip
    return constrain(out, ("batch", None, "model"))


def forward_train(params, cfg, batch, window=None):
    """Returns (logits over token positions, aux losses)."""
    window = cfg.window if window is None else window
    enc_out = enc_pos = None
    if cfg.is_encdec:
        enc_out, enc_pos = _encode(params, cfg, batch["enc_embeds"])
    x, positions, n_prefix = _inputs_to_x(params, cfg, batch)
    x, aux, _ = _run_stack(params, cfg, x, positions, window, enc_out,
                           enc_pos, want_cache=False)
    x = L.apply_rms_norm(params["final_norm"], x, cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    n_ffn_layers = sum(1 for t in cfg.layer_types() if t != "ssm")
    aux = jax.tree.map(lambda v: v / max(n_ffn_layers, 1), aux)
    return _logits(params, cfg, x), aux


# ============================================================== caches / decode

def init_cache(cfg, batch_size: int, cache_len: int, enc_len: int = 0):
    """Zero cache pytree matching _run_stack(want_cache=True) structure but
    with sequence dims sized ``cache_len`` (attention) / constant (ssm, rglru).
    For enc-dec models pass enc_len > 0 to allocate fixed cross-attn caches."""
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    adt = cfg.activation_dtype

    def one(ltype):
        if ltype == "attn":
            c = {"k": jnp.zeros((batch_size, cache_len, nkv, hd), adt),
                 "v": jnp.zeros((batch_size, cache_len, nkv, hd), adt),
                 "pos": jnp.full((batch_size, cache_len), -1, jnp.int32)}
            if cfg.is_encdec and enc_len > 0:
                c["cross_k"] = jnp.zeros((batch_size, enc_len, nkv, hd), adt)
                c["cross_v"] = jnp.zeros((batch_size, enc_len, nkv, hd), adt)
                c["cross_pos"] = jnp.zeros((batch_size, enc_len), jnp.int32)
            return c
        if ltype == "rglru":
            w = cfg.rglru.lru_width or cfg.d_model
            return {"h": jnp.zeros((batch_size, w), jnp.float32),
                    "conv": jnp.zeros((batch_size, cfg.rglru.d_conv - 1, w), adt)}
        if ltype == "ssm":
            s = cfg.ssm
            nh = s.n_heads(cfg.d_model)
            return {"ssm": jnp.zeros((batch_size, nh, s.head_dim, s.d_state),
                                     jnp.float32),
                    "conv": jnp.zeros((batch_size, s.d_conv - 1,
                                       M2.conv_dim(cfg)), adt)}
        raise ValueError(ltype)

    def stack(tree, n):
        return jax.tree.map(lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), tree)

    blocks = None
    if cfg.n_blocks > 0:
        blocks = stack(tuple(one(t) for t in cfg.block_pattern), cfg.n_blocks)
    tail = tuple(one(t) for t in cfg.tail_pattern)
    return {"blocks": blocks, "tail": tail}


def _layer_decode(lp, cfg, ltype, x, pos, cache, window, cross: bool):
    if ltype == "attn":
        h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
        att, ck, cv, cp = L.attention_decode(
            lp["attn"], cfg, h, pos, cache["k"], cache["v"], cache["pos"],
            window=window)
        x = x + att
        new_cache = dict(cache, k=ck, v=cv, pos=cp)
        if cross and "cross_k" in cache:
            h = L.apply_rms_norm(lp["norm_cross"], x, cfg.norm_eps)
            catt, _, _, _ = L.attention_decode(
                lp["cross_attn"], cfg, h, pos, cache["cross_k"],
                cache["cross_v"], cache["cross_pos"], rope=False, cross=True)
            x = x + catt
        h = L.apply_rms_norm(lp["norm2"], x, cfg.norm_eps)
        ff, _ = _ffn_apply(lp, cfg, h)
        return x + ff, new_cache
    if ltype == "rglru":
        h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
        out, rcache = RG.rglru_block_decode(lp["rglru"], cfg, h, cache)
        x = x + out
        h = L.apply_rms_norm(lp["norm2"], x, cfg.norm_eps)
        ff, _ = _ffn_apply(lp, cfg, h)
        return x + ff, rcache
    if ltype == "ssm":
        h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
        out, scache = M2.mamba2_decode(lp["mamba"], cfg, h, cache)
        return x + out, scache
    raise ValueError(ltype)


def decode_step(params, cfg, tokens, pos, cache, window=None):
    """tokens: (B, 1) int32; pos: (B,) int32 absolute position of the new
    token. Returns (logits (B,1,V), new_cache)."""
    window = cfg.window if window is None else window
    cross = cfg.is_encdec
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    positions = pos

    def block_fn(h, xs):
        bp, bc = xs
        new_caches = []
        for i, t in enumerate(cfg.block_pattern):
            h, nc = _layer_decode(bp[i], cfg, t, h, positions, bc[i], window,
                                  cross)
            new_caches.append(nc)
        return h, tuple(new_caches)

    new_blocks = None
    if cfg.n_blocks > 0 and "blocks" in params:
        if cfg.scan_layers:
            x, new_blocks = jax.lax.scan(block_fn, x,
                                         (params["blocks"], cache["blocks"]))
        else:
            ys = []
            for i in range(cfg.n_blocks):
                xs_i = jax.tree.map(lambda a: a[i],
                                    (params["blocks"], cache["blocks"]))
                x, y = block_fn(x, xs_i)
                ys.append(y)
            new_blocks = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    new_tail = []
    for i, t in enumerate(cfg.tail_pattern):
        x, nc = _layer_decode(params["tail"][i], cfg, t, x, positions,
                              cache["tail"][i], window, cross)
        new_tail.append(nc)
    x = L.apply_rms_norm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), {"blocks": new_blocks,
                                     "tail": tuple(new_tail)}


# ============================================================== paged decode

def paged_supported(cfg) -> bool:
    """The paged KV path covers pure-attention decoder stacks: recurrent
    mixers (ssm/rglru) carry O(1) state that a prefix block chain cannot
    capture, and enc-dec adds cross caches the block table doesn't model."""
    return (not cfg.is_encdec
            and all(t == "attn" for t in cfg.layer_types()))


def init_paged_cache(cfg, n_pool_blocks: int, block_size: int):
    """Pool-shaped KV cache: per attention layer, row b of the (P, nkv, bs,
    hd) pool arrays is the bs-token page named by block id b (heads outside
    tokens, so one head's page is a (bs, hd) tile for the decode kernel). The same
    block id indexes every layer, so one host-side block table describes a
    sequence across the whole stack. Structure mirrors `init_cache`
    ("blocks" stacked on a leading n_blocks axis, "tail" unrolled) so the
    decode scan consumes it unchanged. No "pos" leaf: a paged page's gather
    index *is* its absolute position."""
    if not paged_supported(cfg):
        raise ValueError(f"{cfg.arch_id}: paged KV cache requires a pure-"
                         "attention decoder (no ssm/rglru/enc-dec layers)")
    hd, nkv = cfg.resolved_head_dim, cfg.n_kv_heads
    adt = cfg.activation_dtype

    def one():
        return {"k": jnp.zeros((n_pool_blocks, nkv, block_size, hd), adt),
                "v": jnp.zeros((n_pool_blocks, nkv, block_size, hd), adt)}

    def stack(tree, n):
        return jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (n,) + a.shape), tree)

    blocks = None
    if cfg.n_blocks > 0:
        blocks = stack(tuple(one() for _ in cfg.block_pattern), cfg.n_blocks)
    tail = tuple(one() for _ in cfg.tail_pattern)
    return {"blocks": blocks, "tail": tail}


def copy_pool_blocks(cache, src_ids, dst_ids):
    """Copy whole KV pages src -> dst in every layer's pool (the device
    half of copy-on-write: the host manager picked the ids)."""
    src = jnp.asarray(src_ids, jnp.int32)
    dst = jnp.asarray(dst_ids, jnp.int32)

    def cp(a, axis):
        idx = (slice(None),) * axis
        return a.at[idx + (dst,)].set(a[idx + (src,)])

    out = {"blocks": None}
    if cache.get("blocks") is not None:
        out["blocks"] = jax.tree.map(lambda a: cp(a, 1), cache["blocks"])
    out["tail"] = jax.tree.map(lambda a: cp(a, 0), cache["tail"])
    return out


def _layer_decode_paged(lp, cfg, x, pos, pool, table, window,
                        kernel="reference"):
    h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
    att, ck, cv = L.attention_decode_paged(
        lp["attn"], cfg, h, pos, pool["k"], pool["v"], table, window=window,
        kernel=kernel)
    x = x + att
    h = L.apply_rms_norm(lp["norm2"], x, cfg.norm_eps)
    ff, _ = _ffn_apply(lp, cfg, h)
    return x + ff, {"k": ck, "v": cv}


def decode_step_paged(params, cfg, tokens, pos, cache, table, window=None,
                      kernel="reference"):
    """`decode_step` over a paged cache. tokens: (B, 1); pos: (B,); table:
    (B, nb) block ids per slot (see `init_paged_cache`). Returns
    (logits (B,1,V), new_cache). The scatter plus kernel-switched attention
    read per layer is `layers.attention_decode_paged` (kernel="pallas"
    streams pages from the pool; "reference" is the dense gather)."""
    window = cfg.window if window is None else window
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)

    def block_fn(h, xs):
        bp, bpool = xs
        new_pools = []
        for i in range(len(cfg.block_pattern)):
            h, np_ = _layer_decode_paged(bp[i], cfg, h, pos, bpool[i],
                                         table, window, kernel)
            new_pools.append(np_)
        return h, tuple(new_pools)

    new_blocks = None
    if cfg.n_blocks > 0 and "blocks" in params:
        if cfg.scan_layers:
            x, new_blocks = jax.lax.scan(block_fn, x,
                                         (params["blocks"], cache["blocks"]))
        else:
            ys = []
            for i in range(cfg.n_blocks):
                xs_i = jax.tree.map(lambda a: a[i],
                                    (params["blocks"], cache["blocks"]))
                x, y = block_fn(x, xs_i)
                ys.append(y)
            new_blocks = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    new_tail = []
    for i in range(len(cfg.tail_pattern)):
        x, nc = _layer_decode_paged(params["tail"][i], cfg, x, pos,
                                    cache["tail"][i], table, window, kernel)
        new_tail.append(nc)
    x = L.apply_rms_norm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), {"blocks": new_blocks,
                                     "tail": tuple(new_tail)}


def _layer_verify_paged(lp, cfg, x, pos, pool, table, window):
    h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
    att, ck, cv = L.attention_verify_paged(
        lp["attn"], cfg, h, pos, pool["k"], pool["v"], table, window=window)
    x = x + att
    h = L.apply_rms_norm(lp["norm2"], x, cfg.norm_eps)
    ff, _ = _ffn_apply(lp, cfg, h)
    return x + ff, {"k": ck, "v": cv}


def verify_step_paged(params, cfg, tokens, pos, cache, table, window=None):
    """Multi-token `decode_step_paged`: the speculative-decoding verify
    forward. tokens: (B, T) — slot s's tokens occupy absolute positions
    pos[s] + [0, T); all T tokens' K/V are written into the slot's pages
    and all T positions' logits come back from one forward (causal within
    the burst via absolute positions). Returns (logits (B, T, V),
    new_cache). The caller decides afterwards which written positions
    survive (acceptance) and rewinds its frontier past the rest — stale
    rows beyond the frontier are masked by every subsequent read."""
    window = cfg.window if window is None else window
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)

    def block_fn(h, xs):
        bp, bpool = xs
        new_pools = []
        for i in range(len(cfg.block_pattern)):
            h, np_ = _layer_verify_paged(bp[i], cfg, h, pos, bpool[i],
                                         table, window)
            new_pools.append(np_)
        return h, tuple(new_pools)

    new_blocks = None
    if cfg.n_blocks > 0 and "blocks" in params:
        if cfg.scan_layers:
            x, new_blocks = jax.lax.scan(block_fn, x,
                                         (params["blocks"], cache["blocks"]))
        else:
            ys = []
            for i in range(cfg.n_blocks):
                xs_i = jax.tree.map(lambda a: a[i],
                                    (params["blocks"], cache["blocks"]))
                x, y = block_fn(x, xs_i)
                ys.append(y)
            new_blocks = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    new_tail = []
    for i in range(len(cfg.tail_pattern)):
        x, nc = _layer_verify_paged(params["tail"][i], cfg, x, pos,
                                    cache["tail"][i], table, window)
        new_tail.append(nc)
    x = L.apply_rms_norm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), {"blocks": new_blocks,
                                     "tail": tuple(new_tail)}


def _layer_prefill_paged(lp, cfg, x, q_pos, n_tok, pool, table, window):
    h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
    att, ck, cv = L.attention_prefill_paged(
        lp["attn"], cfg, h, q_pos, n_tok, pool["k"], pool["v"], table,
        window=window)
    x = x + att
    h = L.apply_rms_norm(lp["norm2"], x, cfg.norm_eps)
    ff, _ = _ffn_apply(lp, cfg, h)
    return x + ff, {"k": ck, "v": cv}


def forward_prefill_paged(params, cfg, tokens, start, n_tok, cache, table,
                          window=None):
    """Prefill only the *uncached suffix* of a prompt against a paged cache
    whose pages [0, start) are already resident (radix prefix hit).

    tokens: (1, S) suffix tokens, right-padded to the bucket length S;
    start: scalar absolute position of tokens[0, 0]; n_tok: scalar number
    of real (non-pad) tokens; table: (nb,) the slot's block chain. Returns
    (logits (1, S, V), new_cache) — only logits[:, :n_tok] are meaningful.
    """
    window = cfg.window if window is None else window
    S = tokens.shape[1]
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)
    q_pos = start + jnp.arange(S)

    def block_fn(h, xs):
        bp, bpool = xs
        new_pools = []
        for i in range(len(cfg.block_pattern)):
            h, np_ = _layer_prefill_paged(bp[i], cfg, h, q_pos, n_tok,
                                          bpool[i], table, window)
            new_pools.append(np_)
        return h, tuple(new_pools)

    new_blocks = None
    if cfg.n_blocks > 0 and "blocks" in params:
        if cfg.scan_layers:
            x, new_blocks = jax.lax.scan(block_fn, x,
                                         (params["blocks"], cache["blocks"]))
        else:
            ys = []
            for i in range(cfg.n_blocks):
                xs_i = jax.tree.map(lambda a: a[i],
                                    (params["blocks"], cache["blocks"]))
                x, y = block_fn(x, xs_i)
                ys.append(y)
            new_blocks = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    new_tail = []
    for i in range(len(cfg.tail_pattern)):
        x, nc = _layer_prefill_paged(params["tail"][i], cfg, x, q_pos, n_tok,
                                     cache["tail"][i], table, window)
        new_tail.append(nc)
    x = L.apply_rms_norm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), {"blocks": new_blocks,
                                     "tail": tuple(new_tail)}


def _layer_mixed_paged(lp, cfg, x, pos, n_chunk, pool, table, ctable,
                       window, kernel):
    h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
    att, ck, cv = L.attention_mixed_paged(
        lp["attn"], cfg, h, pos, n_chunk, pool["k"], pool["v"], table,
        ctable, window=window, kernel=kernel)
    x = x + att
    h = L.apply_rms_norm(lp["norm2"], x, cfg.norm_eps)
    ff, _ = _ffn_apply(lp, cfg, h)
    return x + ff, {"k": ck, "v": cv}


def mixed_step_paged(params, cfg, tokens, pos, n_chunk, cache, table, ctable,
                     window=None, kernel="reference"):
    """One chunked-prefill scheduler iteration on device: a single stack
    traversal over B decode rows + C chunk rows (`tokens` (B + C,), rows
    laid out as in `layers.attention_mixed_paged`), with ONE combined
    pool scatter per layer. Splitting decode and chunk into two programs
    (or two sequential pool updates in one program) pays the functional
    pool copy twice — the dominant per-dispatch cost — so the fusion is
    what makes chunk piggybacking near-free next to a plain decode step.
    Returns (logits (B + C, V), new_cache)."""
    window = cfg.window if window is None else window
    x = L.embed(params["embed"], tokens)[None].astype(cfg.activation_dtype)

    def block_fn(h, xs):
        bp, bpool = xs
        new_pools = []
        for i in range(len(cfg.block_pattern)):
            h, np_ = _layer_mixed_paged(bp[i], cfg, h, pos, n_chunk,
                                        bpool[i], table, ctable, window,
                                        kernel)
            new_pools.append(np_)
        return h, tuple(new_pools)

    new_blocks = None
    if cfg.n_blocks > 0 and "blocks" in params:
        if cfg.scan_layers:
            x, new_blocks = jax.lax.scan(block_fn, x,
                                         (params["blocks"], cache["blocks"]))
        else:
            ys = []
            for i in range(cfg.n_blocks):
                xs_i = jax.tree.map(lambda a: a[i],
                                    (params["blocks"], cache["blocks"]))
                x, y = block_fn(x, xs_i)
                ys.append(y)
            new_blocks = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    new_tail = []
    for i in range(len(cfg.tail_pattern)):
        x, nc = _layer_mixed_paged(params["tail"][i], cfg, x, pos, n_chunk,
                                   cache["tail"][i], table, ctable, window,
                                   kernel)
        new_tail.append(nc)
    x = L.apply_rms_norm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x)[0], {"blocks": new_blocks,
                                        "tail": tuple(new_tail)}


def _layer_prefill_chunk_paged(lp, cfg, x, start, n_tok, pool, table, window):
    h = L.apply_rms_norm(lp["norm1"], x, cfg.norm_eps)
    att, ck, cv = L.attention_prefill_chunk_paged(
        lp["attn"], cfg, h, start, n_tok, pool["k"], pool["v"], table,
        window=window)
    x = x + att
    h = L.apply_rms_norm(lp["norm2"], x, cfg.norm_eps)
    ff, _ = _ffn_apply(lp, cfg, h)
    return x + ff, {"k": ck, "v": cv}


def prefill_chunk_paged(params, cfg, tokens, start, n_tok, cache, table,
                        window=None):
    """One fixed-shape prefill *chunk* against a paged cache — the device
    half of the chunked-prefill scheduler (`serve/scheduler.py`).

    tokens: (1, C) — C == chunk_budget, a compile-time constant, so ONE
    jit trace serves every chunk of every prompt regardless of how many
    real tokens it carries; start: scalar absolute position of
    tokens[0, 0]; n_tok: scalar count of real (non-pad) tokens; table:
    (nb,) the slot's block chain. Positions [0, start) must already be
    resident in the chain (earlier chunks and/or the reused radix
    prefix). Returns (logits (1, C, V), new_cache) — only
    logits[:, :n_tok] are meaningful; the caller reads position n_tok-1
    when the chunk completes its prompt (the deferred first token).
    """
    window = cfg.window if window is None else window
    x = L.embed(params["embed"], tokens).astype(cfg.activation_dtype)

    def block_fn(h, xs):
        bp, bpool = xs
        new_pools = []
        for i in range(len(cfg.block_pattern)):
            h, np_ = _layer_prefill_chunk_paged(bp[i], cfg, h, start, n_tok,
                                                bpool[i], table, window)
            new_pools.append(np_)
        return h, tuple(new_pools)

    new_blocks = None
    if cfg.n_blocks > 0 and "blocks" in params:
        if cfg.scan_layers:
            x, new_blocks = jax.lax.scan(block_fn, x,
                                         (params["blocks"], cache["blocks"]))
        else:
            ys = []
            for i in range(cfg.n_blocks):
                xs_i = jax.tree.map(lambda a: a[i],
                                    (params["blocks"], cache["blocks"]))
                x, y = block_fn(x, xs_i)
                ys.append(y)
            new_blocks = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    new_tail = []
    for i in range(len(cfg.tail_pattern)):
        x, nc = _layer_prefill_chunk_paged(params["tail"][i], cfg, x, start,
                                           n_tok, cache["tail"][i], table,
                                           window)
        new_tail.append(nc)
    x = L.apply_rms_norm(params["final_norm"], x, cfg.norm_eps)
    return _logits(params, cfg, x), {"blocks": new_blocks,
                                     "tail": tuple(new_tail)}


def forward_prefill(params, cfg, batch, window=None):
    """Full forward that also returns per-layer caches at natural length
    (the serving engine copies them into a fixed-size ring/linear cache).
    For enc-dec models the cross k/v caches are included."""
    window = cfg.window if window is None else window
    enc_out = enc_pos = None
    if cfg.is_encdec:
        enc_out, enc_pos = _encode(params, cfg, batch["enc_embeds"])
    x, positions, n_prefix = _inputs_to_x(params, cfg, batch)
    x, aux, caches = _run_stack(params, cfg, x, positions, window, enc_out,
                                enc_pos, want_cache=True)
    x = L.apply_rms_norm(params["final_norm"], x, cfg.norm_eps)
    if n_prefix:
        x = x[:, n_prefix:]
    return _logits(params, cfg, x), caches
