"""Mamba2 block — SSD (state-space duality) [arXiv:2405.21060], TPU-adapted.

The CUDA reference implements SSD with a fused kernel over (chunk, head)
thread-blocks using shared memory; the TPU adaptation keeps the *algorithm*
(chunked: quadratic intra-chunk in matmul form for the MXU, linear
inter-chunk recurrence) but re-tiles it for VMEM: the chunked scan is either
pure-jnp (`ssd_chunked`, what the dry-run lowers; XLA fuses the chunk
einsums onto the MXU) or the Pallas kernel in kernels/ssd_scan (grid over
(batch*head, chunk) with the running state carried in a VMEM scratch
accumulator).

Layer structure follows the Mamba2 paper: in_proj -> (z | xBC | dt),
causal conv1d on xBC, SSD, gated RMSNorm(y * silu(z)), out_proj.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init, rms_norm


# --------------------------------------------------------------------- SSD core

def ssd_chunked(x, dt, A, B, C, chunk_size: int):
    """Chunked SSD. x:(b,s,h,p) dt:(b,s,h) A:(h,) B,C:(b,s,g,n).

    Recurrence: h_t = exp(dt_t*A) h_{t-1} + dt_t * B_t x_t ;  y_t = C_t . h_t
    Returns (y, final_state) with final_state (b,h,p,n).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    q = min(chunk_size, s)
    if s % q:
        # pad to a chunk multiple: dt=0 padding is exact (decay exp(0)=1,
        # zero state update); extra outputs are sliced off below.
        pad = q - s % q
        padded = [jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                  for t in (x, dt, B, C)]
        y, state = ssd_chunked(padded[0], padded[1], A, padded[2], padded[3],
                               q)
        return y[:, :s], state
    nc = s // q
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)                                  # (b,s,h,n)
    Ch = jnp.repeat(C, rep, axis=2)

    f32 = jnp.float32
    xdt = (x * dt[..., None]).astype(f32)                            # dt*B*x factor
    a = (dt * A[None, None, :]).astype(f32)                          # log-decay/step

    def resh(t):
        return t.reshape(b, nc, q, *t.shape[2:])

    xc, ac, Bc, Cc = resh(xdt), resh(a), resh(Bh.astype(f32)), resh(Ch.astype(f32))
    acs = jnp.cumsum(ac, axis=2)                                     # (b,nc,q,h) inclusive

    # intra-chunk (quadratic, matmul form): L[i,j] = exp(acs_i - acs_j), i >= j
    seg = acs[:, :, :, None, :] - acs[:, :, None, :, :]              # (b,nc,q,q,h)
    tri = jnp.tril(jnp.ones((q, q), bool))
    L = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
    y_diag = jnp.einsum("bcihn,bcjhn,bcijh,bcjhp->bcihp", Cc, Bc, L, xc)

    # per-chunk end states: S_c = sum_j exp(acs_last - acs_j) dt_j B_j x_j
    decay_to_end = jnp.exp(acs[:, :, -1:, :] - acs)                  # (b,nc,q,h)
    S_c = jnp.einsum("bcjhn,bcjh,bcjhp->bchpn", Bc, decay_to_end, xc)

    # inter-chunk recurrence (linear scan over chunks)
    chunk_decay = jnp.exp(acs[:, :, -1, :])                          # (b,nc,h)

    def step(H, inputs):
        s_c, dec, acs_c, c_c = inputs                                # per chunk
        # contribution of carried state to every position in this chunk
        y_off = jnp.einsum("bihn,bih,bhpn->bihp", c_c, jnp.exp(acs_c), H)
        H_new = dec[:, :, None, None] * H + s_c
        return H_new, y_off

    H0 = jnp.zeros((b, h, p, n), f32)
    xs = (jnp.moveaxis(S_c, 1, 0), jnp.moveaxis(chunk_decay, 1, 0),
          jnp.moveaxis(acs, 1, 0), jnp.moveaxis(Cc, 1, 0))
    H_fin, y_off = jax.lax.scan(step, H0, xs)
    y_off = jnp.moveaxis(y_off, 0, 1)                                # (b,nc,q,h,p)
    y = (y_diag + y_off).reshape(b, s, h, p)
    return y.astype(x.dtype), H_fin


def ssd_decode_step(state, x, dt, A, B, C):
    """One-token recurrence. state:(b,h,p,n) x:(b,h,p) dt:(b,h) B,C:(b,g,n)."""
    h = x.shape[1]
    rep = h // B.shape[1]
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)              # (b,h,n)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    decay = jnp.exp(dt * A[None, :]).astype(jnp.float32)             # (b,h)
    upd = (dt[..., None] * x)[..., :, None] * Bh[:, :, None, :]      # (b,h,p,n)
    state = decay[:, :, None, None] * state + upd
    y = jnp.einsum("bhpn,bhn->bhp", state, Ch)
    return y.astype(x.dtype), state


# --------------------------------------------------------------------- layer

def conv_dim(cfg):
    s = cfg.ssm
    return s.d_inner(cfg.d_model) + 2 * s.n_groups * s.d_state


def init_mamba2(key, cfg):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    cd = conv_dim(cfg)
    pdt = cfg.parameter_dtype
    ks = jax.random.split(key, 5)
    # dt bias initialized so softplus(dt_bias) spans [dt_min, dt_max]
    u = jax.random.uniform(ks[3], (nh,))
    dt_init = jnp.exp(u * (jnp.log(s.dt_max) - jnp.log(s.dt_min)) + jnp.log(s.dt_min))
    dt_bias = dt_init + jnp.log(-jnp.expm1(-dt_init))                # inv softplus
    return {
        "in_proj": dense_init(ks[0], d, 2 * di + 2 * s.n_groups * s.d_state + nh, pdt),
        "conv_w": dense_init(ks[1], s.d_conv, cd, pdt, scale=1.0 / s.d_conv),
        "conv_b": jnp.zeros((cd,), pdt),
        "A_log": jnp.log(jnp.arange(1, nh + 1, dtype=jnp.float32)),
        "dt_bias": dt_bias.astype(jnp.float32),
        "D": jnp.ones((nh,), pdt),
        "norm_scale": jnp.zeros((di,), pdt),
        "out_proj": dense_init(ks[4], di, d, pdt),
    }


def _causal_conv(xBC, w, b):
    """Depthwise causal conv1d. xBC:(B,S,cd), w:(width,cd)."""
    width = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i][None, None, :]
              for i in range(width))
    return jax.nn.silu(out + b[None, None, :])


def _split_proj(cfg, proj):
    s = cfg.ssm
    di = s.d_inner(cfg.d_model)
    gn = s.n_groups * s.d_state
    z, xBC, dt = jnp.split(proj, [di, di + di + 2 * gn], axis=-1)
    return z, xBC, dt


def mamba2_forward(params, cfg, u):
    """u: (B, S, d) -> (y, final_state_dict) full-sequence (train/prefill)."""
    s = cfg.ssm
    B_, S, d = u.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    proj = u @ params["in_proj"]
    z, xBC_raw, dt = _split_proj(cfg, proj)
    xBC = _causal_conv(xBC_raw, params["conv_w"], params["conv_b"])
    x, Bm, Cm = jnp.split(xBC, [di, di + gn], axis=-1)
    x = x.reshape(B_, S, nh, s.head_dim)
    Bm = Bm.reshape(B_, S, s.n_groups, s.d_state)
    Cm = Cm.reshape(B_, S, s.n_groups, s.d_state)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"][None, None, :])
    A = -jnp.exp(params["A_log"])
    if cfg.attention_impl == "pallas":
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, state = ssd_ops.ssd_scan(x, dt, A, Bm, Cm, chunk_size=s.chunk_size)
    else:
        y, state = ssd_chunked(x, dt, A, Bm, Cm, s.chunk_size)
    y = y + x * params["D"][None, None, :, None]
    y = y.reshape(B_, S, di)
    y = rms_norm(y * jax.nn.silu(z), params["norm_scale"], cfg.norm_eps)
    out = y @ params["out_proj"]
    # cache: SSD state + last (d_conv-1) pre-activation conv inputs
    cache = {"ssm": state, "conv": xBC_raw[:, -(s.d_conv - 1):, :]}
    return out, cache


def mamba2_decode(params, cfg, u, cache):
    """u: (B, 1, d); cache: {"ssm": (B,h,p,n) f32, "conv": (B, d_conv-1, cd)}."""
    s = cfg.ssm
    B_, _, d = u.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    z, xBC_raw, dt = _split_proj(cfg, u @ params["in_proj"])
    conv_buf = jnp.concatenate([cache["conv"], xBC_raw], axis=1)      # (B, d_conv, cd)
    w = params["conv_w"]
    xBC = jax.nn.silu(jnp.einsum("bwc,wc->bc", conv_buf, w) + params["conv_b"])
    x, Bm, Cm = jnp.split(xBC, [di, di + gn], axis=-1)
    x = x.reshape(B_, nh, s.head_dim)
    Bm = Bm.reshape(B_, s.n_groups, s.d_state)
    Cm = Cm.reshape(B_, s.n_groups, s.d_state)
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"][None, :])
    A = -jnp.exp(params["A_log"])
    y, state = ssd_decode_step(cache["ssm"], x, dtv, A, Bm, Cm)
    y = y + x * params["D"][None, :, None]
    y = y.reshape(B_, 1, di)
    y = rms_norm(y * jax.nn.silu(z), params["norm_scale"], cfg.norm_eps)
    new_cache = {"ssm": state, "conv": conv_buf[:, 1:, :]}
    return y @ params["out_proj"], new_cache
