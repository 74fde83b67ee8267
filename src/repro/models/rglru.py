"""RG-LRU recurrent block (RecurrentGemma / Griffin, arXiv:2402.19427).

Recurrence (per channel):
    r_t = sigmoid(W_r x_t + b_r)          # recurrence gate
    i_t = sigmoid(W_i x_t + b_i)          # input gate
    log_a_t = -c * softplus(Lambda) * r_t
    h_t = exp(log_a_t) * h_{t-1} + sqrt(1 - exp(2 log_a_t)) * (i_t * x_t)

Full sequences use jax.lax.associative_scan (log-depth on TPU); decode is a
one-step update. The block wraps the RG-LRU in the Griffin gated unit:
two linear branches, conv1d(4) + RG-LRU on one, GeLU gate on the other.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_rglru_block(key, cfg):
    d = cfg.d_model
    w = cfg.rglru.lru_width or d
    pdt = cfg.parameter_dtype
    ks = jax.random.split(key, 6)
    # Lambda init so a^c in [0.9, 0.999] roughly (Griffin appendix)
    u = jax.random.uniform(ks[4], (w,), minval=0.9, maxval=0.999)
    lam = jnp.log(jnp.expm1(-jnp.log(u) / cfg.rglru.c_exponent))      # inv softplus
    return {
        "w_x": dense_init(ks[0], d, w, pdt),        # recurrent branch in-proj
        "w_gate_branch": dense_init(ks[1], d, w, pdt),
        "conv_w": dense_init(ks[2], cfg.rglru.d_conv, w, pdt,
                             scale=1.0 / cfg.rglru.d_conv),
        "conv_b": jnp.zeros((w,), pdt),
        "w_r": dense_init(ks[3], w, w, pdt),
        "b_r": jnp.zeros((w,), jnp.float32),
        "w_i": dense_init(ks[5], w, w, pdt),
        "b_i": jnp.zeros((w,), jnp.float32),
        "Lambda": lam.astype(jnp.float32),
        "out_proj": dense_init(jax.random.fold_in(key, 7), w, d, pdt),
    }


def _gates(params, cfg, x):
    r = jax.nn.sigmoid((x @ params["w_r"]).astype(jnp.float32) + params["b_r"])
    i = jax.nn.sigmoid((x @ params["w_i"]).astype(jnp.float32) + params["b_i"])
    log_a = -cfg.rglru.c_exponent * jax.nn.softplus(params["Lambda"]) * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    gated_x = beta * (i * x.astype(jnp.float32))
    return a, gated_x


def rglru_scan(params, cfg, x, h0=None):
    """x: (B, S, w) -> (y, h_final). Associative scan over time (XLA) or
    the Pallas channel-tiled kernel (cfg.attention_impl == "pallas")."""
    a, gx = _gates(params, cfg, x)                                    # (B,S,w) f32
    if cfg.attention_impl == "pallas" and h0 is None:
        from repro.kernels.rglru_scan import ops as rg_ops
        y, h_fin = rg_ops.rglru_scan(a, gx)
        return y.astype(x.dtype), h_fin
    if h0 is not None:
        # fold initial state in as a virtual step 0 with a=1 (identity decay)
        a = jnp.concatenate([jnp.ones_like(a[:, :1]), a], axis=1)
        gx = jnp.concatenate([h0[:, None, :].astype(jnp.float32), gx], axis=1)

    def combine(left, right):
        a1, b1 = left
        a2, b2 = right
        return a1 * a2, a2 * b1 + b2

    _, Y = jax.lax.associative_scan(combine, (a, gx), axis=1)
    if h0 is not None:
        Y = Y[:, 1:]
    return Y.astype(x.dtype), Y[:, -1].astype(jnp.float32)


def rglru_step(params, cfg, x, h):
    """x: (B, w); h: (B, w) f32 -> (y, h_new)."""
    a, gx = _gates(params, cfg, x[:, None, :])
    h_new = a[:, 0] * h + gx[:, 0]
    return h_new.astype(x.dtype), h_new


def _conv_full(x, w, b):
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + x.shape[1], :] * w[i][None, None, :] for i in range(width))
    return out + b[None, None, :]


def rglru_block_forward(params, cfg, x):
    """Griffin recurrent block, full sequence. x: (B, S, d)."""
    branch = x @ params["w_x"]                                        # (B,S,w)
    gate = jax.nn.gelu(x @ params["w_gate_branch"])
    conv_out = _conv_full(branch, params["conv_w"], params["conv_b"])
    y, h_fin = rglru_scan(params, cfg, conv_out)
    out = (y * gate) @ params["out_proj"]
    cache = {"h": h_fin,
             "conv": branch[:, -(cfg.rglru.d_conv - 1):, :]}
    return out, cache


def rglru_block_decode(params, cfg, x, cache):
    """x: (B, 1, d); cache {"h": (B,w) f32, "conv": (B, d_conv-1, w)}."""
    branch = x @ params["w_x"]                                        # (B,1,w)
    gate = jax.nn.gelu(x @ params["w_gate_branch"])
    buf = jnp.concatenate([cache["conv"], branch], axis=1)            # (B, d_conv, w)
    conv_out = jnp.einsum("bwc,wc->bc", buf, params["conv_w"]) + params["conv_b"]
    y, h_new = rglru_step(params, cfg, conv_out, cache["h"])
    out = (y[:, None, :] * gate) @ params["out_proj"]
    return out, {"h": h_new, "conv": buf[:, 1:, :]}
