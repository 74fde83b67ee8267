"""The plain float32 reference forward, independent of the program.

A decoder as the published Qwen3 and Mistral code defines it: RMSNorm with
weight, grouped-query attention with rotate-half RoPE (base rope_theta) on
q and k, per-head RMSNorm of q and k before RoPE where the config says
qk_norm (Qwen3), a causal softmax, a SwiGLU MLP, a final norm and an output
head that is the embedding (tied) or its own matrix. No cache, no kernel,
no batching of slots: one full forward over each whole sequence.

Weights come from the seed (`weights`), one layer at a time, in the served
dtype and then widened to float32; every matmul runs at HIGHEST precision,
so on a TPU nothing is computed below float32. Sequences are padded at the
end to a few fixed lengths (the causal mask keeps padding out of every real
position) and the logits are formed only at the rows asked for.

`quant="int8"` is the control, the reference computed in int8 where the
program computes in bfloat16: every weight matrix held in int8 (per output
channel), every matmul taking int8 operands with int32 accumulation, and
every tensor the program stores in bfloat16 (the residual stream, the norms'
outputs, q, k and v, each matmul's output, the MLP's products, the logits)
stored in int8 with one symmetric scale per row. Softmax and the norms'
arithmetic stay in float32, as the program's do.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HI = jax.lax.Precision.HIGHEST
SCORE_BUDGET = 1 << 30      # bytes of attention scores per batch


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (b, L, h, hd) at positions 0..L-1."""
    L, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def _store(x, quant):
    """x as the control would hold it: int8 with one scale per row."""
    if quant is None:
        return x
    q, s = _int8(x, -1)
    return q.astype(jnp.float32) * s


def _mm(x, w, quant):
    if quant is None:
        return jnp.einsum("...i,io->...o", x, w, precision=HI)
    xq, sx = _int8(x, -1)
    wq, sw = _int8(w, 0)
    acc = jnp.einsum("...i,io->...o", xq, wq,
                     preferred_element_type=jnp.int32)
    return _store(acc.astype(jnp.float32) * sx * sw[0], quant)


def _layer(h, w, c, quant):
    b, L, _ = h.shape
    nh, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    eps = c["rms_norm_eps"]
    x = _store(_rms(h, 1.0 + w["input_norm"], eps), quant)
    q = _mm(x, w["q_proj"], quant).reshape(b, L, nh, hd)
    k = _mm(x, w["k_proj"], quant).reshape(b, L, nkv, hd)
    v = _mm(x, w["v_proj"], quant).reshape(b, L, nkv, hd)
    if c["qk_norm"]:
        q = _rms(q, 1.0 + w["q_norm"], eps)
        k = _rms(k, 1.0 + w["k_norm"], eps)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    q, k, v = _store(q, quant), _store(k, quant), _store(v, quant)
    k = jnp.repeat(k, nh // nkv, axis=2)        # head i reads kv head i//rep
    v = jnp.repeat(v, nh // nkv, axis=2)
    s = jnp.einsum("blhd,bmhd->bhlm", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhlm,bmhd->blhd", p, v, precision=HI)
    h = _store(h + _mm(o.reshape(b, L, nh * hd), w["o_proj"], quant), quant)
    x = _store(_rms(h, 1.0 + w["post_norm"], eps), quant)
    up = _store(jax.nn.silu(_mm(x, w["gate_proj"], quant)), quant) \
        * _mm(x, w["up_proj"], quant)
    return _store(h + _mm(_store(up, quant), w["down_proj"], quant), quant)


def _f32(tree):
    return {k: v.astype(jnp.float32) for k, v in tree.items()}


class Reference:
    """The forward for one configuration and one seed."""

    HEAD_ROWS = 256     # rows of logits formed at a time

    def __init__(self, c: dict, seed: int, quant: Optional[str] = None):
        if quant not in (None, "int8"):
            raise ValueError(f"quant must be None or 'int8', got {quant!r}")
        self.c, self.quant = c, quant
        self.key = W.base_key(seed)
        self._globals = jax.jit(lambda key: _f32(W.globals_(key, c)))
        self._layer_w = jax.jit(lambda key, i: _f32(W.layer(key, c, i)))
        self._layer = jax.jit(lambda h, w: _layer(h, w, c, quant))
        self._head = jax.jit(self._head_fn)

    def _head_fn(self, h, idx, g, toks):
        """h: (b, L, d); idx: (R,) flat row indices, R a multiple of
        HEAD_ROWS; toks: (S, R). Returns each row's best logit, its argmax
        and the logit of each of the S tokens asked for."""
        c = self.c
        rows = jnp.take(h.reshape(-1, h.shape[-1]), idx, axis=0)
        head = g["embed"].T if c["tie_word_embeddings"] else g["lm_head"]

        def chunk(args):
            x, t = args
            x = _store(_rms(x, 1.0 + g["final_norm"], c["rms_norm_eps"]),
                       self.quant)
            lg = _mm(x, head, self.quant)
            got = jnp.take_along_axis(lg[None], t[..., None], axis=-1)[..., 0]
            return jnp.max(lg, -1), jnp.argmax(lg, -1), got

        n = idx.shape[0] // self.HEAD_ROWS
        x = rows.reshape(n, self.HEAD_ROWS, -1)
        t = toks.reshape(toks.shape[0], n, self.HEAD_ROWS).transpose(1, 0, 2)
        best, arg, got = jax.lax.map(chunk, (x, t))
        return best.reshape(-1), arg.reshape(-1), \
            got.transpose(1, 0, 2).reshape(toks.shape[0], idx.shape[0])

    def evaluate(self, seqs: Sequence[Sequence[int]],
                 rows: Sequence[Sequence[int]], lengths: Sequence[int],
                 max_rows: int, token_sets: Sequence[Sequence[int]] = ()
                 ) -> Dict[str, np.ndarray]:
        """Over rows[i] of sequence i, in order (N rows in all): the best
        logit "best" (N,), its token "argmax" (N,), and "got" (S, N), the
        logit of token_sets[s][n] at row n. `lengths` are the padded
        lengths to use (ascending, the last holding the longest sequence);
        no sequence has more than max_rows rows."""
        groups: Dict[int, List[int]] = {}
        for i, s in enumerate(seqs):
            L = next((n for n in lengths if n >= len(s)), None)
            if L is None or len(rows[i]) > max_rows:
                raise ValueError(f"sequence of {len(s)} tokens and "
                                 f"{len(rows[i])} rows does not fit "
                                 f"lengths {lengths}, max_rows {max_rows}")
            groups.setdefault(L, []).append(i)
        starts = np.cumsum([0] + [len(r) for r in rows])
        sets = np.asarray(token_sets, np.int32).reshape(len(token_sets),
                                                        int(starts[-1]))
        nh = self.c["num_attention_heads"]
        batches = []        # (sequence indices, tokens (b, L))
        for L, idx in sorted(groups.items()):
            b = max(1, min(8, SCORE_BUDGET // (4 * nh * L * L)))
            for j in range(0, len(idx), b):
                part = idx[j:j + b]
                toks = np.zeros((b, L), np.int32)
                for r, i in enumerate(part):
                    toks[r, :len(seqs[i])] = seqs[i]
                batches.append((part, toks))
        g = self._globals(self.key)
        hs = [_store(jnp.take(g["embed"], jnp.asarray(t), axis=0),
                     self.quant) for _, t in batches]
        for layer in range(self.c["num_hidden_layers"]):
            w = self._layer_w(self.key, layer)
            hs = [self._layer(h, w) for h in hs]
            del w
        n_all = int(starts[-1])
        out = {"best": np.zeros(n_all, np.float32),
               "argmax": np.zeros(n_all, np.int64),
               "got": np.zeros((len(sets), n_all), np.float32)}
        for (part, toks), h in zip(batches, hs):
            b, L = toks.shape
            R = -(-b * max_rows // self.HEAD_ROWS) * self.HEAD_ROWS
            idx = np.zeros(R, np.int32)
            t = np.zeros((len(sets), R), np.int32)
            dest, k = [], 0
            for r, i in enumerate(part):
                n = len(rows[i])
                idx[k:k + n] = r * L + np.asarray(rows[i])
                t[:, k:k + n] = sets[:, starts[i]:starts[i] + n]
                dest.append((k, starts[i], n))
                k += n
            best, arg, got = (np.asarray(a) for a in self._head(
                h, jnp.asarray(idx), g, jnp.asarray(t)))
            for k, s0, n in dest:
                out["best"][s0:s0 + n] = best[k:k + n]
                out["argmax"][s0:s0 + n] = arg[k:k + n]
                out["got"][:, s0:s0 + n] = got[:, k:k + n]
        return out


def served_rows(prompt: Sequence[int], output: Sequence[int]):
    """The sequence whose logits predict each served token, and the rows:
    prompt + output[:-1], rows len(prompt)-1 .. len(prompt)+len(output)-2."""
    seq = list(prompt) + list(output[:-1])
    return seq, list(range(len(prompt) - 1, len(seq)))
