"""The plain float32 reference forward, independent of the program.

The architecture's steps (`archs/<model_type>.py`: `embed`, `layer`,
`head`) driven over whole sequences: no cache, no kernel, no batching of
slots, one full forward over each whole sequence.

Weights come from the seed (`weights`, with the architecture's lists of
tensors), one layer at a time, in the served dtype and then widened to
float32; every matmul runs at HIGHEST precision, so on a TPU nothing is
computed below float32. Sequences are padded at the end to a few fixed
lengths (causal layers keep padding out of every real position) and the
logits are formed only at the rows asked for.

`quant="int8"` is the control, the reference computed in int8 where the
program computes in bfloat16: every weight matrix held in int8 (per output
channel), every matmul taking int8 operands with int32 accumulation
(`mm`), and every tensor the program stores in bfloat16 (the residual
stream, the norms' outputs, q, k and v, each matmul's output, the MLP's
products, the logits) stored in int8 with one symmetric scale per row
(`store`). Softmax and the norms' arithmetic stay in float32, as the
program's do. An architecture's steps use `mm` and `store` for this.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HI = jax.lax.Precision.HIGHEST
SCORE_BUDGET = 1 << 30      # bytes of attention scores per batch


def int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s > 0, s, 1.0)
    return jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8), s


def store(x, quant):
    """x as the control would hold it: int8 with one scale per row."""
    if quant is None:
        return x
    q, s = int8(x, -1)
    return q.astype(jnp.float32) * s


def mm(x, w, quant):
    if quant is None:
        return jnp.einsum("...i,io->...o", x, w, precision=HI)
    xq, sx = int8(x, -1)
    wq, sw = int8(w, 0)
    acc = jnp.einsum("...i,io->...o", xq, wq,
                     preferred_element_type=jnp.int32)
    return store(acc.astype(jnp.float32) * sx * sw[0], quant)


def _f32(tree):
    return {k: v.astype(jnp.float32) for k, v in tree.items()}


class Reference:
    """The forward for one architecture, configuration and seed."""

    HEAD_ROWS = 256     # rows of logits formed at a time

    def __init__(self, arch, c: dict, seed: int,
                 quant: Optional[str] = None):
        if quant not in (None, "int8"):
            raise ValueError(f"quant must be None or 'int8', got {quant!r}")
        self.arch, self.c, self.quant = arch, c, quant
        self.key = W.base_key(seed)
        self._globals = jax.jit(
            lambda key: _f32(W.globals_(key, c, arch.global_tensors(c))))
        self._draws = {}    # one jitted draw per list of layer tensors
        self._layer = jax.jit(lambda h, w: arch.layer(h, w, c, quant))
        self._head = jax.jit(self._head_fn)

    def _layer_w(self, i: int):
        tensors = tuple(self.arch.layer_tensors(self.c, i))
        if tensors not in self._draws:
            self._draws[tensors] = jax.jit(
                lambda key, i: _f32(W.layer(key, self.c, tensors, i)))
        return self._draws[tensors](self.key, i)

    def _head_fn(self, h, idx, g, toks):
        """h: (b, L, d); idx: (R,) flat row indices, R a multiple of
        HEAD_ROWS; toks: (S, R). Returns each row's best logit, its argmax
        and the logit of each of the S tokens asked for."""
        rows = jnp.take(h.reshape(-1, h.shape[-1]), idx, axis=0)
        logits = self.arch.head(g, self.c, self.quant)

        def chunk(args):
            x, t = args
            lg = logits(x)
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
        hs = [self.arch.embed(g, jnp.asarray(t), self.c, self.quant)
              for _, t in batches]
        for layer in range(self.c["num_hidden_layers"]):
            w = self._layer_w(layer)
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
