"""Weights drawn from the seed, tensor by tensor, in the dtype they are
served in.

Each tensor has its own key: fold_in(fold_in(fold_in(seed key, group),
layer), tensor), so the program's copy (every layer at once, on the device,
in one jitted call) and the reference's (one layer at a time, after the
window) are the same numbers without either handing them to the other.
Keys are of JAX's "rbg" kind, whose bits come from XLA's RngBitGenerator:
far cheaper on a TPU than threefry, and the same for a key and a shape in
any program, though not under vmap, so layers are drawn in a loop.

Names follow the published checkpoints (q_proj, ..., down_proj). Norm
tensors are drawn as the offset u of the weight 1 + u, so that random norm
weights are exercised as well as the identity.
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

GLOBAL, LAYER = 0, 1
NORM_SPREAD = 0.2       # norm weights are 1 + U(-0.2, 0.2)
EMBED_STD = 0.02        # embedding rows ~ N(0, 0.02^2)


def base_key(seed: int):
    """A key for any seed below 2**62 (a key from an int keeps 32 bits)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 62:
        raise ValueError(f"seed {seed} out of range")
    return jax.random.fold_in(
        jax.random.key(seed & 0xFFFFFFFF, impl="rbg"), seed >> 32)


def dtype_of(c: dict):
    return jnp.dtype(c["torch_dtype"])


def layer_tensors(c: dict) -> List[Tuple[str, tuple, str]]:
    d, hd, ff = c["hidden_size"], c["head_dim"], c["intermediate_size"]
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    out = [("input_norm", (d,), "norm"),
           ("q_proj", (d, nh * hd), "dense"),
           ("k_proj", (d, nkv * hd), "dense"),
           ("v_proj", (d, nkv * hd), "dense"),
           ("o_proj", (nh * hd, d), "dense")]
    if c["qk_norm"]:
        out += [("q_norm", (hd,), "norm"), ("k_norm", (hd,), "norm")]
    return out + [("post_norm", (d,), "norm"),
                  ("gate_proj", (d, ff), "dense"),
                  ("up_proj", (d, ff), "dense"),
                  ("down_proj", (ff, d), "dense")]


def global_tensors(c: dict) -> List[Tuple[str, tuple, str]]:
    d, v = c["hidden_size"], c["vocab_size"]
    out = [("embed", (v, d), "embed"), ("final_norm", (d,), "norm")]
    if not c["tie_word_embeddings"]:
        out.append(("lm_head", (d, v), "dense"))
    return out


def _draw(key, shape, kind, dtype):
    if kind == "dense":
        s = 1.0 / math.sqrt(shape[0])
        return jax.random.uniform(key, shape, dtype, -s, s)
    if kind == "norm":
        return jax.random.uniform(key, shape, dtype, -NORM_SPREAD,
                                  NORM_SPREAD)
    if kind == "embed":
        return (jax.random.normal(key, shape, dtype) * EMBED_STD).astype(dtype)
    raise ValueError(kind)


def layer(key, c: dict, i) -> Dict[str, jax.Array]:
    """Layer i's tensors (i may be a traced loop index)."""
    k = jax.random.fold_in(jax.random.fold_in(key, LAYER), i)
    return {name: _draw(jax.random.fold_in(k, j), shape, kind, dtype_of(c))
            for j, (name, shape, kind) in enumerate(layer_tensors(c))}


def stacked_layers(key, c: dict) -> Dict[str, jax.Array]:
    """Every layer's tensors stacked on a leading axis, drawn one layer at
    a time into buffers updated in place (no second copy of the model)."""
    n = c["num_hidden_layers"]
    one = jax.eval_shape(lambda: layer(key, c, 0))
    init = {k: jnp.zeros((n,) + v.shape, v.dtype) for k, v in one.items()}

    def body(i, acc):
        w = layer(key, c, i)
        return {k: acc[k].at[i].set(w[k]) for k in acc}
    return jax.lax.fori_loop(0, n, body, init)


def globals_(key, c: dict) -> Dict[str, jax.Array]:
    k = jax.random.fold_in(key, GLOBAL)
    return {name: _draw(jax.random.fold_in(k, j), shape, kind, dtype_of(c))
            for j, (name, shape, kind) in enumerate(global_tensors(c))}
