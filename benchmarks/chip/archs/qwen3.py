"""The dense decoder, as the published Qwen3 and Mistral code defines it
(`archs/mistral.py` is this module under Mistral's `model_type`).

RMSNorm with weight, grouped-query attention with rotate-half RoPE (base
rope_theta) on q and k, per-head RMSNorm of q and k before RoPE where the
config says qk_norm (Qwen3), a causal softmax, a SwiGLU MLP, a final norm
and an output head that is the embedding (tied) or its own matrix. Every
layer is of the one kind.

A module of `archs/` gives the harness all that depends on the
architecture, under the names in `__all__`; `chipbench.spec.arch` finds it
by the configuration's `model_type`:

    program_config(c)       the program's ModelConfig (imports the program)
    program_params(key, c)  the program's parameter tree from the seed's
                            tensors, for one jitted call (imports nothing
                            of the program itself)
    layer_tensors(c, i)     layer i's tensors, and the global ones, as
    global_tensors(c)       (name, shape, kind) for `chipbench.weights`
    embed(g, tokens, c, quant), layer(h, w, c, quant), head(g, c, quant)
                            the plain reference's steps, in float32 at
                            HIGHEST, or as the int8 control (quant="int8",
                            `chipbench.reference`); nothing of the program
    work(c)                 model FLOPs and the paged kernel's work
                            (`chipbench.work`)
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from chipbench import weights as W
from chipbench.reference import HI, mm, store

__all__ = ["program_config", "program_params", "layer_tensors",
           "global_tensors", "embed", "layer", "head", "work"]


def program_config(c: dict):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        arch_id=c["name"], family="dense",
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        qk_norm=c["qk_norm"], rope_theta=float(c["rope_theta"]),
        tie_embeddings=c["tie_word_embeddings"],
        norm_eps=c["rms_norm_eps"], act=c["hidden_act"],
        dtype=c["torch_dtype"], param_dtype=c["torch_dtype"],
        source=c["source"])


def program_params(key, c: dict):
    """The seed's weights in the program's parameter tree: every layer
    stacked on a leading axis (the program scans over them); norm tensors
    are the program's (1 + scale) offsets, which is how `weights` draws
    them."""
    g = W.globals_(key, c, global_tensors(c))
    L = W.stacked_layers(key, c, layer_tensors(c, 0),
                         range(c["num_hidden_layers"]))
    attn = {"wq": L["q_proj"], "wk": L["k_proj"], "wv": L["v_proj"],
            "wo": L["o_proj"]}
    if c["qk_norm"]:
        attn["q_norm"] = {"scale": L["q_norm"]}
        attn["k_norm"] = {"scale": L["k_norm"]}
    block = {"norm1": {"scale": L["input_norm"]}, "attn": attn,
             "norm2": {"scale": L["post_norm"]},
             "ffn": {"w_gate": L["gate_proj"], "w_up": L["up_proj"],
                     "w_down": L["down_proj"]}}
    p = {"embed": {"table": g["embed"]}, "blocks": (block,), "tail": (),
         "final_norm": {"scale": g["final_norm"]}}
    if not c["tie_word_embeddings"]:
        p["lm_head"] = g["lm_head"]
    return p


def layer_tensors(c: dict, i) -> list:
    """Names follow the published checkpoints; norm weights are drawn as
    the offset u of the weight 1 + u."""
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


def global_tensors(c: dict) -> list:
    d, v = c["hidden_size"], c["vocab_size"]
    out = [("embed", (v, d), "embed"), ("final_norm", (d,), "norm")]
    if not c["tie_word_embeddings"]:
        out.append(("lm_head", (d, v), "dense"))
    return out


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x: (b, L, h, hd) at positions 0..L-1."""
    L, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(g, tokens, c: dict, quant):
    """tokens: (b, L) -> the residual stream (b, L, d)."""
    return store(jnp.take(g["embed"], tokens, axis=0), quant)


def layer(h, w, c: dict, quant):
    b, L, _ = h.shape
    nh, nkv, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    eps = c["rms_norm_eps"]
    x = store(rms(h, 1.0 + w["input_norm"], eps), quant)
    q = mm(x, w["q_proj"], quant).reshape(b, L, nh, hd)
    k = mm(x, w["k_proj"], quant).reshape(b, L, nkv, hd)
    v = mm(x, w["v_proj"], quant).reshape(b, L, nkv, hd)
    if c["qk_norm"]:
        q = rms(q, 1.0 + w["q_norm"], eps)
        k = rms(k, 1.0 + w["k_norm"], eps)
    q, k = rope(q, c["rope_theta"]), rope(k, c["rope_theta"])
    q, k, v = store(q, quant), store(k, quant), store(v, quant)
    k = jnp.repeat(k, nh // nkv, axis=2)        # head i reads kv head i//rep
    v = jnp.repeat(v, nh // nkv, axis=2)
    s = jnp.einsum("blhd,bmhd->bhlm", q, k, precision=HI) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhlm,bmhd->blhd", p, v, precision=HI)
    h = store(h + mm(o.reshape(b, L, nh * hd), w["o_proj"], quant), quant)
    x = store(rms(h, 1.0 + w["post_norm"], eps), quant)
    up = store(jax.nn.silu(mm(x, w["gate_proj"], quant)), quant) \
        * mm(x, w["up_proj"], quant)
    return store(h + mm(store(up, quant), w["down_proj"], quant), quant)


def head(g, c: dict, quant):
    """The output step: a function from rows x (n, d) of the last layer's
    output to their logits (n, V), its matrix formed once outside it."""
    w = g["embed"].T if c["tie_word_embeddings"] else g["lm_head"]

    def logits(x):
        x = store(rms(x, 1.0 + g["final_norm"], c["rms_norm_eps"]), quant)
        return mm(x, w, quant)
    return logits


@dataclass(frozen=True)
class Work:
    """The sizes of the decoder that the counts need. Counted as the work
    the computation requires: the paged-decode kernel is charged for the
    live K/V tokens of each slot (and its q and output), a prefill for its
    real tokens, not for the padding of its bucket."""
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    itemsize: int = 2       # bytes per element of K/V, q and the output

    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * (self.n_heads + 2 * self.n_kv_heads) * hd \
            + self.n_heads * hd * d
        return attn + 3 * d * self.d_ff

    def paged_attention_call(self, live: Sequence[int]
                             ) -> Tuple[float, float]:
        """(FLOPs, bytes) of one decode-attention call over one layer: each
        live slot's query attends its `live` keys (QK^T and PV, 2 FLOPs per
        multiply-add), reading each live K and V row once, its q once and
        writing its output once."""
        nh, nkv, hd, b = self.n_heads, self.n_kv_heads, self.head_dim, \
            self.itemsize
        tokens = sum(live)
        flops = 4.0 * nh * hd * tokens
        bytes_ = 2.0 * nkv * hd * b * tokens + 2.0 * nh * hd * b * len(live)
        return flops, bytes_

    def decode_token_flops(self, live: int) -> float:
        """Model FLOPs of one decoded token that attends `live` keys: every
        matmul of every layer, attention, and one row of logits."""
        per_layer = 2.0 * self.layer_matmul_params \
            + 4.0 * self.n_heads * self.head_dim * live
        return self.n_layers * per_layer + 2.0 * self.d_model * self.vocab

    def prefill_flops(self, start: int, n_tok: int) -> float:
        """Model FLOPs of prefilling n_tok real tokens at positions start ..
        start + n_tok - 1 (causal: position p attends p + 1 keys), and the
        one row of logits the first generated token needs."""
        keys = n_tok * start + n_tok * (n_tok + 1) / 2.0
        per_layer = 2.0 * self.layer_matmul_params * n_tok \
            + 4.0 * self.n_heads * self.head_dim * keys
        return self.n_layers * per_layer + 2.0 * self.d_model * self.vocab


def work(c: dict) -> Work:
    return Work(d_model=c["hidden_size"], n_layers=c["num_hidden_layers"],
                n_heads=c["num_attention_heads"],
                n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                itemsize=2 if c["torch_dtype"] == "bfloat16" else 4)
