"""The system under test, as the benchmark drives it: the repository's
`Gateway` over one `ServeEngine` replica with the engine settings the
configuration file states, and the model's parameters laid out as the
program expects them. This is the only module that imports the program.
"""
from __future__ import annotations

import os
import sys

import jax

from . import weights as W


class ProgramMissing(Exception):
    """The repository's sources are not beside the benchmark."""


def import_program(checkout: str):
    src = os.path.join(checkout, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise ProgramMissing(f"the program's sources are not at {src}")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(c: dict):
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


def _program_tree(key, c: dict):
    """The seed's weights in the program's parameter tree: every layer
    stacked on a leading axis (the program scans over them); norm tensors
    are the program's (1 + scale) offsets, which is how `weights` draws
    them."""
    g = W.globals_(key, c)
    L = W.stacked_layers(key, c)
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


def make_params(c: dict, mcfg, seed: int):
    """The parameters, made on the device in one jitted call. Raises if
    the tree differs in any leaf's place, shape or dtype from the one the
    program's own initializer builds."""
    from repro.models import transformer as T
    key = W.base_key(seed)
    want = jax.eval_shape(lambda k: T.init_lm(k, mcfg), key)
    got = jax.eval_shape(lambda k: _program_tree(k, c), key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter tree has changed: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
    return jax.block_until_ready(
        jax.jit(lambda k: _program_tree(k, c))(key))


def build_gateway(params, mcfg, serving: dict):
    """One replica on its own worker thread, behind the durable queue."""
    from repro.gateway.gateway import Gateway
    gw = Gateway.build(
        params, mcfg, replicas=1, batch_slots=serving["batch_slots"],
        cache_len=serving["cache_len"], block_size=serving["block_size"],
        pool_blocks=serving["pool_blocks"], kv_layout=serving["kv_layout"],
        prefill_mode=serving["prefill_mode"],
        decode_kernel=serving["decode_kernel"], async_workers=True)
    gw.start_workers()
    return gw


def prefill_buckets(serving: dict, lengths) -> list:
    """The prompt lengths the program pads prefills to, for these prompt
    lengths."""
    from repro.serve.step import bucket_len
    return sorted({bucket_len(n, serving["cache_len"]) for n in lengths})
