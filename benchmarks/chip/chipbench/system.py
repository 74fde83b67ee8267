"""The system under test, as the benchmark drives it: the repository's
`Gateway` over one `ServeEngine` replica with the engine settings the
configuration file states, and the model's parameters laid out as the
program expects them (by the architecture's module, `archs/<model_type>.py`,
whose program half only this module calls). This is the only module that
imports the program.
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


def make_params(arch, c: dict, mcfg, seed: int):
    """The parameters, made on the device in one jitted call. Raises if
    the tree differs in any leaf's place, shape or dtype from the one the
    program's own initializer builds."""
    from repro.models import transformer as T
    key = W.base_key(seed)
    want = jax.eval_shape(lambda k: T.init_lm(k, mcfg), key)
    got = jax.eval_shape(lambda k: arch.program_params(k, c), key)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError("the program's parameter tree has changed: "
                         f"{jax.tree.map(lambda a: a.shape, want)}")
    return jax.block_until_ready(
        jax.jit(lambda k: arch.program_params(k, c))(key))


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
