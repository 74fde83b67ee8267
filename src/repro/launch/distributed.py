"""Distributed (mesh-sharded) training driver — the code path the dry-run
lowers, executed for real: params/optimizer sharded by the rule engine,
per-process batch feeding, jit with explicit in/out shardings and donation.

On a pod: call ``initialize()`` once per host (jax.distributed), build the
production mesh, and run ``train_sharded``. In this container the same path
runs on N host devices (tests use the 2x2 debug mesh via subprocess).
"""
from __future__ import annotations

import time
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.models import transformer as T
from repro.optim import adamw
from repro.optim.schedules import linear_warmup_cosine
from repro.sharding import rules as R
from repro.train.step import build_lm_train_step


def initialize(coordinator: Optional[str] = None, num_processes: int = 1,
               process_id: int = 0):
    """Multi-host init (etcd/CoreOS discovery in the 2015 stack -> JAX
    coordination service). No-op for single-process runs."""
    if coordinator and num_processes > 1:
        jax.distributed.initialize(coordinator_address=coordinator,
                                   num_processes=num_processes,
                                   process_id=process_id)


def shard_model(cfg, mesh, params, opt_state):
    """Place params + optimizer state by the rule engine's specs."""
    p_shapes = jax.eval_shape(lambda p: p, params)
    p_specs = R.param_specs(cfg, p_shapes, mesh)
    o_shapes = jax.eval_shape(lambda s: s, opt_state)
    o_specs = R.opt_state_specs(cfg, o_shapes, p_specs)
    def to(tree, specs):
        return jax.tree.map(
            lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
            tree, specs)
    return to(params, p_specs), to(opt_state, o_specs), p_specs, o_specs


def make_sharded_step(cfg, mesh, opt_update, p_specs, o_specs, batch_example,
                      *, microbatches: int = 1):
    b_shapes = jax.eval_shape(lambda b: b, batch_example)
    b_specs = R.batch_specs(cfg, b_shapes, mesh)
    step = build_lm_train_step(cfg, opt_update, microbatches=microbatches)
    metric_specs = None    # let XLA replicate scalars
    jitted = jax.jit(step,
                     in_shardings=R.to_shardings(
                         mesh, (p_specs, o_specs, b_specs)),
                     out_shardings=R.to_shardings(
                         mesh, (p_specs, o_specs, metric_specs)),
                     donate_argnums=(0, 1))
    return jitted, b_specs


def put_batch(mesh, b_specs, batch):
    return jax.tree.map(
        lambda x, s: jax.device_put(jnp.asarray(x), NamedSharding(mesh, s)),
        batch, b_specs)


def train_sharded(cfg, mesh, data: Iterable, *, num_steps: int, lr=3e-4,
                  microbatches: int = 1, seed: int = 0, log_every: int = 10,
                  verbose: bool = True):
    """End-to-end sharded training loop. Returns (params, opt_state, losses)."""
    with jax.sharding.set_mesh(mesh):
        params = T.init_lm(jax.random.PRNGKey(seed), cfg)
        opt_init, opt_update = adamw(
            linear_warmup_cosine(lr, max(num_steps // 10, 1), num_steps))
        opt_state = opt_init(params)
        params, opt_state, p_specs, o_specs = shard_model(cfg, mesh, params,
                                                          opt_state)
        it = iter(data)
        first = next(it)
        jitted, b_specs = make_sharded_step(cfg, mesh, opt_update, p_specs,
                                            o_specs, first,
                                            microbatches=microbatches)
        losses = []
        t0 = time.perf_counter()
        batch = first
        for s in range(1, num_steps + 1):
            params, opt_state, m = jitted(params, opt_state,
                                          put_batch(mesh, b_specs, batch))
            if s % log_every == 0 or s == num_steps:
                losses.append(float(m["loss"]))
                if verbose:
                    print(f"  [sharded] step {s} loss {losses[-1]:.4f}")
            if s < num_steps:
                batch = next(it)
        if verbose:
            print(f"  [sharded] {num_steps} steps in "
                  f"{time.perf_counter() - t0:.1f}s on {mesh.devices.size} "
                  f"devices")
    return params, opt_state, losses
