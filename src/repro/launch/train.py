"""Training launcher: --arch <id> [--reduced] --steps N ...

On the container (1 CPU) use --reduced; on a pod slice the full config and
the production mesh apply (the same code path the dry-run lowers).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.data.tokens import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as T
from repro.optim import adamw
from repro.optim.schedules import linear_warmup_cosine
from repro.train.step import build_lm_train_step
from repro.train.trainer import train_loop


def stub_inputs(cfg, bs, seq):
    out = {}
    if cfg.is_encdec:
        out["enc_embeds"] = jnp.zeros((bs, max(seq // 2, 4), cfg.d_model),
                                      cfg.activation_dtype)
    elif cfg.embed_stub:
        out["embeds"] = jnp.zeros((bs, max(seq // 4, 2), cfg.d_model),
                                  cfg.activation_dtype)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = registry.get(args.arch, reduced=args.reduced)
    print(f"[train] {cfg.arch_id} reduced={args.reduced} "
          f"params~{cfg.param_count()/1e6:.1f}M backend={jax.default_backend()}")
    params = T.init_lm(jax.random.PRNGKey(args.seed), cfg)
    opt_init, opt_update = adamw(
        linear_warmup_cosine(args.lr, args.steps // 10 + 1, args.steps))
    opt_state = opt_init(params)
    step = build_lm_train_step(cfg, opt_update,
                               microbatches=args.microbatches)
    stream = TokenStream(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    stubs = stub_inputs(cfg, args.batch, args.seq)

    def data():
        for b in stream:
            yield {**{k: jnp.asarray(v) for k, v in b.items()}, **stubs}

    params, opt_state, log = train_loop(
        jax.jit(step, donate_argnums=(0, 1)), params, opt_state, data(),
        num_steps=args.steps, log_every=max(args.steps // 20, 1),
        ckpt_dir=args.ckpt_dir, ckpt_every=max(args.steps // 2, 1))
    print(f"[train] loss {log.losses[0]:.4f} -> {log.losses[-1]:.4f} "
          f"over {args.steps} steps")


if __name__ == "__main__":
    main()
