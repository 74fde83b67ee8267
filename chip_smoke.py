"""Smoke run of this repository on TPU: qwen3-1.7b at its published widths
(28 layers, d_model 2048, GQA 16/8, head_dim 128, vocab 151,936) in bf16,
with random weights from a fixed seed.

    python chip_smoke.py               # one chip: serve through the gateway
    python chip_smoke.py --four-chips  # four chips: sharded training only

One chip: `Gateway.build` with the paged KV pool, bulk prefill and the
Pallas paged-decode kernel serves greedy and seeded sampled requests
(16-300 prompt tokens, two sharing a prefix). Every request must finish
with its full token count on a healthy replica. The kernel's attention
output over the served pool must match the dense-gather reference, and the
first greedy token of each request is compared with a dense-cache engine.

Four chips: `launch.distributed.train_sharded` takes a few steps on a 2x2
("data", "model") mesh and on one device, with the same batches and seed
(qwen3-1.7b widths, 4 layers). The losses must agree and the parameters
must really be split over the four chips.

Phase lines go first. The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
With no TPU, without the repository's sources beside this file, or when a
phase fails, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

ARCH = "qwen3-1.7b"
SEED = 0
BATCH_SLOTS, CACHE_LEN = 8, 1024
# greedy requests as (prompt length, max new tokens); the last one shares
# its first 192 tokens with the 300-token prompt
GREEDY = [(16, 32), (40, 16), (64, 24), (100, 16), (150, 20), (200, 16),
          (300, 24), (260, 32)]
SHARED_PREFIX = 192
SAMPLED = (48, 24)
# Kernel vs reference: both accumulate in f32 and round the output to
# bf16, so they may differ by one bf16 ulp; allow two (2**-6 relative to
# the output's largest magnitude).
KERNEL_RTOL = 2.0 ** -6
# Sharded vs one-device training losses after a few AdamW steps in bf16:
# the partitioned reductions round differently. About one bf16 ulp (2**-7)
# of the loss.
LOSS_RTOL = 1e-2


class SmokeFailure(Exception):
    """A phase produced a wrong or missing result."""


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu():
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(f"no TPU: JAX found {len(devs)} "
                           f"{devs[0].platform} device(s)")
    return devs


def import_repo():
    if not os.path.isdir(os.path.join(SRC, "repro")):
        raise SmokeFailure(f"the repository's sources are not at {SRC}")
    if SRC not in sys.path:
        sys.path.insert(0, SRC)


class CompileClock:
    """Sums backend compile time and counts compiles inside its `with`."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def _on_event(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def make_requests(vocab: int, seed: int):
    """[(prompt, max_new_tokens, SamplingParams)], greedy ones first."""
    import numpy as np
    from repro.gateway.sampler import SamplingParams
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, vocab, n).tolist() for n, _ in GREEDY]
    prompts[-1][:SHARED_PREFIX] = prompts[-2][:SHARED_PREFIX]
    out = [(p, m, SamplingParams()) for p, (_, m) in zip(prompts, GREEDY)]
    n, m = SAMPLED
    out.append((rng.integers(0, vocab, n).tolist(), m,
                SamplingParams(temperature=0.8, top_k=50, seed=seed + 1)))
    return out


def check_served(gw, handles, requests):
    """Every request done with its full token count, every replica healthy
    and never failed: the gateway fails a raising replica forward, so its
    recorded error is the only trace of a device failure."""
    problems = []
    for r in gw.replicas:
        if not r.healthy or r.failures:
            problems.append(f"replica {r.replica_id} failed "
                            f"{r.failures}x: {r.last_error}")
    for h, (_, max_new, _) in zip(handles, requests):
        if h.status != "done" or len(h.output) != max_new:
            problems.append(f"request {h.gid}: status {h.status}, "
                            f"{len(h.output)}/{max_new} tokens")
    if problems:
        raise SmokeFailure("; ".join(problems))


def serve(params, cfg, requests, **engine_kw):
    """Drive one gateway to completion; returns (gateway, handles)."""
    from repro.gateway.gateway import Gateway
    gw = Gateway.build(params, cfg, batch_slots=BATCH_SLOTS,
                       cache_len=CACHE_LEN, prefill_mode="bulk", **engine_kw)
    handles = [gw.submit(p, max_new_tokens=m, sampling=s)
               for p, m, s in requests]
    gw.run()
    gw.shutdown()
    check_served(gw, handles, requests)
    return gw, handles


def kernel_check(pool_k, pool_v, cfg, seed: int) -> float:
    """One decode step's attention over a served layer's pool: the Pallas
    kernel against the dense-gather reference. Returns max abs error."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention.ops import paged_attention
    P, nkv, bs, hd = pool_k.shape
    nb = CACHE_LEN // bs
    written = np.flatnonzero(np.abs(np.asarray(pool_k, np.float32))
                             .sum(axis=(1, 2, 3)) > 0)
    written = written[written > 0]
    if written.size == 0:
        raise SmokeFailure("kernel check: the served pool holds no KV")
    rng = np.random.default_rng(seed)
    table = rng.choice(written, size=(BATCH_SLOTS, nb))
    pos = np.linspace(0, CACHE_LEN - 1, BATCH_SLOTS).astype(np.int32)
    q = jax.random.normal(jax.random.PRNGKey(seed),
                          (BATCH_SLOTS, cfg.n_heads, hd), cfg.activation_dtype)
    args = (q, pool_k, pool_v, jnp.asarray(table, jnp.int32),
            jnp.asarray(pos, jnp.int32))
    out = np.asarray(paged_attention(*args, kernel="pallas"), np.float32)
    ref = np.asarray(paged_attention(*args, kernel="reference"), np.float32)
    if not np.isfinite(out).all():
        raise SmokeFailure("kernel check: non-finite kernel output")
    err = float(np.abs(out - ref).max())
    tol = KERNEL_RTOL * max(1.0, float(np.abs(ref).max()))
    log(f"kernel vs reference: max abs err {err!r} (tolerance {tol!r}, "
        f"{written.size} written pages of {P})")
    if err > tol:
        raise SmokeFailure(f"kernel check: max abs err {err} > {tol}")
    return err


def serve_phase(cfg, seed: int = SEED):
    """The one-chip phase; raises SmokeFailure on any wrong result."""
    import jax
    from repro.models import transformer as T

    t0 = time.perf_counter()
    params = jax.jit(T.init_lm, static_argnums=1)(
        jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"init: {n_params} params ({cfg.param_dtype}) in "
        f"{time.perf_counter() - t0!r} s")

    requests = make_requests(cfg.vocab_size, seed)
    t0 = time.perf_counter()
    gw, handles = serve(params, cfg, requests, kv_layout="paged",
                        decode_kernel="pallas")
    log(f"serve paged+pallas: {len(handles)} requests, "
        f"{sum(len(h.output) for h in handles)} tokens in "
        f"{time.perf_counter() - t0!r} s, compiles included")
    for h, (p, m, s) in zip(handles, requests):
        log(f"  request {h.gid}: prompt {len(p)}, "
            f"{'greedy' if s.is_greedy else 'sampled'}, "
            f"{len(h.output)}/{m} tokens, status {h.status}")
    kv = gw.kvcache_summary()
    log(f"kvcache: {kv}")
    if not kv["tokens_reused"]:
        raise SmokeFailure("the shared-prefix request reused no pages")

    pool = gw.replicas[0].engine.cache["blocks"][0]
    kernel_check(pool["k"][0], pool["v"][0], cfg, seed)
    paged_first = [h.output[0] for h, (_, _, s) in zip(handles, requests)
                   if s.is_greedy]
    del gw, handles, pool

    greedy = [(p, 1, s) for p, _, s in requests if s.is_greedy]
    t0 = time.perf_counter()
    _, dense = serve(params, cfg, greedy, kv_layout="dense")
    dense_first = [h.output[0] for h in dense]
    agree = sum(a == b for a, b in zip(paged_first, dense_first))
    log(f"first greedy token, paged+pallas vs dense: {agree}/{len(greedy)} "
        f"agree (paged {paged_first}, dense {dense_first}; "
        f"{time.perf_counter() - t0!r} s)")


def four_chip_phase(cfg, *, steps: int = 4, batch: int = 8, seq: int = 256,
                    seed: int = SEED):
    """train_sharded on a 2x2 mesh against one device, same batches."""
    import jax
    import numpy as np
    from jax.sharding import AxisType
    from repro.data.tokens import TokenStream
    from repro.launch.distributed import train_sharded
    from repro.launch.mesh import make_debug_mesh

    meshes = {"2x2": make_debug_mesh(),
              "1": jax.make_mesh((1, 1), ("data", "model"),
                                 (AxisType.Auto,) * 2,
                                 devices=jax.devices()[:1])}
    losses, params = {}, {}
    for name, mesh in meshes.items():
        t0 = time.perf_counter()
        stream = TokenStream(cfg.vocab_size, seq, batch, seed=seed)
        params[name], _, losses[name] = train_sharded(
            cfg, mesh, iter(stream), num_steps=steps, seed=seed,
            log_every=1, verbose=False)
        log(f"train_sharded on {name} device(s): losses {losses[name]!r} "
            f"in {time.perf_counter() - t0!r} s, compiles included")
    a, b = np.asarray(losses["2x2"]), np.asarray(losses["1"])
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    log(f"loss agreement: max relative difference {rel!r} "
        f"(tolerance {LOSS_RTOL!r})")
    if not (np.isfinite(a).all() and rel <= LOSS_RTOL):
        raise SmokeFailure(f"sharded losses {a} disagree with {b}")
    table = params["2x2"]["embed"]["table"]
    shards = table.addressable_shards
    shapes = sorted({s.data.shape for s in shards})
    devices = {s.device for s in shards}
    log(f"embed table {table.shape}: shard shapes {shapes} on "
        f"{len(devices)} devices")
    if table.shape in shapes or len(devices) != 4:
        raise SmokeFailure("the 2x2 run's parameters are not sharded over "
                           "four devices")


def run(four_chips: bool):
    devs = require_tpu()
    import_repo()
    import jax
    from repro.configs import registry
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    cfg = registry.get(ARCH)
    t0 = time.perf_counter()
    with CompileClock() as clock:
        if four_chips:
            if len(devs) < 4:
                raise SmokeFailure(f"--four-chips needs 4 devices, found "
                                   f"{len(devs)}")
            four_chip_phase(cfg.replace(n_layers=4))
        else:
            serve_phase(cfg)
    log(f"total {time.perf_counter() - t0!r} s; {clock.count} compiles "
        f"took {clock.seconds!r} s")
    for d in devs[:4 if four_chips else 1]:
        stats = d.memory_stats() or {}
        log(f"{d}: peak HBM {stats.get('peak_bytes_in_use', 'not reported')}"
            f" of {stats.get('bytes_limit', 'not reported')} bytes")
    return {"ok": True, "device": {"platform": devs[0].platform,
                                   "kind": devs[0].device_kind,
                                   "count": len(jax.devices())}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-training comparison on four "
                    "chips")
    args = ap.parse_args(argv)
    try:
        result = run(args.four_chips)
    except SmokeFailure as err:
        print(f"[chip_smoke] FAILED: {err}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
