"""Kernel micro-benchmarks. CPU wall-clock is NOT the TPU story: these
prove the wrappers jit cleanly and record the chunked-vs-sequential SSD
ratio for reference. On CPU (no MXU) the chunked matmul form does MORE
arithmetic and can be slower; its point is turning a length-S sequential
dependency into S/chunk matmul steps that the MXU executes at peak — the
dry-run FLOPs/bytes analysis, not this wall-clock, is the TPU predictor.
The same caveat applies to the paged-attention rows: Pallas interpret mode
executes the kernel body in Python per grid cell, so its wall-clock only
proves the kernel runs; the reference-path timing shows the dense-gather
cost the kernel exists to delete (see roofline.py for the bytes story).

The one row that IS a real CPU claim is the fused decode loop: scanning
n_tokens greedy decode steps inside one jit dispatch removes per-token
host round-trips, which dominate small-model decode on any backend. That
row is machine-checked at >= 1.5x.

Results land in BENCH_kernels.json at the repo root via benchmarks._util,
like every other bench.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks._util import smoke_requested, write_bench_json
from repro.kernels.ssd_scan.ref import ssd_ref
from repro.models.mamba2 import ssd_chunked
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.ops import paged_attention


def _timeit(fn, *args, n=5):
    jax.block_until_ready(fn(*args))         # single warmup: compile + run
    t0 = time.perf_counter()
    for _ in range(n):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / n


def _paged_case(n_pages, bs, B=4, nkv=2, hd=64, seed=7):
    """Every slot holds a full chain of n_pages pages (worst case for the
    dense gather: the whole table materializes)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    P = B * n_pages + 1
    kpool = jax.random.normal(ks[0], (P, nkv, bs, hd))
    vpool = jax.random.normal(ks[1], (P, nkv, bs, hd))
    q = jax.random.normal(ks[2], (B, 2 * nkv, hd))
    table = (jnp.arange(B * n_pages, dtype=jnp.int32) + 1).reshape(B, n_pages)
    pos = jnp.full((B,), n_pages * bs - 1, jnp.int32)
    return q, kpool, vpool, table, pos


def _bench_paged_rows(smoke):
    chains = (2, 8) if smoke else (4, 16, 64)
    bs = 16
    out, json_rows = [], []
    for nb in chains:
        q, kpool, vpool, table, pos = _paged_case(nb, bs)
        def ref(*a):
            return paged_attention(*a, kernel="reference")

        def ker(*a):
            return paged_attention(*a, kernel="pallas", interpret=True)
        t_ref = _timeit(ref, q, kpool, vpool, table, pos)
        t_ker = _timeit(ker, q, kpool, vpool, table, pos)
        out.append((f"paged_attn_gather_ref_{nb * bs}tok", t_ref * 1e6,
                    f"dense gather over {nb}-page chains"))
        out.append((f"paged_attn_pallas_{nb * bs}tok", t_ker * 1e6,
                    "interpret mode (Python per page — proves the kernel, "
                    "not the speed; bytes story in roofline)"))
        json_rows.append({
            "cell": f"paged_attn_{nb * bs}tok", "chain_pages": nb,
            "block_size": bs, "chain_tokens": nb * bs,
            "ref_gather_us": t_ref * 1e6, "pallas_interpret_us": t_ker * 1e6,
        })
    return out, json_rows


def _bench_fused_decode(smoke):
    """Fused multi-token decode vs the per-token step loop, decode phase
    only, all-greedy batch on the paged layout. Reports wall-clock per
    generated token and the jit-dispatch counts behind the gap.

    A deliberately small 1-layer model isolates the loop machinery: the
    per-dispatch cost being deleted (jit call + host<->device transfers +
    engine bookkeeping) is shape-independent, while per-token device
    compute is identical on both paths — a big model would only bury the
    measured quantity under matmul time. (On TPU the same hoisting removes
    the host round-trip that leaves the device idle between tokens.)"""
    from repro.configs.base import ModelConfig
    from repro.models import transformer as T
    from repro.serve.engine import ServeEngine

    n_fused = 8
    max_new = 17 if smoke else 33            # budget after 1st = 16 / 32
    slots = 4
    cfg = ModelConfig("bench", "dense", 1, 64, 2, 1, 128, 97)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    prompts = [[(7 * i + j) % cfg.vocab_size for j in range(5)]
               for i in range(slots)]
    cache_len = 8 + max_new + (-(8 + max_new)) % 16

    def drive(fused_tokens):
        eng = ServeEngine(params, cfg, batch_slots=slots,
                          cache_len=cache_len, prefill_mode="bulk",
                          kv_layout="paged", fused_tokens=fused_tokens)

        def once():
            reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
            eng._admit()                     # prefill outside the clock
            dispatches = 0
            t0 = time.perf_counter()
            while eng.has_work():
                eng.step()
                dispatches += 1
            dt = time.perf_counter() - t0
            return [r.output for r in reqs], dt, dispatches

        once()       # warm THIS engine's jit traces (compile off the clock)
        runs = [once() for _ in range(3)]
        outs = {tuple(map(tuple, o)) for o, _, _ in runs}
        if len(outs) != 1:
            raise AssertionError("decode loop is not deterministic")
        # best-of-3: the bar below is machine-checked in CI, where a
        # single scheduler hiccup on a shared runner would otherwise flake
        # a few-millisecond timed region
        _, dt, dispatches = min(runs, key=lambda r: r[1])
        return runs[0][0], dt, dispatches

    out_single, t_single, d_single = drive(1)
    out_fused, t_fused, d_fused = drive(n_fused)
    if out_fused != out_single:
        raise AssertionError("fused decode diverged from single-step")
    gain = t_single / t_fused
    if gain < 1.5:
        # the acceptance bar is machine-checked: fused dispatch must
        # actually delete per-token host overhead, not just exist
        raise AssertionError(
            f"fused decode loop only {gain:.2f}x vs single-step "
            f"(bar is 1.5x at n_tokens={n_fused})")
    n_tok = sum(len(o) for o in out_single)
    rows = [
        ("decode_loop_single_step", t_single / n_tok * 1e6,
         f"{d_single} dispatches for {n_tok} tokens"),
        ("decode_loop_fused8", t_fused / n_tok * 1e6,
         f"{d_fused} dispatches for {n_tok} tokens ({gain:.2f}x faster)"),
    ]
    json_rows = [{
        "cell": f"decode_loop_fused{n_fused}", "n_tokens_per_dispatch":
        n_fused, "slots": slots, "max_new": max_new,
        "generated_tokens": n_tok,
        "single_dispatches": d_single, "fused_dispatches": d_fused,
        "single_wall_s": t_single, "fused_wall_s": t_fused,
        "speedup_x": gain, "outputs_match": True,
        "arch": cfg.arch_id, "decode_kernel": "reference",
    }]
    return rows, json_rows


def run(smoke: bool = False) -> list:
    smoke = smoke or smoke_requested()
    key = jax.random.PRNGKey(0)
    b, s, h, p, n = (1, 128, 2, 32, 32) if smoke else (2, 512, 4, 64, 64)
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h))) * 0.1
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.5)
    B = jax.random.normal(ks[3], (b, s, 1, n))
    C = jax.random.normal(ks[4], (b, s, 1, n))

    seq = jax.jit(lambda *a: ssd_ref(*a))
    chk = jax.jit(lambda *a: ssd_chunked(*a, 128))
    t_seq = _timeit(seq, x, dt, A, B, C)
    t_chk = _timeit(chk, x, dt, A, B, C)

    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k = jax.random.normal(ks[1], (1, 256, 2, 64))
    v = jax.random.normal(ks[2], (1, 256, 2, 64))
    att = jax.jit(lambda *a: attention_ref(*a))
    t_att = _timeit(att, q, k, v)

    out = [
        ("ssd_sequential_scan", t_seq * 1e6, f"seq={s}"),
        ("ssd_chunked_matmul", t_chk * 1e6,
         f"{t_seq / t_chk:.2f}x vs sequential on CPU (matmul form; wins on "
         f"MXU, see roofline)"),
        ("attention_ref_256", t_att * 1e6, "oracle path"),
    ]
    json_rows = [
        {"cell": "ssd_sequential_scan", "us": t_seq * 1e6, "seq": s},
        {"cell": "ssd_chunked_matmul", "us": t_chk * 1e6,
         "ratio_vs_seq": t_seq / t_chk},
        {"cell": "attention_ref_256", "us": t_att * 1e6},
    ]

    paged_out, paged_json = _bench_paged_rows(smoke)
    fused_out, fused_json = _bench_fused_decode(smoke)
    out += paged_out + fused_out
    json_rows += paged_json + fused_json
    write_bench_json("kernels", json_rows,
                     meta={"smoke_shapes": bool(smoke)}, smoke=smoke)
    return out
