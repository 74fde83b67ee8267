"""Batched serving engine: fixed-slot continuous batching.

A `ServeEngine` owns a decode cache with `batch_slots` sequences. Requests
(prompt token lists) are admitted into free slots, prefilled, then all
active slots decode in lockstep with one jitted `decode_step` per token.
Finished sequences (EOS or max_new_tokens) free their slot, and waiting
requests are admitted — continuous batching. This is the paper's "task
execution" stage re-shaped for inference: the slot pool is the worker pool,
admission is the queue pull, and a finished request "fails forward" without
disturbing its batch peers.

The jitted step returns last-position logits (not an argmax'd token): each
request carries its own `Sampler`, so slots in one lockstep batch can decode
greedy, temperature, top-k/top-p with independent seeded PRNG streams. The
engine also exposes event hooks (`on_token`, `on_finish`) that the gateway
tier uses for streaming and telemetry; they default to None and cost
nothing when unused.

KV memory is pluggable (`kv_layout`): the default "dense" layout gives each
slot a private cache strip; "paged" stores KV in refcounted block-pool
pages with a radix-tree prefix index (`repro.kvcache`), so requests sharing
a prompt prefix reuse already-prefilled pages (copy-on-write for partial
pages) instead of re-running prefill — see __init__ for the trade-offs.

Two paged-layout decode accelerators stack on top:

  * `decode_kernel="pallas"` swaps the per-token attention read from the
    dense block-table gather ("reference", the oracle of record) to the
    fused Pallas kernel (`kernels/paged_attention`) that streams KV pages
    straight from the pool with online softmax. Off-TPU the kernel body
    runs in Pallas interpret mode (Python on CPU) — same grid/BlockSpecs
    as the TPU lowering, so CPU CI executes the real kernel, just slowly;
    "reference" stays the sensible CPU production default.
  * `fused_tokens=N` (N > 1) hoists the per-token host loop: while every
    active slot is greedy, `step()` dispatches one jitted `lax.scan` of up
    to N decode steps (`serve.step.build_decode_fused`) instead of N
    jit-call round-trips, with EOS and per-slot budgets masked in-jit and
    reconciled host-side on exit. Any slot needing host-side sampling
    drops that dispatch back to single-token decode, and `on_token` hooks
    then fire in a burst of up to N tokens per dispatch.
  * `spec_tokens=K` (K >= 1) adds speculative decoding on top: a drafter
    (`serve.draft`, default self-speculative n-gram; `drafter=` accepts an
    instance or a "ngram[:n]" / "model:<arch_id>" spec) proposes K tokens
    per slot, verified by ONE batched forward over the paged cache
    (`serve.step.build_decode_spec`) that emits every draft the target
    model itself would have produced plus the free bonus token — up to
    K+1 tokens per dispatch, token-identical to greedy single-step by
    construction. Rejected drafts' KV rows are rolled back at block
    granularity: the host frontier rewinds (stale rows are masked by
    every subsequent read, then overwritten) and `KVCacheManager.rollback`
    checks no radix-shared page is in the trimmed range (copy-on-write
    safety for prefix chains). Greedy-only like the fused path — sampled
    slots drop the batch to single-token dispatch — and it takes
    precedence over `fused_tokens` when both are set. Acceptance-rate
    counters (`spec_metrics`) feed the gateway dashboard.
  * `scheduler="chunked"` (chunk_budget=N) replaces the admit-then-bulk-
    prefill admission ("phased", the default and oracle) with the token-
    budget iteration scheduler (`serve/scheduler.py`): each step with a
    partially-prefilled slot dispatches ONE fused mixed step — a lockstep
    decode over every decoding slot plus up to N prompt tokens sliced
    from an in-flight prefill (`serve.step.build_mixed_step`, one
    combined pool scatter per layer) — so a long prompt's prefill rides
    along instead of stalling every decode stream for the whole prompt
    (the head-of-line-blocking latency cliff `bench_scheduler`
    measures). First tokens are deferred to the completing chunk, and
    prompts radix-commit at every chunk boundary so concurrent same-
    prefix requests reuse pages mid-prefill. Scheduler counters
    (`scheduler_metrics`) feed the gateway dashboard.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kvcache import KVCacheManager, PoolExhausted
from repro.obs import trace as otrace
from repro.obs.registry import Histogram
from repro.models import transformer as T
from repro.serve.draft import make_drafter
from repro.serve.sampler import GREEDY, Sampler, SamplingParams
from repro.serve.scheduler import SCHEDULERS, ChunkedScheduler
from repro.serve.step import (build_decode, build_decode_fused,
                              build_decode_paged, build_decode_spec,
                              build_mixed_step, build_prefill_bucketed,
                              build_prefill_paged, bucket_len)


@dataclass
class Request:
    request_id: int
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    sampling: SamplingParams = GREEDY
    output: List[int] = field(default_factory=list)
    done: bool = False
    error: Optional[BaseException] = field(default=None, repr=False)
    # perf_counter when an engine last took it (enqueue): the start of its
    # `engine.pending` span, which its admission into a slot ends
    enqueued_t: float = field(default=0.0, repr=False, compare=False)

    def __post_init__(self):
        self._sampler = Sampler(self.sampling)

    def next_token(self, logits) -> int:
        return self._sampler.sample(logits)


class ServeEngine:
    def __init__(self, params, cfg, *, batch_slots: int = 4,
                 cache_len: int = 256, window=None,
                 prefill_mode: str = "decode", kv_layout: str = "dense",
                 block_size: int = 16, pool_blocks: Optional[int] = None,
                 decode_kernel: str = "reference", fused_tokens: int = 1,
                 spec_tokens: int = 0, drafter=None,
                 scheduler: str = "phased", chunk_budget: int = 32):
        """prefill_mode: "decode" feeds prompt tokens one at a time through
        decode_step (simple, exact); "bulk" runs the full-sequence prefill
        kernel once per request and copies the caches into the slot (one
        jit'd forward instead of len(prompt) decode steps — the production
        path). Bulk prompts are right-padded to power-of-two buckets on
        pure-attention archs, bounding jit retraces at log2(cache_len)
        shapes instead of one per unique prompt length.

        kv_layout selects the decode cache organization:
          * "dense" — the historical layout: each slot owns a private
            (cache_len, ...) KV strip per layer. Simple, supports every
            arch (incl. ssm/rglru state and ring/window caches), zero
            sharing: a request's prefill always computes its full prompt.
          * "paged" — KV lives in a pool of `block_size`-token pages
            (`kvcache.BlockPool` ids -> rows of per-layer pool arrays);
            each slot holds a block table. A radix tree over past prompts
            (`kvcache.RadixTree`) lets a new request *reuse* already-
            prefilled pages for its longest cached prefix (copy-on-write
            for a partially matching page) and prefill only the uncached
            suffix. Pure-attention decoder archs only; window must be None
            (paged pages are position-addressed, not a ring).
        pool_blocks sizes the paged pool (default: 2x the slots' worth of
        pages + the null block, so retired prefixes stay cached).

        decode_kernel ("reference"|"pallas"), fused_tokens (> 1 enables
        the multi-token scan dispatch), and spec_tokens (>= 1 enables
        speculative draft-verify decode; `drafter` picks the proposer)
        accelerate the paged decode path — see the module docstring. All
        require kv_layout="paged".

        scheduler picks the prefill/decode interleaving policy:
          * "phased" — the historical default and oracle: an admitted
            request's whole prompt is prefilled in one monolithic forward
            before the batch decodes again (every decoding slot stalls
            for the full prompt length).
          * "chunked" — the token-budget iteration scheduler
            (`serve/scheduler.py`): each step dispatches the lockstep
            decode PLUS up to `chunk_budget` prefill tokens sliced from
            an in-flight prompt in ONE jitted mixed step, so long-prompt
            prefill rides along instead of preempting decode. The first
            generated token is deferred to the chunk that completes the
            prompt, and the prompt's full pages are radix-committed at
            each chunk boundary (concurrent same-prefix admissions reuse
            them mid-prefill). Requires kv_layout="paged"; outputs are
            token-identical to "phased" by construction."""
        self.params = params
        self.cfg = cfg
        self.slots = batch_slots
        self.cache_len = cache_len
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be dense|paged, got {kv_layout}")
        if decode_kernel not in ("reference", "pallas"):
            raise ValueError(f"decode_kernel must be reference|pallas, "
                             f"got {decode_kernel}")
        if spec_tokens < 0:
            raise ValueError(f"spec_tokens must be >= 0, got {spec_tokens}")
        if scheduler not in SCHEDULERS:
            raise ValueError(f"scheduler must be one of {SCHEDULERS}, "
                             f"got {scheduler!r}")
        if kv_layout != "paged":
            if decode_kernel != "reference":
                raise ValueError("decode_kernel='pallas' targets the paged "
                                 "block pool; use kv_layout='paged'")
            if fused_tokens > 1:
                raise ValueError("fused multi-token decode scans the paged "
                                 "decode step; use kv_layout='paged'")
            if spec_tokens > 0:
                raise ValueError("speculative decode verifies over (and "
                                 "rolls back) paged KV; use kv_layout="
                                 "'paged'")
            if scheduler == "chunked":
                raise ValueError("chunked prefill scatters bounded chunks "
                                 "into paged block tables; use "
                                 "kv_layout='paged'")
        self.kv_layout = kv_layout
        self.decode_kernel = decode_kernel
        self.fused_tokens = int(fused_tokens)
        self.spec_tokens = int(spec_tokens)
        # brownout lever (set_degraded): parks the spec/fused fast lanes
        # and caps chunked-prefill chunks without touching any jit shape
        self.degraded = False
        self.drafter = make_drafter(drafter) if spec_tokens > 0 else None
        self._decode_fused = None
        self._decode_spec = None
        # speculative-decode telemetry (gateway dashboard aggregates these)
        self.spec_dispatches = 0
        self.spec_tokens_drafted = 0
        self.spec_tokens_accepted = 0
        self.spec_tokens_emitted = 0
        self.spec_tokens_rolled_back = 0
        self.block_size = block_size
        self.manager: Optional[KVCacheManager] = None
        # chunked-prefill scheduler (None on the phased path)
        self.scheduler: Optional[ChunkedScheduler] = None
        self.scheduler_mode = scheduler
        if kv_layout == "paged":
            if (window if window is not None else cfg.window) is not None:
                raise ValueError("paged KV cache does not support sliding-"
                                 "window (ring) caches; use kv_layout=dense")
            if cache_len % block_size:
                raise ValueError(f"cache_len {cache_len} must be a multiple "
                                 f"of block_size {block_size}")
            nb = cache_len // block_size
            if pool_blocks is None:
                pool_blocks = 2 * batch_slots * nb + 1
            self.cache = T.init_paged_cache(cfg, pool_blocks, block_size)
            self.manager = KVCacheManager(pool_blocks, block_size)
            # per-slot block tables; row of ids into the pool arrays.
            # Retired/empty slots are all-zero -> the reserved null block
            self.table = np.zeros((batch_slots, nb), np.int32)
            self._slot_blocks: List[List[int]] = [[] for _ in range(batch_slots)]
            self._decode_tok = jax.jit(build_decode_paged(
                cfg, window=window, kernel=decode_kernel))
            self._decode_lg = jax.jit(build_decode_paged(
                cfg, window=window, return_logits=True,
                kernel=decode_kernel))
            if self.fused_tokens > 1:
                self._decode_fused = jax.jit(build_decode_fused(
                    cfg, self.fused_tokens, window=window,
                    kernel=decode_kernel))
            if self.spec_tokens > 0:
                self._decode_spec = jax.jit(build_decode_spec(
                    cfg, self.spec_tokens, window=window))
            if scheduler == "chunked":
                self.scheduler = ChunkedScheduler(chunk_budget)
                self._mixed_tok = jax.jit(build_mixed_step(
                    cfg, window=window, kernel=decode_kernel))
                self._mixed_lg = jax.jit(build_mixed_step(
                    cfg, window=window, kernel=decode_kernel,
                    return_logits=True))
        else:
            self.cache = T.init_cache(cfg, batch_slots, cache_len)
            self._decode_tok = jax.jit(build_decode(cfg, window=window))
            self._decode_lg = jax.jit(build_decode(cfg, window=window,
                                                   return_logits=True))
        self.pos = np.full((batch_slots,), -1, np.int64)   # last written pos
        self.budget = np.zeros((batch_slots,), np.int64)
        self.active: List[Optional[Request]] = [None] * batch_slots
        # two decode variants: the in-jit argmax one keeps the all-greedy
        # hot path transferring one int per slot; the logits one (compiled
        # lazily, on first use) feeds host-side per-request sampling
        self.prefill_mode = prefill_mode
        # prompt tokens actually run through the model (the paged path's
        # prefix hits subtract from this; benchmarks assert the gap)
        self.prefill_tokens_computed = 0
        # pad bulk prompts only where padding cannot distort state:
        # recurrent mixers (ssm/rglru) advance over pad tokens
        self._bucket_prompts = T.paged_supported(cfg)
        if prefill_mode == "bulk":
            if kv_layout == "paged":
                self._prefill_tok = jax.jit(
                    build_prefill_paged(cfg, window=window))
                self._prefill_lg = jax.jit(build_prefill_paged(
                    cfg, window=window, return_logits=True))
            else:
                self._prefill_tok = jax.jit(
                    build_prefill_bucketed(cfg, window=window))
                self._prefill_lg = jax.jit(build_prefill_bucketed(
                    cfg, window=window, return_logits=True))
        self._pending: List[Request] = []
        self._finished: List[Request] = []
        # observability: spans land on this track (the gateway sets it to
        # the replica id), and every step's wall time feeds a fixed-bucket
        # histogram per step kind (prefill/decode/fused/spec/mixed) so the
        # dashboard shows where dispatch time goes, not just token totals
        self.trace_tid = 0
        self.step_times: Dict[str, Histogram] = {}
        # utilization attribution sink (obs.ledger.UtilizationLedger or
        # None): when set, every step's measured wall time is split across
        # the slots that rode the dispatch by token share — see
        # Gateway.arm_ledger(). Post-construction like trace_tid, so
        # reset() (warm reintegration) keeps it.
        self.ledger = None
        # long-lived frontends (the gateway) keep their own handles; set
        # False so finished requests are not retained engine-side forever
        self.retain_finished = True
        self._next_id = 0
        # gateway event hooks: fn(req, ...) or None
        self.on_token: Optional[Callable[[Request, int], None]] = None
        self.on_finish: Optional[Callable[[Request], None]] = None

    # ------------------------------------------------------------- intake
    def submit(self, prompt: List[int], max_new_tokens: int = 16,
               eos_id: Optional[int] = None,
               sampling: Optional[SamplingParams] = None) -> Request:
        req = Request(self._next_id, list(prompt), max_new_tokens, eos_id,
                      sampling or GREEDY)
        self._next_id += 1
        return self.enqueue(req)

    def enqueue(self, req: Request) -> Request:
        """Admit an externally-built Request (the gateway constructs its own
        so ids and samplers survive cross-replica retries)."""
        if self.kv_layout == "paged" and \
                len(req.prompt) + req.max_new_tokens > self.cache_len:
            raise ValueError(
                f"request needs {len(req.prompt) + req.max_new_tokens} "
                f"token positions, table holds {self.cache_len}")
        req.enqueued_t = time.perf_counter()
        self._pending.append(req)
        return req

    def free_slots(self) -> int:
        return sum(1 for a in self.active if a is None) - len(self._pending)

    def active_count(self) -> int:
        return sum(1 for a in self.active if a is not None)

    def pending_count(self) -> int:
        return len(self._pending)

    def has_work(self) -> bool:
        return bool(self._pending) or self.active_count() > 0

    # --------------------------------------------------- capacity / cache
    def token_capacity(self) -> int:
        """Hard per-request ceiling (prompt + new tokens) for this engine:
        the block table's span, and on the paged layout also the pool
        itself (a pool smaller than one table can never serve a request
        larger than its usable pages)."""
        if self.kv_layout == "paged":
            usable = (self.manager.pool.n_blocks - 1) * self.block_size
            return min(self.cache_len, usable)
        return self.cache_len

    def free_token_capacity(self) -> int:
        """Token positions this engine could commit to right now: free
        slots x per-slot capacity on the dense layout; bounded further by
        free + idle-cached pool blocks on the paged layout (the gateway's
        admission-by-token-budget consults this)."""
        free = self.free_slots()
        if free <= 0:
            return 0
        cap = free * self.cache_len
        if self.kv_layout == "paged":
            cap = min(cap, self.manager.free_tokens())
        return cap

    def cached_prefix_tokens(self, prompt) -> int:
        """How many leading tokens of `prompt` are already prefilled here
        (radix probe; 0 on the dense layout). The gateway's prefix-affinity
        policy ranks replicas by this instead of a hash heuristic."""
        if self.manager is None:
            return 0
        return self.manager.match_len(prompt)

    @property
    def cache_metrics(self):
        """kvcache.CacheMetrics for the paged layout, else None."""
        return self.manager.metrics if self.manager is not None else None

    # ---------------------------------------------------------- lifecycle
    def reset(self):
        """Warm rebuild for replica reintegration after a crash: device
        cache re-initialized, fresh KV pool + radix index, every slot and
        block table empty, the chunked scheduler re-created. The jitted
        dispatch functions are deliberately KEPT — state is what a crash
        corrupts; recompiling would pay first-step latency all over."""
        if self.kv_layout == "paged":
            pool_blocks = self.manager.pool.n_blocks
            self.cache = T.init_paged_cache(self.cfg, pool_blocks,
                                            self.block_size)
            self.manager = KVCacheManager(pool_blocks, self.block_size)
            self.table = np.zeros_like(self.table)
            self._slot_blocks = [[] for _ in range(self.slots)]
        else:
            self.cache = T.init_cache(self.cfg, self.slots, self.cache_len)
        self.pos = np.full((self.slots,), -1, np.int64)
        self.budget = np.zeros((self.slots,), np.int64)
        self.active = [None] * self.slots
        self._pending = []
        self._finished = []
        self.prefill_tokens_computed = 0
        if self.scheduler is not None:
            fresh = ChunkedScheduler(self.scheduler.chunk_budget)
            fresh._cap = self.scheduler._cap     # keep brownout throttle
            self.scheduler = fresh
        if self.drafter is not None and hasattr(self.drafter, "_streams"):
            # draft-model incremental KV is keyed by request identity;
            # stale streams from the crashed run must not seed retries
            self.drafter._streams.clear()

    def set_degraded(self, on: bool, *, chunk_cap: int = 8):
        """Brownout level-2 lever: park the speculative and fused fast
        lanes (their long bursts monopolize the lockstep batch under
        pressure) and cap chunked-prefill chunks at `chunk_cap` tokens.
        Shape-safe by construction: lanes are *skipped*, not rebuilt, and
        the chunk cap shortens the token run inside the fixed-width padded
        operand — nothing retraces."""
        self.degraded = bool(on)
        if self.scheduler is not None:
            self.scheduler.throttle(chunk_cap if on else None)

    # ------------------------------------------------------------- internals
    def _observe_step(self, kind: str, t0: float, shares=None):
        """Record one step's wall ms under its step kind, and — when the
        utilization ledger is armed — attribute the same measured seconds
        across the slots that rode the dispatch (`shares` is a list of
        ``(request_id, tokens, blocks_held)``). One clock read feeds both
        sinks, so ledger totals and step_times totals agree exactly."""
        dt = time.perf_counter() - t0
        h = self.step_times.get(kind)
        if h is None:
            h = self.step_times[kind] = Histogram()
        h.observe(dt * 1e3)
        if self.ledger is not None:
            pool_blocks = self.manager.occupancy() \
                if self.manager is not None else 0
            self.ledger.record_step(kind, dt, shares or [],
                                    pool_blocks=pool_blocks)

    def _blocks_held(self, slot: int) -> int:
        """KV blocks this slot currently pins (0 on the dense layout)."""
        return len(self._slot_blocks[slot]) if self.manager is not None else 0

    def step_summary(self) -> Optional[dict]:
        """Per-step-kind wall-time stats (None before the first step):
        {kind: {count, mean, p50, p95, max}} in milliseconds. The gateway
        merges these histograms across replicas for the unified
        dashboard's per-stage timing section."""
        if not self.step_times:
            return None
        return {k: h.summary() for k, h in sorted(self.step_times.items())}

    def _admit(self):
        if not self._pending:
            return
        with otrace.span("engine.admit", tid=self.trace_tid,
                         pending=len(self._pending)):
            self._admit_pending()

    def _admit_pending(self):
        for slot in range(self.slots):
            if self.active[slot] is None and self._pending:
                adm = None
                if self.kv_layout == "paged":
                    req = self._pending[0]
                    try:
                        adm = self.manager.admit(
                            req.prompt, len(req.prompt) + req.max_new_tokens,
                            tid=self.trace_tid)
                    except PoolExhausted as err:
                        if self.active_count() == 0:
                            # nothing in flight will ever free blocks: the
                            # request cannot be served — fail it, not the
                            # replica (the gateway sees a request-scoped
                            # error, same as a sampling failure)
                            self._pending.pop(0)
                            req.error = err
                            req.done = True
                            if self.retain_finished:
                                self._finished.append(req)
                            if self.on_finish:
                                self.on_finish(req)
                            continue
                        break       # retry after a running request retires
                req = self._pending.pop(0)
                otrace.add_span("engine.pending", req.enqueued_t,
                                time.perf_counter(),
                                pid=otrace.ENGINE_QUEUE_PID,
                                tid=req.request_id)
                self.active[slot] = req
                if self.scheduler is not None:
                    self._begin_chunked_prefill(slot, req, adm)
                else:
                    self._prefill_slot(slot, req, adm)

    def _emit(self, req: Request, tok: int):
        req.output.append(tok)
        if self.on_token:
            self.on_token(req, tok)

    def _sample_safe(self, req: Request, logits_row):
        """Host-side sampling is request-scoped: bad SamplingParams or NaN
        logits must fail only this request, never the whole replica (one
        poison request would otherwise disable the fleet). Returns the
        token, or the exception after recording it on the request."""
        try:
            return req.next_token(logits_row)
        except Exception as err:  # noqa: BLE001
            req.error = err
            return err

    def _prefill_slot(self, slot: int, req: Request, adm=None):
        """Fill this slot's cache from the prompt, merging only this slot's
        rows so peers are untouched. `adm` is the paged-layout Admission
        (block chain + reused-prefix length) from the manager."""
        t0 = time.perf_counter()
        tok0 = self.prefill_tokens_computed
        reused = adm.n_reused if adm is not None else 0
        tokens = len(req.prompt) - reused
        with otrace.span("engine.step", tid=self.trace_tid, step="prefill",
                         slot=slot, prompt_len=len(req.prompt),
                         reused=reused, tokens=tokens,
                         padded=self._prefill_padded(tokens)):
            self._prefill_slot_impl(slot, req, adm)
        # share basis: prompt tokens actually computed (min 1 — a full
        # prefix hit still occupied the dispatch); blocks may already be 0
        # if the request retired inside the impl
        computed = max(1, self.prefill_tokens_computed - tok0)
        self._observe_step("prefill", t0,
                           [(req.request_id, computed,
                             self._blocks_held(slot))])

    def _prefill_padded(self, tokens: int) -> int:
        """Positions the prefill of `tokens` uncached prompt tokens
        computes: bulk prefill pads them to bucket_len where it buckets
        (every paged engine does), the token-at-a-time path runs them."""
        if self.prefill_mode == "bulk" and self._bucket_prompts and tokens:
            return bucket_len(tokens, self.cache_len)
        return tokens

    def _prefill_slot_impl(self, slot: int, req: Request, adm=None):
        greedy = req.sampling.is_greedy
        if self.kv_layout == "paged":
            first = self._paged_prefill_slot(slot, req, adm)
        elif not req.prompt:
            # degenerate empty prompt: nothing to condition on; argmax of a
            # zero logits row (token 0), matching the old engine
            first = 0 if greedy else self._sample_safe(
                req, np.zeros((self.cfg.vocab_size,), np.float32))
        elif self.prefill_mode == "bulk":
            first = self._bulk_prefill_slot(slot, req)
            self.prefill_tokens_computed += len(req.prompt)
        else:
            decode = self._decode_tok if greedy else self._decode_lg
            for t, tok in enumerate(req.prompt):
                toks = jnp.zeros((self.slots, 1), jnp.int32) \
                    .at[slot, 0].set(tok)
                pos = jnp.zeros((self.slots,), jnp.int32).at[slot].set(t)
                out, cache = decode(self.params, toks, pos, self.cache)
                self.cache = _merge_slot(self.cache, cache, slot)
            first = self._first_token(req, out[slot])
            self.prefill_tokens_computed += len(req.prompt)
        self._finish_prefill(slot, req, first)

    def _first_token(self, req: Request, out):
        """Read a prefill's last-position result (the token, or the logits
        row to sample): where the engine blocks on the prefill program."""
        with otrace.span("engine.wait", tid=self.trace_tid,
                         program="prefill"):
            out = np.asarray(out)
        return int(out) if req.sampling.is_greedy else \
            self._sample_safe(req, out)

    def _finish_prefill(self, slot: int, req: Request, first):
        """Post-prefill bookkeeping shared by the phased and chunked paths:
        emit the request's first generated token (or fail it request-scoped
        on a sampling error), arm the decode budget, retire on EOS or an
        exhausted budget."""
        self.pos[slot] = len(req.prompt) - 1
        if isinstance(first, Exception):        # request-scoped sampling bug
            self.budget[slot] = 0
            self._retire(slot)
            return
        hit_eos = req.eos_id is not None and first == req.eos_id
        if not hit_eos:
            self._emit(req, first)
        self.budget[slot] = req.max_new_tokens - 1
        if hit_eos or self.budget[slot] <= 0:
            self._retire(slot)

    def _wire_slot_table(self, slot: int, adm):
        """Point the slot's block-table row at the Admission's chain and
        perform the device half of copy-on-write: a partially matching
        page is cloned so our writes can't clobber the cached original
        (`cow_done` drops the manager's pin only AFTER the device copy —
        the ordering the manager's admission pinning relies on)."""
        self._slot_blocks[slot] = list(adm.blocks)
        self.table[slot, :] = 0
        self.table[slot, :len(adm.blocks)] = adm.blocks
        if adm.cow is not None:
            src, dst = adm.cow
            self.cache = T.copy_pool_blocks(self.cache, [src], [dst])
            self.manager.cow_done(src)

    def _begin_chunked_prefill(self, slot: int, req: Request, adm):
        """Chunked-scheduler admission: wire the slot's block table from
        the Admission (exactly like the phased paged path, CoW included)
        but run NO model forward — the prompt's uncached tokens will be
        sliced into bounded chunks by `_step_mixed`, riding along decode
        dispatches. The first generated token is deferred to the chunk
        that completes the prompt."""
        self._wire_slot_table(slot, adm)
        if not req.prompt:
            # degenerate empty prompt: nothing to chunk; argmax of a zero
            # logits row (token 0), matching the phased path
            first = 0 if req.sampling.is_greedy else self._sample_safe(
                req, np.zeros((self.cfg.vocab_size,), np.float32))
            self._finish_prefill(slot, req, first)
            return
        self.scheduler.admit(slot, adm.n_reused)

    def _paged_prefill_slot(self, slot: int, req: Request, adm) -> int:
        """Prefix-reusing prefill: wire the slot's block table from the
        Admission (shared radix pages + CoW clone + fresh pages), then run
        only the uncached suffix through the model — one bulk forward or
        len(suffix) decode steps. Returns the first generated token."""
        greedy = req.sampling.is_greedy
        self._wire_slot_table(slot, adm)
        start, P = adm.n_reused, len(req.prompt)
        self.prefill_tokens_computed += P - start
        if not req.prompt:
            return 0 if greedy else self._sample_safe(
                req, np.zeros((self.cfg.vocab_size,), np.float32))
        if self.prefill_mode == "bulk":
            suffix = req.prompt[start:]
            Sb = bucket_len(len(suffix), self.cache_len)
            toks = jnp.asarray([suffix + [0] * (Sb - len(suffix))], jnp.int32)
            prefill = self._prefill_tok if greedy else self._prefill_lg
            out, self.cache = prefill(
                self.params, toks, jnp.asarray(start, jnp.int32),
                jnp.asarray(len(suffix), jnp.int32), self.cache,
                jnp.asarray(self.table[slot]))
            first = self._first_token(req, out)
        else:
            decode = self._decode_tok if greedy else self._decode_lg
            # peers' rows masked to the null block: their lockstep garbage
            # writes must not touch live pages (the paged analogue of
            # _merge_slot on the dense path)
            tbl = np.zeros_like(self.table)
            tbl[slot] = self.table[slot]
            tbl = jnp.asarray(tbl)
            for t in range(start, P):
                toks = jnp.zeros((self.slots, 1), jnp.int32) \
                    .at[slot, 0].set(req.prompt[t])
                pos = jnp.zeros((self.slots,), jnp.int32).at[slot].set(t)
                out, self.cache = decode(self.params, toks, pos,
                                         self.cache, tbl)
            first = self._first_token(req, out[slot])
        # index the prompt's full pages: the next request sharing this
        # prefix reuses them instead of re-running prefill
        self.manager.commit(req.prompt, self._slot_blocks[slot],
                            tid=self.trace_tid)
        return first

    def _bulk_prefill_slot(self, slot: int, req: Request) -> int:
        """One full-sequence prefill forward; the caches are copied into
        this slot of the fixed decode cache. Prompts are right-padded to
        power-of-two buckets on pure-attention archs (see bucket_len) so
        repeated traffic compiles O(log cache_len) shapes, not one per
        natural prompt length. Returns the request's first generated
        token."""
        from repro.serve.step import prefill_into_cache
        greedy = req.sampling.is_greedy
        prefill = self._prefill_tok if greedy else self._prefill_lg
        Sp = len(req.prompt)
        Sb = bucket_len(Sp, self.cache_len) if self._bucket_prompts else Sp
        toks = jnp.asarray([req.prompt + [0] * (Sb - Sp)], jnp.int32)
        out, nat = prefill(self.params, {"tokens": toks},
                           jnp.asarray(Sp, jnp.int32))
        slot_cache = T.init_cache(self.cfg, 1, self.cache_len)
        slot_cache = prefill_into_cache(self.cfg, nat, slot_cache,
                                        jnp.asarray([len(req.prompt)]))

        # write the single-row cache into this slot (batch axis: 0 for tail
        # leaves, 1 for block-stacked leaves)
        def write(full, one, axis):
            idx = [slice(None)] * full.ndim
            idx[axis] = slice(slot, slot + 1)
            return full.at[tuple(idx)].set(one)
        merged = {"blocks": None}
        if self.cache.get("blocks") is not None:
            merged["blocks"] = jax.tree.map(
                lambda f, o: write(f, o, 1), self.cache["blocks"],
                slot_cache["blocks"])
        merged["tail"] = jax.tree.map(lambda f, o: write(f, o, 0),
                                      self.cache["tail"], slot_cache["tail"])
        self.cache = merged
        return self._first_token(req, out[0])

    def _release_slot_blocks(self, slot: int, req: Optional[Request],
                             commit: bool = True):
        """Paged bookkeeping when a slot empties: optionally index the
        sequence written so far (prompt + generated full pages) for future
        prefix reuse, then drop the request's block references — pages the
        radix tree kept stay resident, the rest return to the pool."""
        blocks = self._slot_blocks[slot]
        if not blocks:
            return
        if commit and req is not None:
            written = (req.prompt + req.output)[:int(self.pos[slot]) + 1]
            self.manager.commit(written, blocks, tid=self.trace_tid)
        self.manager.release(blocks)
        self._slot_blocks[slot] = []
        self.table[slot, :] = 0

    def _retire(self, slot: int):
        req = self.active[slot]
        with otrace.span("engine.retire", tid=self.trace_tid, slot=slot,
                         request=req.request_id):
            self._retire_impl(slot, req)

    def _retire_impl(self, slot: int, req):
        req.done = True
        if self.scheduler is not None:
            self.scheduler.drop(slot)    # no-op unless mid-prefill
        if self.kv_layout == "paged":
            self._release_slot_blocks(slot, req)
        self.active[slot] = None
        self.pos[slot] = -1
        if self.retain_finished:
            self._finished.append(req)
        if self.on_finish:
            self.on_finish(req)

    # ------------------------------------------------------------- run
    def step(self) -> int:
        """Admit + one lockstep decode over active slots. Returns #active.
        On a fused engine (fused_tokens > 1) an all-greedy batch advances
        up to fused_tokens positions in this one call; any slot needing
        host-side sampling falls the batch back to single-token dispatch.
        On a chunked engine, any step with a partially-prefilled slot
        dispatches the mixed decode+chunk step instead (the fused/spec
        fast lanes resume once no prefill is in flight)."""
        self._admit()
        if self.scheduler is not None and self.scheduler.has_prefill_work():
            return self._step_mixed()
        live = [s for s in range(self.slots) if self.active[s] is not None]
        if not live:
            return 0
        toks = np.zeros((self.slots, 1), np.int32)
        for s in live:
            toks[s, 0] = self.active[s].output[-1]
        pos = np.maximum(self.pos + 1, 0).astype(np.int32)
        greedy_batch = all(self.active[s].sampling.is_greedy for s in live)
        if self._decode_spec is not None and greedy_batch \
                and not self.degraded:
            return self._step_spec(live, toks, pos)
        if self._decode_fused is not None and greedy_batch and \
                not self.degraded and \
                2 * max(self.budget[s] for s in live) > self.fused_tokens:
            # request endgame guard: the scan always runs fused_tokens full
            # forwards, so once every live slot would go dead within the
            # first half of the burst, the wasted null-page forwards cost
            # more than the host round-trips saved — finish single-step
            return self._step_fused(live, toks, pos)
        t0 = time.perf_counter()
        # one token per live slot this dispatch; read blocks before the
        # reconcile loop can retire slots and release them
        shares = [(self.active[s].request_id, 1, self._blocks_held(s))
                  for s in live]
        # keys each live slot attends: its written positions and this one
        keys = int(self.pos[live].sum()) + 2 * len(live)
        with otrace.span("engine.step", tid=self.trace_tid, step="decode",
                         live=len(live), keys=keys):
            decode = self._decode_tok if greedy_batch else self._decode_lg
            with otrace.span("jit.decode", tid=self.trace_tid,
                             kind="single", greedy=int(greedy_batch)):
                if self.kv_layout == "paged":
                    # no merge needed: every live slot scatters exactly
                    # into its own frontier page; empty slots' zero tables
                    # hit the null block
                    out, self.cache = decode(self.params, jnp.asarray(toks),
                                             jnp.asarray(pos), self.cache,
                                             jnp.asarray(self.table))
                else:
                    out, new_cache = decode(self.params, jnp.asarray(toks),
                                            jnp.asarray(pos), self.cache)
                    self.cache = _merge_slots(self.cache, new_cache, live)
            with otrace.span("engine.wait", tid=self.trace_tid,
                             program="decode"):
                out = np.asarray(out)
            for s in live:
                req = self.active[s]
                self.pos[s] += 1
                self.budget[s] -= 1
                tok = int(out[s]) if greedy_batch else \
                    self._sample_safe(req, out[s])
                if isinstance(tok, Exception):
                    self.budget[s] = 0
                    self._retire(s)
                    continue
                hit_eos = req.eos_id is not None and tok == req.eos_id
                if not hit_eos:
                    self._emit(req, tok)
                if hit_eos or self.budget[s] <= 0:
                    self._retire(s)
        self._observe_step("decode", t0, shares)
        return len(live)

    def _step_mixed(self) -> int:
        """One chunked-scheduler iteration: lockstep single-token decode
        over every *decoding* slot plus ONE bounded prefill chunk for the
        scheduler's head prefilling slot, dispatched together through
        `build_mixed_step`. Decoding slots never wait out a monolithic
        prompt forward — the stall per step is bounded by chunk_budget.

        Reconciliation: decode slots advance exactly as in `step()`; the
        chunk advances its slot's cursor, radix-commits the prompt's
        newly completed pages (concurrent same-prefix admissions reuse
        them mid-prefill), and — when it completes the prompt — samples
        the deferred first token from the chunk's last-position logits
        and flips the slot to decoding."""
        t0 = time.perf_counter()
        with otrace.span("engine.step", tid=self.trace_tid, step="mixed"):
            n, shares = self._step_mixed_impl()
        self._observe_step("mixed", t0, shares)
        return n

    def _step_mixed_impl(self):
        sched = self.scheduler
        plan = sched.plan_chunk(
            {s: self.active[s].prompt for s in range(self.slots)
             if self.active[s] is not None and sched.prefilling(s)})
        decode_live = [s for s in range(self.slots)
                       if self.active[s] is not None
                       and not sched.prefilling(s)]
        creq = self.active[plan.slot]
        # ledger shares: each decoding slot gets one token, the chunk slot
        # its chunk length; blocks read before the reconcile loop retires
        shares = [(self.active[s].request_id, 1, self._blocks_held(s))
                  for s in decode_live]
        shares.append((creq.request_id, len(plan.tokens),
                       self._blocks_held(plan.slot)))
        toks = np.zeros((self.slots, 1), np.int32)
        for s in decode_live:
            toks[s, 0] = self.active[s].output[-1]
        pos = np.maximum(self.pos + 1, 0).astype(np.int32)
        # prefilling (and empty) slots' table rows are masked to the null
        # block: their lockstep decode writes must never touch live pages
        tbl = np.zeros_like(self.table)
        for s in decode_live:
            tbl[s] = self.table[s]
        ctoks = np.zeros((1, sched.chunk_budget), np.int32)
        ctoks[0, :len(plan.tokens)] = plan.tokens
        # the chunk can only attend pages up to its own end: pass a
        # truncated table so the in-jit gather spans ceil(end/bs) pages —
        # bucketed to powers of two, so retraces stay O(log nb) — instead
        # of the whole cache span on every chunk (early chunks of a long
        # prompt would otherwise pay full-table attention P/C times over)
        nbp = -(-(plan.start + len(plan.tokens)) // self.block_size)
        nbp = min(bucket_len(nbp, 0), self.table.shape[1])
        greedy_batch = all(self.active[s].sampling.is_greedy
                           for s in decode_live)
        need_logits = (bool(decode_live) and not greedy_batch) or \
            (plan.completes and not creq.sampling.is_greedy)
        mixed = self._mixed_lg if need_logits else self._mixed_tok
        with otrace.span("jit.mixed", tid=self.trace_tid,
                         decoding=len(decode_live), chunk=len(plan.tokens)):
            out_d, out_c, self.cache = mixed(
                self.params, jnp.asarray(toks), jnp.asarray(pos), self.cache,
                jnp.asarray(tbl), jnp.asarray(ctoks),
                jnp.asarray(plan.start, jnp.int32),
                jnp.asarray(len(plan.tokens), jnp.int32),
                jnp.asarray(self.table[plan.slot, :nbp]))
        sched.mixed_dispatches += 1
        with otrace.span("engine.wait", tid=self.trace_tid, program="mixed"):
            out_d = np.asarray(out_d)
        for s in decode_live:
            req = self.active[s]
            self.pos[s] += 1
            self.budget[s] -= 1
            tok = self._sample_safe(req, out_d[s]) if need_logits \
                else int(out_d[s])
            if isinstance(tok, Exception):
                self.budget[s] = 0
                self._retire(s)
                continue
            hit_eos = req.eos_id is not None and tok == req.eos_id
            if not hit_eos:
                self._emit(req, tok)
            if hit_eos or self.budget[s] <= 0:
                self._retire(s)
        # chunk reconciliation: cursor forward, commit at the boundary
        sched.advance(plan)
        self.prefill_tokens_computed += len(plan.tokens)
        cur = plan.start + len(plan.tokens)
        self.manager.commit(creq.prompt[:cur], self._slot_blocks[plan.slot],
                            tid=self.trace_tid)
        if plan.completes:
            first = self._sample_safe(creq, np.asarray(out_c)) \
                if need_logits else int(out_c)
            self._finish_prefill(plan.slot, creq, first)
        return len(decode_live) + 1, shares

    def _step_fused(self, live, toks, pos) -> int:
        """One fused dispatch: up to fused_tokens greedy decode steps in a
        single jitted scan. EOS and per-slot budgets are masked in-jit (a
        dead slot's writes are redirected to the null page); this method
        reconciles the device's view back into host bookkeeping — tokens
        emitted per slot, pos/budget advanced by the steps actually taken,
        finished slots retired."""
        t0 = time.perf_counter()
        with otrace.span("engine.step", tid=self.trace_tid, step="fused",
                         live=len(live), fused_tokens=self.fused_tokens):
            n, shares = self._step_fused_impl(live, toks, pos)
        self._observe_step("fused", t0, shares)
        return n

    def _step_fused_impl(self, live, toks, pos):
        eos = np.full((self.slots,), -1, np.int32)
        steps = np.zeros((self.slots,), np.int32)
        alive = np.zeros((self.slots,), bool)
        for s in live:
            req = self.active[s]
            if req.eos_id is not None:
                eos[s] = req.eos_id
            steps[s] = self.budget[s]
            alive[s] = True
        with otrace.span("jit.fused", tid=self.trace_tid, live=len(live)):
            emitted, live_out, steps_out, self.cache = self._decode_fused(
                self.params, jnp.asarray(toks), jnp.asarray(pos), self.cache,
                jnp.asarray(self.table), jnp.asarray(eos), jnp.asarray(alive),
                jnp.asarray(steps))
        with otrace.span("engine.wait", tid=self.trace_tid, program="fused"):
            emitted = np.asarray(emitted)
            live_out = np.asarray(live_out)
            steps_out = np.asarray(steps_out)
        shares = []
        for s in live:
            req = self.active[s]
            used = int(steps[s] - steps_out[s])
            # ledger share = steps this slot actually advanced in the
            # burst; blocks read before a possible retire releases them
            shares.append((req.request_id, used, self._blocks_held(s)))
            self.pos[s] += used
            self.budget[s] -= used
            for t in range(emitted.shape[0]):
                tok = int(emitted[t, s])
                if tok < 0:
                    break
                self._emit(req, tok)
            if not live_out[s]:
                self._retire(s)
        return len(live), shares

    def _step_spec(self, live, toks, pos) -> int:
        """One speculative dispatch: draft K tokens per live slot (host,
        `self.drafter`), verify all of them in one batched forward, emit
        the accepted prefix + bonus token, rewind the frontier past the
        rejects. Reconciliation mirrors `_step_fused`, plus the rollback:
        for each slot, positions beyond pos+adv hold rejected-draft KV —
        `KVCacheManager.rollback` audits the trimmed page range (never
        radix-shared, never freed) and counts it; device-side the rewind
        alone suffices because every read masks beyond the frontier."""
        t0 = time.perf_counter()
        with otrace.span("engine.step", tid=self.trace_tid, step="spec",
                         live=len(live), spec_tokens=self.spec_tokens):
            n, shares = self._step_spec_impl(live, toks, pos)
        self._observe_step("spec", t0, shares)
        return n

    def _step_spec_impl(self, live, toks, pos):
        K = self.spec_tokens
        # packed per-slot operands: draft | eos | steps | live (see builder)
        inp = np.zeros((self.slots, K + 3), np.int32)
        inp[:, K] = -1
        steps = np.zeros((self.slots,), np.int32)
        with otrace.span("draft", tid=self.trace_tid, live=len(live), k=K):
            for s in live:
                req = self.active[s]
                inp[s, :K] = self.drafter.propose(req.prompt + req.output, K)
                if req.eos_id is not None:
                    inp[s, K] = req.eos_id
                inp[s, K + 1] = steps[s] = self.budget[s]
                inp[s, K + 2] = 1
        with otrace.span("jit.verify", tid=self.trace_tid, live=len(live)):
            out, self.cache = self._decode_spec(
                self.params, jnp.asarray(toks), jnp.asarray(pos), self.cache,
                jnp.asarray(self.table), jnp.asarray(inp))
        with otrace.span("engine.wait", tid=self.trace_tid, program="verify"):
            out = np.asarray(out)       # one packed transfer (build_decode_spec)
        emitted, adv, n_acc, live_out, steps_out = \
            out[:K + 1], out[K + 1], out[K + 2], out[K + 3], out[K + 4]
        self.spec_dispatches += 1
        # one O(tree) walk per dispatch, not per rolling-back slot: safe
        # to share across the loop because a retire's commit only indexes
        # the retiring slot's own pages, which can never sit in another
        # slot's (private) rollback range
        shared_blocks = None
        shares = []
        for s in live:
            req = self.active[s]
            p0 = int(pos[s])
            used = int(steps[s] - steps_out[s])
            # ledger share = tokens this slot got out of the verify (the
            # accepted prefix + bonus); blocks read before retire
            shares.append((req.request_id, used, self._blocks_held(s)))
            a = int(adv[s])
            self.spec_tokens_drafted += K
            self.spec_tokens_accepted += min(int(n_acc[s]), K)
            self.spec_tokens_emitted += used
            # the verify forward wrote positions p0..p0+K (span-clamped to
            # the null page); only p0..p0+a survive acceptance
            n_written = min(p0 + K, self.cache_len - 1) + 1
            n_valid = p0 + a + 1
            if n_written > n_valid:
                if shared_blocks is None:
                    shared_blocks = set(self.manager.radix.all_blocks())
                self.manager.rollback(self._slot_blocks[s], n_valid,
                                      n_written, shared=shared_blocks)
                self.spec_tokens_rolled_back += n_written - n_valid
            self.pos[s] = p0 + a
            self.budget[s] -= used
            for t in range(emitted.shape[0]):
                tok = int(emitted[t, s])
                if tok < 0:
                    break
                self._emit(req, tok)
            if not live_out[s]:
                self._retire(s)
        return len(live), shares

    @property
    def spec_metrics(self) -> Optional[dict]:
        """Speculative-decode counters (None when spec is off): drafted vs
        accepted sets the acceptance rate; emitted counts the bonus tokens
        too, so emitted/dispatches is the realized tokens-per-dispatch."""
        if self.spec_tokens <= 0:
            return None
        drafted = self.spec_tokens_drafted
        return {
            "spec_tokens": self.spec_tokens,
            "drafter": getattr(self.drafter, "name", "custom"),
            "dispatches": self.spec_dispatches,
            "tokens_drafted": drafted,
            "tokens_accepted": self.spec_tokens_accepted,
            "tokens_emitted": self.spec_tokens_emitted,
            "tokens_rolled_back": self.spec_tokens_rolled_back,
            "acceptance_rate": (self.spec_tokens_accepted / drafted
                                if drafted else 0.0),
            "tokens_per_dispatch": (self.spec_tokens_emitted
                                    / self.spec_dispatches
                                    if self.spec_dispatches else 0.0),
        }

    @property
    def scheduler_metrics(self) -> Optional[dict]:
        """Chunked-prefill scheduler counters (None on the phased path):
        chunks/tokens dispatched, prefills started/completed/in-flight,
        and realized tokens-per-chunk — the gateway dashboard's scheduler
        section aggregates these across replicas."""
        return self.scheduler.metrics() if self.scheduler is not None \
            else None

    def run(self) -> List[Request]:
        """Drive to completion and return finished requests. Works even on
        an engine whose frontend disabled retain_finished (requests that
        finish inside this call are tracked and returned either way)."""
        retain, self.retain_finished = self.retain_finished, True
        start = len(self._finished)
        try:
            while self._pending or any(a is not None for a in self.active):
                self.step()
        finally:
            self.retain_finished = retain
        if retain:
            return list(self._finished)
        done, self._finished[start:] = self._finished[start:], []
        return done

    def evict(self, req: Request) -> bool:
        """Drop a request from this engine (pending or mid-decode) without
        marking it done — the gateway uses this when re-dispatching leased
        work away from a failed replica. Returns True if found."""
        if req in self._pending:
            self._pending.remove(req)
            return True
        for slot in range(self.slots):
            if self.active[slot] is req:
                if self.scheduler is not None:
                    # half-prefilled: forget its cursor/queue position too
                    self.scheduler.drop(slot)
                if self.kv_layout == "paged":
                    # replica is being failed out: don't index its pages
                    # (state is suspect), just return the references
                    self._release_slot_blocks(slot, req, commit=False)
                self.active[slot] = None
                self.pos[slot] = -1
                return True
        return False


def _take_rows(o, n, slots, axis):
    sel = np.zeros(o.shape[axis], bool)
    sel[list(slots)] = True
    reshape = [1] * o.ndim
    reshape[axis] = o.shape[axis]
    mask = jnp.asarray(sel).reshape(reshape)
    return jnp.where(mask, n, o)


def _merge_slots(old_cache, new_cache, slots):
    """Take rows in `slots` from new_cache, keep the rest from old_cache.
    Batch axis is 0 for tail leaves, 1 for block-stacked leaves."""
    merged = {"blocks": None}
    if old_cache.get("blocks") is not None:
        merged["blocks"] = jax.tree.map(
            lambda o, n: _take_rows(o, n, slots, 1),
            old_cache["blocks"], new_cache["blocks"])
    merged["tail"] = jax.tree.map(lambda o, n: _take_rows(o, n, slots, 0),
                                  old_cache["tail"], new_cache["tail"])
    return merged


def _merge_slot(old_cache, new_cache, slot: int):
    return _merge_slots(old_cache, new_cache, [slot])
