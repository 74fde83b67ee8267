"""Chunked-prefill scheduler tests: mixed-batch edges and bookkeeping.

Output fidelity of the chunked path as a whole lives in the parity matrix
(test_decode_parity.py adds a `chunked` row); here we pin the edges the
scheduler introduces: chunk boundaries landing exactly on block
boundaries, a chunk longer than the remaining prompt, admission while
another prompt is mid-prefill (including radix reuse of pages committed
at chunk boundaries), eviction of a half-prefilled request without
leaking blocks, deferred first-token emission, and the fused mixed step
against the standalone chunk-prefill oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import ChunkedScheduler

V = 41
BS = 4


@pytest.fixture(scope="module")
def model():
    cfg = ModelConfig("t", "dense", 2, 32, 2, 2, 64, V)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    return params, cfg


def _engine(model, *, chunk_budget=3, slots=2, cache_len=32, **kw):
    params, cfg = model
    return ServeEngine(params, cfg, batch_slots=slots, cache_len=cache_len,
                       kv_layout="paged", block_size=BS,
                       scheduler="chunked", chunk_budget=chunk_budget, **kw)


def _phased_outputs(model, prompts, max_new=6, cache_len=32, slots=2):
    params, cfg = model
    eng = ServeEngine(params, cfg, batch_slots=slots, cache_len=cache_len)
    reqs = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    eng.run()
    return [r.output for r in reqs]


# ------------------------------------------------------------------ guards

def test_chunked_requires_paged_layout(model):
    params, cfg = model
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(params, cfg, scheduler="chunked")
    with pytest.raises(ValueError, match="scheduler"):
        ServeEngine(params, cfg, scheduler="dynamic")
    with pytest.raises(ValueError, match="chunk_budget"):
        ChunkedScheduler(0)


# ------------------------------------------------------- boundary geometry

@pytest.mark.parametrize("prompt_len,chunk", [
    (8, BS),        # every chunk boundary == a block boundary
    (8, 8),         # one chunk exactly covers the prompt
    (5, 8),         # chunk larger than the whole prompt
    (7, 3),         # final chunk shorter than the budget, off-block
    (9, 1),         # token-at-a-time degenerate budget
])
def test_chunk_boundary_geometry(model, prompt_len, chunk):
    """Chunk boundaries on/off block boundaries and chunks exceeding the
    remaining prompt all reproduce the phased outputs exactly."""
    prompt = [(3 * i + 1) % V for i in range(prompt_len)]
    want = _phased_outputs(model, [prompt])
    eng = _engine(model, chunk_budget=chunk)
    req = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert req.output == want[0]
    m = eng.scheduler.metrics()
    assert m["prefill_tokens_chunked"] == prompt_len
    assert m["chunks_dispatched"] == -(-prompt_len // chunk)
    assert m["prefills_completed"] == 1 and m["prefills_in_flight"] == 0
    eng.manager.check_invariants()


def test_empty_prompt_chunked(model):
    want = _phased_outputs(model, [[]])
    eng = _engine(model)
    req = eng.submit([], max_new_tokens=6)
    eng.run()
    assert req.output == want[0]
    assert eng.scheduler.metrics()["chunks_dispatched"] == 0


def test_pure_prefill_no_decoders(model):
    """A single-slot engine has no decoding peers while the prompt chunks
    through — the mixed step must still make progress alone."""
    prompt = [(2 * i + 1) % V for i in range(11)]
    want = _phased_outputs(model, [prompt], slots=1)
    eng = _engine(model, chunk_budget=4, slots=1)
    req = eng.submit(prompt, max_new_tokens=6)
    eng.run()
    assert req.output == want[0]


# ----------------------------------------------------- in-flight admission

def test_admission_during_inflight_chunked_prefill(model):
    """A request admitted while another prompt is mid-prefill joins the
    chunk queue; both finish with phased-identical outputs."""
    long_p = [(5 * i + 2) % V for i in range(12)]
    short_p = [9, 10, 11]
    want = _phased_outputs(model, [long_p, short_p])
    eng = _engine(model, chunk_budget=3)
    a = eng.submit(long_p, max_new_tokens=6)
    eng.step()                                  # long admitted, mid-prefill
    assert eng.scheduler.has_prefill_work()
    b = eng.submit(short_p, max_new_tokens=6)
    eng.run()
    assert [a.output, b.output] == want
    assert eng.scheduler.metrics()["prefills_started"] == 2
    eng.manager.check_invariants()


def test_chunk_boundary_commit_enables_midflight_reuse(model):
    """Pages committed at chunk boundaries are reusable by a same-prefix
    request admitted while the first is STILL prefilling — the radix
    index never waits for the prompt to finish."""
    prefix = [7, 3, 7, 1] * 5                   # 20 tokens = 5 full pages
    eng = _engine(model, chunk_budget=4, slots=2, cache_len=64)
    a = eng.submit(prefix + [9], max_new_tokens=4)
    for _ in range(3):                          # 3 chunks committed so far
        eng.step()
    assert eng.scheduler.has_prefill_work()
    assert eng.cached_prefix_tokens(prefix) >= 8
    b = eng.submit(prefix + [11], max_new_tokens=4)
    eng.run()
    assert a.error is None and b.error is None
    assert eng.manager.metrics.tokens_reused > 0
    # parity against a phased engine with the same submissions
    want = _phased_outputs(model, [prefix + [9], prefix + [11]],
                           max_new=4, cache_len=64)
    assert [a.output, b.output] == want
    eng.manager.check_invariants()


# ------------------------------------------------------ stall-free streams

def test_decoders_stream_during_long_prefill(model):
    """THE tentpole property at token granularity: while a long prompt
    chunks through, already-decoding requests keep emitting every step —
    on the phased path the same admission emits nothing for anyone until
    the whole prompt is prefilled (single monolithic forward)."""
    eng = _engine(model, chunk_budget=2, slots=2, cache_len=64)
    short = eng.submit([1, 2, 3], max_new_tokens=30)
    eng.step()                  # chunk 1 of 2: short itself mid-prefill
    eng.step()                  # chunk 2: short's deferred first token
    assert len(short.output) == 1
    emitted_during = []
    eng.on_token = lambda req, tok: emitted_during.append(req.request_id)
    long_req = eng.submit([(3 * i + 2) % V for i in range(16)],
                          max_new_tokens=4)
    for _ in range(8):                          # 16 tokens / chunk 2
        eng.step()
    eng.on_token = None
    # the short request streamed a token on every mixed step...
    assert emitted_during.count(short.request_id) == 8
    # ...and the long one's first token was deferred to the final chunk
    assert emitted_during.count(long_req.request_id) == 1
    assert emitted_during[-1] == long_req.request_id
    eng.run()


# ----------------------------------------------------------- eviction edge

def test_evict_half_prefilled_request_leaks_nothing(model):
    """Evicting a request mid-prefill returns its block references; only
    chunk-committed pages stay (held by the radix tree — that IS the
    cache), and a full tree eviction drains the pool to zero."""
    eng = _engine(model, chunk_budget=4, slots=2, cache_len=64)
    req = eng.submit([(3 * i + 1) % V for i in range(20)], max_new_tokens=4)
    eng.step()
    eng.step()
    assert eng.scheduler.has_prefill_work()
    assert eng.evict(req)
    assert not eng.scheduler.has_prefill_work()
    eng.manager.check_invariants()
    held = eng.manager.pool.allocated_count()
    tree = len(set(eng.manager.radix.all_blocks()))
    assert held == tree, "evicted half-prefilled request leaked blocks"
    eng.manager.radix.evict(10 ** 9)
    assert eng.manager.pool.allocated_count() == 0
    # the engine still serves fresh work afterwards
    nxt = eng.submit([5, 6, 7], max_new_tokens=4)
    eng.run()
    assert nxt.done and nxt.error is None


# ------------------------------------------------- fused path vs the oracle

def test_mixed_step_matches_chunk_prefill_oracle(model):
    """The fused mixed step (one combined pool scatter per layer) must
    write the same KV and produce the same chunk logits as the standalone
    `transformer.prefill_chunk_paged` oracle."""
    params, cfg = model
    from repro.serve.step import build_mixed_step
    bs, nb, slots, C = BS, 8, 2, 4
    pool_blocks = 2 * slots * nb + 1
    tokens = [3, 1, 4, 1, 5, 9, 2, 6]
    chain = list(range(1, nb + 1))

    def run_chunks(fused):
        cache = T.init_paged_cache(cfg, pool_blocks, bs)
        outs = []
        for start in range(0, len(tokens), C):
            n = min(C, len(tokens) - start)
            ctoks = jnp.asarray([tokens[start:start + n] + [0] * (C - n)],
                                jnp.int32)
            if fused:
                mixed = build_mixed_step(cfg)
                dec, last, cache = mixed(
                    params, jnp.zeros((slots, 1), jnp.int32),
                    jnp.zeros((slots,), jnp.int32), cache,
                    jnp.zeros((slots, nb), jnp.int32), ctoks,
                    jnp.asarray(start, jnp.int32), jnp.asarray(n, jnp.int32),
                    jnp.asarray(chain, jnp.int32))
                outs.append(int(last))
            else:
                logits, cache = T.prefill_chunk_paged(
                    params, cfg, ctoks, jnp.asarray(start, jnp.int32),
                    jnp.asarray(n, jnp.int32), cache,
                    jnp.asarray(chain, jnp.int32))
                outs.append(int(jnp.argmax(logits[0, n - 1])))
        return outs, cache

    outs_f, cache_f = run_chunks(True)
    outs_o, cache_o = run_chunks(False)
    assert outs_f == outs_o
    for lf, lo in zip(jax.tree.leaves(cache_f), jax.tree.leaves(cache_o)):
        # exclude pool row 0 (the reserved null page, axis -4 of every
        # (..., P, nkv, bs, hd) leaf): the fused step's masked decode rows
        # and the oracle's pad rows both dump different junk there; every
        # real page must match the oracle exactly
        lf = np.asarray(lf)[..., 1:, :, :, :]
        lo = np.asarray(lo)[..., 1:, :, :, :]
        np.testing.assert_allclose(lf, lo, rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ gateway wire

def test_gateway_chunked_end_to_end(model):
    from repro.core import reporting
    from repro.gateway.gateway import Gateway
    params, cfg = model
    prompts = [[(5 * i + j) % V for j in range(3 + 4 * i)] for i in range(4)]

    def drive(**kw):
        gw = Gateway.build(params, cfg, replicas=2, batch_slots=2,
                           cache_len=64, kv_layout="paged", block_size=BS,
                           policy="round-robin", **kw)
        reqs = [gw.submit(p, max_new_tokens=5) for p in prompts]
        gw.run()
        return [r.output for r in reqs], gw

    want, gw_p = drive()
    got, gw_c = drive(scheduler="chunked", chunk_budget=3)
    assert got == want
    assert gw_p.scheduler_summary() is None
    sched = gw_c.scheduler_summary()
    assert sched["scheduler"] == "chunked" and sched["chunk_budget"] == 3
    assert sched["prefills_completed"] == len(prompts)
    assert sched["prefill_tokens_chunked"] == sum(len(p) for p in prompts)
    s = gw_c.summary()
    for key in ("itl_p95_ms", "itl_max_ms", "stall_p50_ms", "stall_p95_ms",
                "stall_max_ms"):
        assert np.isfinite(s[key]) and s[key] >= 0
    assert s["stall_max_ms"] >= s["stall_p50_ms"]
    # per-request ITL distribution on the caller-facing metrics record
    with_itls = [g for g in gw_c.requests() if g.metrics.n_tokens > 1]
    assert with_itls
    for gwreq in with_itls:
        m = gwreq.metrics
        assert m.itl_p50 <= m.itl_p95 <= m.itl_max
    dash = reporting.gateway_dashboard(s, gw_c.metrics.gauges,
                                       scheduler=sched)
    assert "chunked-prefill scheduler" in dash
    assert "prefill_tokens_chunked" in dash
