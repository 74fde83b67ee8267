"""Paged-attention decode kernel vs the dense-gather oracle (ref.py),
in the TPU interpreter (kernel body executes on CPU with the TPU's VMEM,
DMAs and semaphores simulated, never-written VMEM read as NaN; the grid is
identical to the TPU lowering). Covers the GQA group shapes, partially
filled frontier pages, null-page (empty/retired) slots, blocks of pages
past the frontier, and the window=None-only guard, plus the wiring
through layers/transformer."""
import jax
import jax.experimental.pallas.tpu as pltpu
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


@pytest.fixture(autouse=True)
def tpu_interpreter():
    """Every kernel call here runs in the TPU interpreter; the serving path
    off the chip keeps the faster generic one (``interpret=True``)."""
    with pltpu.force_tpu_interpret_mode():
        yield


def _chain(pool_rows, bs, nb, fill_tokens):
    """Allocate a block chain covering `fill_tokens` positions out of the
    shuffled non-null pool rows; zero-pad the table tail like the engine."""
    need = -(-max(fill_tokens, 1) // bs)
    ids = [pool_rows.pop() for _ in range(need)]
    return ids + [0] * (nb - need)


def _case(B, nb, bs, nkv, rep, hd, fills, seed=0):
    """fills[b]: tokens resident in slot b (0 = empty slot, all-null
    table); pos[b] = fills[b] - 1, the newest token's position."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    P = B * nb + 1
    kpool = jax.random.normal(ks[0], (P, nkv, bs, hd))
    vpool = jax.random.normal(ks[1], (P, nkv, bs, hd))
    q = jax.random.normal(ks[2], (B, nkv * rep, hd))
    rows = list(range(1, P))
    table = np.zeros((B, nb), np.int32)
    pos = np.zeros((B,), np.int32)
    for b in range(B):
        if fills[b] > 0:
            table[b] = _chain(rows, bs, nb, fills[b])
        pos[b] = max(fills[b] - 1, 0)
    return q, kpool, vpool, jnp.asarray(table), jnp.asarray(pos)


CASES = [
    # B, nb, bs, nkv, rep, hd, fills (tokens resident per slot)
    (2, 4, 8, 2, 2, 32, (32, 32)),          # GQA grouped, full chains
    (2, 4, 8, 4, 1, 32, (32, 19)),          # n_kv_heads == n_heads (MHA)
    (3, 4, 8, 1, 4, 64, (9, 1, 27)),        # MQA, frontier pages mid-fill
    (4, 3, 16, 2, 2, 32, (17, 0, 48, 0)),   # null-page (empty) slots mixed in
    (1, 6, 8, 2, 3, 16, (41,)),             # long chain, ragged tail page
    # the served geometries (bs 16, hd 128, nkv 8): several pages per grid
    # step, so nb 20 and 33 end in a partial block (16 + 4 and 16 + 16 + 1
    # pages in bf16, 8 + 8 + 4 and 4 x 8 + 1 in f32)
    (2, 20, 16, 8, 2, 128, (300, 17)),      # qwen3-1.7b: rep 2
    (2, 33, 16, 8, 4, 128, (520, 0)),       # mistral-nemo-12b: rep 4
    (3, 20, 16, 8, 2, 128, (257, 256, 1)),  # frontier on a block boundary
                                            # (pos 256, 255) and pos 0
    (3, 40, 16, 8, 2, 128, (40, 0, 600)),   # live slots beside blocks
                                            # wholly past the frontier
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_vs_ref(case, dtype):
    B, nb, bs, nkv, rep, hd, fills = case
    q, kpool, vpool, table, pos = _case(B, nb, bs, nkv, rep, hd, fills)
    q, kpool, vpool = (a.astype(dtype) for a in (q, kpool, vpool))
    out = paged_attention(q, kpool, vpool, table, pos, kernel="pallas",
                          interpret=True)
    ref = paged_attention_ref(q, kpool, vpool, table, pos)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_empty_slot_rows_are_zero():
    """A retired/empty slot (all-zero table) must emit exact zeros from the
    kernel's skipped-page finalize — not uniform-softmax junk the engine
    would have to know to ignore for numerical reasons."""
    q, kpool, vpool, table, pos = _case(3, 4, 8, 2, 2, 32, (16, 0, 24))
    out = paged_attention(q, kpool, vpool, table, pos, kernel="pallas",
                          interpret=True)
    np.testing.assert_array_equal(np.asarray(out[1]),
                                  np.zeros_like(np.asarray(out[1])))


def test_beyond_frontier_pages_do_not_leak():
    """Pages past the causal frontier (allocated-but-unwritten budget pages
    full of stale garbage) must not affect the output: poisoning them
    changes nothing. Covered in one block of pages and in several, where
    whole blocks lie past the frontier and the frontier's block holds dead
    pages; the frontier page's own tail is poisoned too."""
    for B, nb, bs, nkv, rep, hd, fills in [(2, 6, 8, 2, 2, 32, (12, 12)),
                                           (2, 20, 16, 8, 2, 128, (40, 200))]:
        q, kpool, vpool, _, pos = _case(B, nb, bs, nkv, rep, hd, fills)
        # the whole table allocated, as with budget pages reserved ahead
        table = jnp.arange(1, B * nb + 1, dtype=jnp.int32).reshape(B, nb)
        out = paged_attention(q, kpool, vpool, table, pos, kernel="pallas",
                              interpret=True)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(paged_attention_ref(
                q, kpool, vpool, table, pos)), atol=2e-5, rtol=2e-5)
        kp, vp = kpool, vpool
        for b in range(B):
            frontier = int(pos[b]) // bs
            dead = np.asarray(table)[b, frontier + 1:]
            kp = kp.at[dead].set(jnp.nan).at[table[b, frontier], :,
                                             int(pos[b]) % bs + 1:].set(1e4)
            vp = vp.at[dead].set(jnp.inf).at[table[b, frontier], :,
                                             int(pos[b]) % bs + 1:].set(-1e4)
        out2 = paged_attention(q, kp, vp, table, pos, kernel="pallas",
                               interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_pages_per_block_rule():
    """Pages per grid step come from shapes alone: the served geometries
    get at least 4, their double buffer of K and V stays within the
    kernel's stated VMEM budget, a short table caps the block, and the
    grid a call builds is (B, ceil(nb / ppb)) whatever the table holds."""
    from repro.kernels.paged_attention.kernel import (KV_BUFFER_BYTES,
                                                      pages_per_block)
    for nb in (96, 129):                    # qwen3-1.7b, mistral-nemo-12b
        ppb = pages_per_block(nb, 16, 8, 128, 2)
        assert ppb >= 4
        assert 2 * 2 * ppb * 8 * 16 * 128 * 2 <= KV_BUFFER_BYTES
    assert pages_per_block(3, 16, 8, 128, 2) == 3
    assert pages_per_block(96, 16, 8, 128, 4) == \
        pages_per_block(96, 16, 8, 128, 2) // 2

    def grid(B, nb, fills):
        args = _case(B, nb, 16, 8, 2, 128, fills)
        jaxpr = jax.make_jaxpr(lambda *a: paged_attention(
            *a, kernel="pallas", interpret=True))(*args)
        found = []

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.append(eqn.params["grid_mapping"].grid)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found

    ppb = pages_per_block(33, 16, 8, 128, 4)
    assert grid(2, 33, (520, 0)) == grid(2, 33, (1, 17)) == \
        [(2, -(-33 // ppb))]


def test_window_guard():
    q, kpool, vpool, table, pos = _case(1, 2, 8, 2, 2, 16, (10,))
    with pytest.raises(ValueError, match="window"):
        paged_attention(q, kpool, vpool, table, pos, window=8,
                        kernel="pallas")
    with pytest.raises(ValueError, match="kernel"):
        paged_attention(q, kpool, vpool, table, pos, kernel="triton")
    # the reference path does accept a window (dense-gather semantics)
    paged_attention(q, kpool, vpool, table, pos, window=8,
                    kernel="reference")


def test_kernel_switch_inside_decode_step():
    """decode_step_paged(kernel='pallas') matches the reference gather for
    every live slot through the full layer stack (scatter + attention +
    mlp + logits)."""
    from repro.configs.base import ModelConfig
    from repro.models import transformer as T

    cfg = ModelConfig("t", "dense", 2, 32, 4, 2, 64, 97)
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    bs, nb = 4, 4
    cache = T.init_paged_cache(cfg, 2 * nb + 1, bs)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0]], jnp.int32)
    toks = jnp.asarray([[7], [11]], jnp.int32)
    pos = jnp.asarray([9, 5], jnp.int32)
    lr, cr = T.decode_step_paged(params, cfg, toks, pos, cache, table,
                                 kernel="reference")
    lp, cp = T.decode_step_paged(params, cfg, toks, pos, cache, table,
                                 kernel="pallas")
    np.testing.assert_allclose(np.asarray(lr), np.asarray(lp),
                               atol=2e-4, rtol=2e-4)
    # later layers' scattered K/V depend on earlier layers' attention
    # outputs, so the pools agree to float tolerance, not bit-exactly
    for a, b in zip(jax.tree.leaves(cr), jax.tree.leaves(cp)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)
