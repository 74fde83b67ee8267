"""Paged-attention decode kernel vs the dense-gather oracle (ref.py),
interpret mode (kernel body executes in Python on CPU; grid/BlockSpecs are
identical to the TPU lowering). Covers the GQA group shapes, partially
filled frontier pages, null-page (empty/retired) slots, and the
window=None-only guard, plus the wiring through layers/transformer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref


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
    changes nothing."""
    q, kpool, vpool, table, pos = _case(2, 6, 8, 2, 2, 32, (12, 12))
    out = paged_attention(q, kpool, vpool, table, pos, kernel="pallas",
                          interpret=True)
    frontier = 12 // 8                       # pages 2.. are beyond
    poison_rows = np.asarray(table)[:, frontier + 1:].ravel()
    poison_rows = poison_rows[poison_rows != 0]
    kp = kpool.at[poison_rows].set(1e4)
    vp = vpool.at[poison_rows].set(-1e4)
    out2 = paged_attention(q, kp, vp, table, pos, kernel="pallas",
                           interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


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
