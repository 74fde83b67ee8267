"""The plain float32 reference against the program's served paths, at a
small size on the CPU, for both configurations' code paths (Qwen3-style:
qk-norm, tied head; Mistral-style: untied head, heads x head_dim != width):
bulk prefill over the paged pool, then decode steps through the Pallas
paged-decode kernel (interpret mode here), compared on logits."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tinycell
from chipbench import reference, system, weights

SEED = 2 ** 33 + 5          # above 32 bits on purpose


def _served_logits(c, seed, prompt, n_decode):
    from repro.models import transformer as T
    mcfg = system.model_config(c)
    s = c["serving"]
    bs, nb = s["block_size"], s["cache_len"] // s["block_size"]
    params = system.make_params(c, mcfg, seed)
    cache = T.init_paged_cache(mcfg, s["pool_blocks"], bs)
    table = np.zeros((s["batch_slots"], nb), np.int32)
    table[0] = np.arange(1, nb + 1)          # slot 0 owns pages 1..nb
    prefill = jax.jit(functools.partial(T.forward_prefill_paged, cfg=mcfg))
    decode = jax.jit(functools.partial(T.decode_step_paged, cfg=mcfg,
                                       kernel="pallas"))
    P = len(prompt)
    toks = np.zeros((1, 32), np.int32)
    toks[0, :P] = prompt
    lg, cache = prefill(params, tokens=jnp.asarray(toks),
                        start=jnp.int32(0), n_tok=jnp.int32(P), cache=cache,
                        table=jnp.asarray(table[0]))
    rows = [np.asarray(lg[0, :P])]
    seq = list(prompt)
    for i in range(n_decode):
        seq.append(int(np.argmax(rows[-1][-1])))
        tok = np.zeros((s["batch_slots"], 1), np.int32)
        tok[0, 0] = seq[-1]
        pos = np.zeros((s["batch_slots"],), np.int32)
        pos[0] = P + i
        lg, cache = decode(params, tokens=jnp.asarray(tok),
                           pos=jnp.asarray(pos), cache=cache,
                           table=jnp.asarray(table))
        rows.append(np.asarray(lg[0, :1]))
    return seq, np.concatenate(rows)


@pytest.mark.parametrize("c", [tinycell.QWEN, tinycell.MISTRAL],
                         ids=["qwen3-path", "mistral-path"])
def test_reference_matches_served_prefill_and_paged_decode(c):
    prompt = np.random.default_rng(0).integers(0, c["vocab_size"],
                                               21).tolist()
    seq, served = _served_logits(c, SEED, prompt, n_decode=5)
    n, v = len(seq), c["vocab_size"]
    every = [list(range(v))] * n          # every token at every row
    ref = reference.Reference(c, SEED).evaluate(
        [seq], [list(range(n))], [64], max_rows=n,
        token_sets=np.asarray(every).T.tolist())
    full = ref["got"].T                    # (rows, vocab)
    assert served.shape == full.shape
    scale = float(np.abs(full).max())
    # both in float32: they differ by summation order only
    assert float(np.abs(served - full).max()) <= 1e-4 * max(1.0, scale)
    assert np.array_equal(ref["argmax"], full.argmax(-1))
    assert np.allclose(ref["best"], full.max(-1))


def test_int8_control_departs_from_the_reference():
    c = tinycell.QWEN
    seq = np.random.default_rng(1).integers(0, c["vocab_size"], 40).tolist()
    args = ([seq], [list(range(40))], [64], 40)
    ref = reference.Reference(c, SEED).evaluate(*args)
    ctl = reference.Reference(c, SEED, quant="int8").evaluate(*args)
    assert not np.allclose(ref["best"], ctl["best"], atol=1e-4)


def test_program_and_reference_draw_the_same_weights():
    c = tinycell.MISTRAL
    key = weights.base_key(SEED)
    tree = jax.jit(lambda k: system._program_tree(k, c))(key)
    blk = tree["blocks"][0]
    names = {"q_proj": blk["attn"]["wq"], "o_proj": blk["attn"]["wo"],
             "down_proj": blk["ffn"]["w_down"],
             "post_norm": blk["norm2"]["scale"]}
    for i in range(c["num_hidden_layers"]):
        w = weights.layer(key, c, i)
        for name, stacked in names.items():
            assert np.array_equal(np.asarray(stacked[i]),
                                  np.asarray(w[name])), (name, i)
    g = weights.globals_(key, c)
    assert np.array_equal(np.asarray(tree["lm_head"]),
                          np.asarray(g["lm_head"]))


def test_seeds_beyond_32_bits_stay_distinct():
    a = jax.random.key_data(weights.base_key(5))
    b = jax.random.key_data(weights.base_key(5 + 2 ** 32))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
    with pytest.raises(ValueError):
        weights.base_key(-1)
