"""Architecture modules (`archs/<model_type>.py`), found by the
configuration's model_type: the dense decoder gives what the harness gave
before it moved there (golden data in testdata/, computed with the harness
of the commit before the move), an unknown model_type is an error, and an
architecture added as one file decides `correct` with its own reference."""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import tinycell
from chipbench import BENCH_DIR, harness, reference, spec, weights

with open(os.path.join(BENCH_DIR, "testdata", "dense_work_and_tree.json")) \
        as _f:
    GOLDEN = json.load(_f)
LOGITS = os.path.join(BENCH_DIR, "testdata", "dense_logits.npz")
SEED = 2 ** 31 + 11


def _config(name):
    tiny = {c["name"]: c for c in (tinycell.QWEN, tinycell.MISTRAL)}
    if name in tiny:
        return tiny[name]
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("quant", [None, "int8"], ids=["float32", "int8"])
@pytest.mark.parametrize("name", ["tiny-qwen", "tiny-mistral"])
def test_dense_reference_logits_are_the_golden_ones(name, quant):
    """The reference's and the int8 control's logits of every token at the
    golden rows, bit for bit."""
    c = _config(name)
    v = c["vocab_size"]
    rows = GOLDEN["rows"]
    ref = reference.Reference(spec.arch(BENCH_DIR, c["model_type"]), c,
                              GOLDEN["seed"], quant=quant)
    got = ref.evaluate([GOLDEN["sequences"][name]], [rows],
                       [GOLDEN["padded_length"]], len(rows),
                       [[t] * len(rows) for t in range(v)])["got"].T
    want = np.load(LOGITS)[f"{name}.{quant or 'float32'}"]
    assert got.dtype == want.dtype and np.array_equal(got, want)


@pytest.mark.parametrize("name", sorted(GOLDEN["configs"]))
def test_dense_work_counts_and_program_tree_are_the_golden_ones(name):
    c, want = _config(name), GOLDEN["configs"][name]
    arch = spec.arch(BENCH_DIR, c["model_type"])
    s = arch.work(c)
    assert {str(n): s.decode_token_flops(n) for n in (1, 300, 1536)} == \
        want["decode_token_flops"]
    assert {f"{a},{b}": s.prefill_flops(a, b) for a, b in
            ((0, 1), (5, 3), (0, 2048), (1000, 500))} == \
        want["prefill_flops"]
    assert [[live, list(s.paged_attention_call(live))]
            for live, _ in want["paged_attention_call"]] == \
        want["paged_attention_call"]
    tree = jax.eval_shape(lambda k: arch.program_params(k, c),
                          weights.base_key(GOLDEN["seed"]))
    assert [[jax.tree_util.keystr(p), list(a.shape), str(a.dtype)]
            for p, a in jax.tree_util.tree_flatten_with_path(tree)[0]] == \
        want["program_tree"]


def test_unknown_model_type_is_a_spec_error(tmp_path):
    root = tinycell.make(str(tmp_path), qwen_model_type="no_such_arch")
    bench = tinycell.bench_dir(root)
    with pytest.raises(spec.SpecError,
                       match=r"archs/no_such_arch\.py"):
        spec.load_cell(root, bench, "tiny-qwen.closed")
    p = subprocess.run(
        [sys.executable, os.path.join(bench, "run.py"), "--workload",
         "tiny-qwen.closed", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"], cwd=root,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=300)
    assert p.returncode == 2
    assert "archs/no_such_arch.py" in p.stderr
    assert p.stdout == ""


FLIPPED = '''"""The dense decoder, with the reference's o_proj negated."""
from archs.qwen3 import *  # noqa: F401,F403
from archs import qwen3


def layer(h, w, c, quant):
    return qwen3.layer(h, dict(w, o_proj=-w["o_proj"]), c, quant)
'''


def test_architecture_added_as_one_file_decides_correct(tmp_path):
    """A model_type whose module is one new file: the program runs the
    dense decoder as always, and the module's own reference, with one
    weight's sign flipped, makes the run not correct."""
    root = tinycell.make(str(tmp_path), archs={"flipped": FLIPPED},
                         qwen_model_type="flipped")
    bench = tinycell.bench_dir(root)
    cell = spec.load_cell(root, bench, "tiny-qwen.closed")
    assert cell.arch.__file__ == os.path.join(bench, "archs", "flipped.py")
    res = harness.run(cell, bench, root, SEED, 2.0, False,
                      t_start=time.perf_counter(), require_chip=False,
                      compile_cache=False)
    assert res["correct"] is False and res["failed"] == 0
    gap = res["checks"]["served_logit_gap_max"]
    assert gap["value"] > gap["limit"]
    assert res["checks"]["tokens_compared"]["value"] >= 8
