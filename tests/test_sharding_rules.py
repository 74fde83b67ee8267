"""Sharding rule engine: divisibility-aware specs, and actual lowering of
reduced models on a tiny (2,2)/(2,2,2) host mesh — the fast proxy for the
production dry-run (which runs the real 16x16 / 2x16x16 meshes)."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import registry
from repro.launch.shapes import make_case, params_shapes
from repro.sharding import rules as R

def _mesh(shape=(2, 2), axes=("data", "model")):
    n = int(np.prod(shape))
    if jax.device_count() < n:
        pytest.skip(f"needs {n} devices (run under "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count=8)")
    return Mesh(np.asarray(jax.devices()[:n]).reshape(shape), axes)


def test_param_specs_divisibility_rules():
    mesh = _mesh()
    cfg = registry.get("qwen3-1.7b")
    specs = R.param_specs(cfg, params_shapes(cfg), mesh)
    flat = {"/".join(R._pkey(p) for p in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    assert flat["embed/table"] == P("model", None)
    wq = [v for k, v in flat.items() if k.endswith("attn/wq")]
    assert all(s == P(None, None, "model") for s in wq)   # stacked blocks
    wo = [v for k, v in flat.items() if k.endswith("attn/wo")]
    assert all(s == P(None, "model", None) for s in wo)
    norms = [v for k, v in flat.items() if "norm" in k]
    assert all(all(a is None for a in s) for s in norms)


def test_moe_expert_parallel_vs_internal_tp():
    mesh = _mesh()
    # 32 experts % 2 == 0 -> expert-parallel
    cfg = registry.get("granite-moe-1b-a400m")
    specs = R.param_specs(cfg, params_shapes(cfg), mesh)
    flat = {"/".join(R._pkey(p) for p in path): s
            for path, s in jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    gates = [v for k, v in flat.items() if k.endswith("ffn/w_gate")]
    assert all(s == P(None, "model", None, None) for s in gates)


def test_batch_specs_fallbacks():
    mesh = _mesh()
    cfg = registry.get("qwen3-1.7b")
    shapes = {"tokens": jax.ShapeDtypeStruct((8, 16), np.int32)}
    specs = R.batch_specs(cfg, shapes, mesh)
    assert specs["tokens"] == P(("data",), None)
    odd = {"tokens": jax.ShapeDtypeStruct((3, 16), np.int32)}
    specs = R.batch_specs(cfg, odd, mesh)
    assert specs["tokens"] == P(None, None)       # indivisible -> replicate


@pytest.mark.parametrize("arch", ["qwen3-1.7b", "granite-moe-1b-a400m",
                                  "mamba2-130m", "recurrentgemma-9b",
                                  "seamless-m4t-large-v2"])
@pytest.mark.parametrize("shape", ["train_4k", "decode_32k"])
def test_reduced_case_lowers_on_debug_mesh(arch, shape):
    """Lower+compile REDUCED configs on the tiny mesh (fast sanity for the
    production dry-run path; full configs are exercised by dryrun.py)."""
    mesh = _mesh()
    cfg = registry.get(arch, reduced=True).replace(
        window=None if shape == "train_4k" else 16)
    # shrink the shape cases to reduced scale by monkeypatching the case
    from repro.launch import shapes as S
    case_obj = S.SHAPES[shape]
    small = S.ShapeCase(case_obj.name, case_obj.kind, 64, 8)
    try:
        S.SHAPES[shape] = small
        with jax.sharding.set_mesh(mesh):
            case = make_case(cfg, shape, mesh, microbatches=2
                             if case_obj.kind == "train" else None)
            jitted = jax.jit(
                case["fn"],
                in_shardings=R.to_shardings(mesh, case["in_specs"]),
                out_shardings=R.to_shardings(mesh, case["out_specs"]),
                donate_argnums=case["donate"])
            compiled = jitted.lower(*case["args"]).compile()
            assert compiled.cost_analysis() is not None
    finally:
        S.SHAPES[shape] = case_obj
