import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, and extract the roofline raw terms from the compiled
artifact. MUST be the process entry point (device count locks at first jax
init — hence the two lines above, before any other import).

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --all                 # 40 pairs, single-pod
  python -m repro.launch.dryrun --all --multi-pod     # + the (2,16,16) mesh
Outputs one JSON per case under benchmarks/dryrun_results/.
"""
import argparse
import json
import re
import time
import traceback

import jax

from repro.configs import registry
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch.shapes import SHAPES, make_case
from repro.sharding import rules as R

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> dict:
    """Sum per-device result bytes of every collective op in optimized HLO.
    Tuple-shaped (variadic) collectives count every element."""
    out = {c: {"count": 0, "bytes": 0} for c in _COLLECTIVES}
    # e.g.:  %all-reduce.5 = bf16[16,320]{1,0} all-reduce(...)
    #        ROOT %t = (f32[4]{0}, f32[8]{0}) all-to-all(...)
    # async pairs lower to <op>-start/-done; count the -start only.
    line_re = re.compile(
        r"=\s*(\(?[^=\n]*?)\s+(" + "|".join(_COLLECTIVES) +
        r")(?:-start)?\(")
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for m in line_re.finditer(hlo_text):
        shapes, op = m.group(1), m.group(2)
        b = sum(_shape_bytes(dt, dims)
                for dt, dims in shape_re.findall(shapes))
        out[op]["count"] += 1
        out[op]["bytes"] += b
    return out


def _compile_case(cfg, shape_name, mesh, *, microbatches=None, remat=None):
    t0 = time.perf_counter()
    with jax.sharding.set_mesh(mesh):
        case = make_case(cfg, shape_name, mesh, microbatches=microbatches,
                         remat=remat)
        jitted = jax.jit(case["fn"],
                         in_shardings=R.to_shardings(mesh, case["in_specs"]),
                         out_shardings=R.to_shardings(mesh,
                                                      case["out_specs"]),
                         donate_argnums=case["donate"])
        lowered = jitted.lower(*case["args"])
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0
    return case, compiled, t_lower, t_compile


def _measure(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    colls = parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll_bytes": float(sum(c["bytes"] for c in colls.values())),
            "colls": colls}


def _calib_cfg(cfg, k_dec: int, k_enc: int):
    n_layers = k_dec * len(cfg.block_pattern) + len(cfg.tail_pattern)
    kw = dict(n_layers=n_layers, scan_layers=False)
    if cfg.is_encdec:
        kw["n_enc_layers"] = k_enc
    return cfg.replace(**kw)


def calibrate(cfg, shape_name: str, mesh) -> dict:
    """XLA counts while-loop bodies once, so the scanned compile undercounts
    FLOPs/bytes/collective traffic by the trip counts. Measure the marginal
    cost of one extra (unrolled) block at depth 1 vs 2 and extrapolate
    linearly: corrected = c1 + (n_blocks-1) * (c2 - c1) [+ encoder term].
    Calibration runs at microbatches=1; the mb scan only re-reads params
    ((mb-1) * param bytes added to the memory term downstream)."""
    is_train = SHAPES[shape_name].kind == "train"
    mb = dict(microbatches=1) if is_train else {}
    _, comp1, _, _ = _compile_case(_calib_cfg(cfg, 1, 1), shape_name, mesh, **mb)
    m1 = _measure(comp1)
    _, comp2, _, _ = _compile_case(_calib_cfg(cfg, 2, 1), shape_name, mesh, **mb)
    m2 = _measure(comp2)
    d_block = {k: m2[k] - m1[k] for k in ("flops", "bytes", "coll_bytes")}
    nb = cfg.n_blocks
    corrected = {k: m1[k] + (nb - 1) * d_block[k]
                 for k in ("flops", "bytes", "coll_bytes")}
    if cfg.is_encdec:
        _, compe, _, _ = _compile_case(_calib_cfg(cfg, 1, 2), shape_name,
                                       mesh, **mb)
        me = _measure(compe)
        d_enc = {k: me[k] - m1[k] for k in ("flops", "bytes", "coll_bytes")}
        for k in corrected:
            corrected[k] += (cfg.n_enc_layers - 1) * d_enc[k]
    corrected["delta_block"] = d_block
    corrected["depth1"] = {k: m1[k] for k in ("flops", "bytes", "coll_bytes")}
    return corrected


def run_case(arch_id: str, shape_name: str, mesh, mesh_tag: str, *,
             microbatches=None, remat=None, verbose=True,
             calibrated=True) -> dict:
    cfg = registry.get(arch_id)
    case, compiled, t_lower, t_compile = _compile_case(
        cfg, shape_name, mesh, microbatches=microbatches, remat=remat)
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):          # older API returned [dict]
        cost = cost[0]
    hlo = compiled.as_text()
    colls = parse_collectives(hlo)
    n_dev = mesh.devices.size

    mem_fields = {}
    for f in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        mem_fields[f] = getattr(mem, f, None)

    rec = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_tag,
        "n_devices": int(n_dev),
        "flops_per_device": float(cost.get("flops", 0.0)),
        "bytes_accessed_per_device": float(cost.get("bytes accessed", 0.0)),
        "collectives": colls,
        "collective_bytes_per_device": int(sum(c["bytes"]
                                               for c in colls.values())),
        "memory": mem_fields,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "meta": {k: v for k, v in case["meta"].items() if k != "cfg"},
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    if calibrated:
        corr = calibrate(registry.get(arch_id), shape_name, mesh)
        mbs = case["meta"].get("microbatches", 1)
        # mb-scan re-reads params each microbatch: add (mb-1) x param traffic
        pbytes = cfg.param_count() * 2 / n_dev    # bf16, sharded
        corr["bytes"] += (mbs - 1) * pbytes
        rec["corrected_per_device"] = corr
    if verbose:
        print(f"[dryrun] {arch_id:24s} {shape_name:12s} {mesh_tag:10s} "
              f"flops/dev={rec['flops_per_device']:.3e} "
              f"coll={rec['collective_bytes_per_device']:.3e}B "
              f"lower={t_lower:.1f}s compile={t_compile:.1f}s")
        print(f"  memory_analysis: {mem_fields}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=registry.ARCH_IDS)
    ap.add_argument("--shape", default=None, choices=sorted(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true",
                    help="also run the (2,16,16) 512-chip mesh")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--debug-mesh", action="store_true",
                    help="(2,2)/(2,2,2) mesh for fast iteration")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat", action="store_true", default=None)
    ap.add_argument("--out", default="benchmarks/dryrun_results")
    args = ap.parse_args()

    mk = make_debug_mesh if args.debug_mesh else make_production_mesh
    meshes = []
    if not args.multi_pod_only:
        meshes.append((mk(multi_pod=False), "pod1"))
    if args.multi_pod or args.multi_pod_only:
        meshes.append((mk(multi_pod=True), "pod2"))

    archs = registry.ARCH_IDS if args.arch is None else [args.arch]
    shapes = sorted(SHAPES) if args.shape is None else [args.shape]
    if not args.all and args.arch is None and args.shape is None:
        ap.error("pass --arch/--shape or --all")

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for mesh, tag in meshes:
        for arch in archs:
            for shape in shapes:
                key = f"{arch}__{shape}__{tag}"
                try:
                    rec = run_case(arch, shape, mesh, tag,
                                   microbatches=args.microbatches,
                                   remat=args.remat)
                    with open(os.path.join(args.out, key + ".json"), "w") as f:
                        json.dump(rec, f, indent=1)
                except Exception as e:
                    failures.append((key, repr(e)))
                    print(f"[dryrun] FAIL {key}: {e}")
                    traceback.print_exc(limit=5)
    print(f"\n[dryrun] done: {len(failures)} failures")
    for k, e in failures:
        print("  FAIL", k, e)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
