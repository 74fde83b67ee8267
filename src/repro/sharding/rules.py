"""Sharding rule engine: param-path patterns -> PartitionSpec, per arch.

Mesh contract (launch/mesh.py): axes ("data", "model") single-pod,
("pod", "data", "model") multi-pod. "pod" and "data" jointly shard the
batch; "model" shards tensor dims. Rules are *divisibility-aware*: a dim is
only sharded when it divides evenly, so e.g. starcoder2's 36 heads fall
back to feature-dim sharding and granite-3b's 40 experts fall back to
expert-internal TP (DESIGN.md §4) without special-casing arch names.

Stacked block params (leading n_blocks axis) get a None prepended
automatically by rank comparison.
"""
from __future__ import annotations

import re
from typing import Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# -------------------------------------------------------------- mesh helpers

def mesh_axis_size(mesh: Mesh, name: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape)).get(name, 1)


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def dp_size(mesh: Mesh) -> int:
    out = 1
    for a in batch_axes(mesh):
        out *= mesh_axis_size(mesh, a)
    return out


# -------------------------------------------------------------- constraints

def constrain(x, dims):
    """Soft sharding constraint usable inside mesh-agnostic model code.

    dims: per-dimension tag — "batch" | "model" | None. Resolved against the
    ambient mesh (set by `jax.sharding.set_mesh` in the launcher); a no-op
    when there is no mesh (CPU unit tests).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return x
    spec = []
    for i, d in enumerate(dims):
        if d == "batch":
            axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
            ok = axes and x.shape[i] % _abstract_size(mesh, axes) == 0
            spec.append(axes if ok else None)
        elif d == "model" and "model" in mesh.axis_names:
            ok = x.shape[i] % _abstract_size(mesh, ("model",)) == 0
            spec.append("model" if ok else None)
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _abstract_size(mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    out = 1
    for a in axes:
        out *= sizes[a]
    return out


# -------------------------------------------------------------- param rules

def _spec_for(path: str, shape, cfg, mesh: Mesh) -> P:
    """Core rule table. `path` is the /-joined pytree path of the leaf."""
    mdl = mesh_axis_size(mesh, "model")

    def shard_if(dim_size, axis="model"):
        return axis if dim_size % mdl == 0 and mdl > 1 else None

    nd = len(shape)

    # ---- embeddings: shard vocab over model (biggest single tensor).
    # embed/table is (V, d); lm_head is (d, V) — sharding lm_head's dim -2
    # would split the CONTRACTING dim and all-reduce full f32 logits
    # (34 GB/device at 131k vocab — §Perf iteration 3).
    if path.endswith("embed/table"):
        return P(shard_if(shape[-2]), None) if nd >= 2 else P(None)
    if path.endswith("lm_head"):
        return P(None, shard_if(shape[-1])) if nd >= 2 else P(None)

    # ---- norms / scalars / small vectors: replicate
    if "norm" in path or path.endswith(("scale", "b_r", "b_i", "Lambda",
                                        "A_log", "dt_bias", "D", "conv_b",
                                        "b")):
        return P(*([None] * nd))

    # ---- MoE experts: expert-parallel if divisible, else per-expert TP
    if re.search(r"ffn/(w_gate|w_up)$", path) and cfg.moe:
        E, dff = shape[-3], shape[-1]
        if E % mdl == 0:
            return P(*([None] * (nd - 3)), "model", None, None)
        return P(*([None] * (nd - 3)), None, None, shard_if(dff))
    if path.endswith("ffn/w_down") and cfg.moe:
        E, dff = shape[-3], shape[-2]
        if E % mdl == 0:
            return P(*([None] * (nd - 3)), "model", None, None)
        return P(*([None] * (nd - 3)), None, shard_if(dff), None)
    if path.endswith("ffn/router"):
        return P(*([None] * nd))

    # ---- dense mlp / attention projections: megatron col/row split
    if re.search(r"(w_gate|w_up|wq|wk|wv|in_proj|w_x|w_gate_branch|w_r|w_i)$",
                 path):
        return P(*([None] * (nd - 2)), None, shard_if(shape[-1]))
    if re.search(r"(w_down|wo|out_proj)$", path):
        return P(*([None] * (nd - 2)), shard_if(shape[-2]), None)
    if path.endswith("conv_w"):
        return P(*([None] * (nd - 2)), None, shard_if(shape[-1]))

    return P(*([None] * nd))


def param_specs(cfg, params_shape, mesh: Mesh):
    """params_shape: pytree of ShapeDtypeStruct (jax.eval_shape of init)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        pstr = "/".join(_pkey(p) for p in path)
        specs.append(_spec_for(pstr, leaf.shape, cfg, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _pkey(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


# -------------------------------------------------------------- other trees

def opt_state_specs(cfg, opt_state_shape, pspecs):
    """AdamW/SGD moments mirror the param specs; step counter replicates.
    State layout: (step, moment_tree, ...) — every moment tree mirrors."""
    step_s, *moments = opt_state_shape
    del step_s
    return type(opt_state_shape)(P(), *[_mirror(m, pspecs) for m in moments])


def _mirror(tree_shape, pspecs):
    return jax.tree.map(lambda _, s: s, tree_shape, pspecs)


def batch_specs(cfg, batch_shape, mesh: Mesh):
    """Token/label/embeds batches: batch dim over ("pod","data") when it
    divides, else fall back to "data", else replicate."""
    bax = batch_axes(mesh)

    def spec(leaf):
        b = leaf.shape[0]
        for axes in (bax, bax[-1:],):
            size = 1
            for a in axes:
                size *= mesh_axis_size(mesh, a)
            if axes and b % size == 0:
                return P(axes, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(spec, batch_shape)


def cache_specs(cfg, cache_shape, mesh: Mesh):
    """Decode caches. Attention k/v: batch over ("pod","data") if divisible;
    cache *sequence* over "model" (flash-decoding style — GQA kv heads are
    too few to shard). SSM/RG-LRU states: batch only. Leading n_blocks axis
    (rank sentinel) gets None."""
    bax = batch_axes(mesh)
    mdl = mesh_axis_size(mesh, "model")

    def spec(path, leaf):
        pstr = "/".join(_pkey(p) for p in path)
        shape = leaf.shape
        # block-stacked leaves have n_blocks leading: detect via path
        lead = 1 if pstr.startswith("blocks") else 0
        dims = [None] * leaf.ndim
        bdim = lead
        b = shape[bdim]
        size = dp_size(mesh)
        if bax and b % size == 0:
            dims[bdim] = bax
        elif "data" in mesh.axis_names and b % mesh_axis_size(mesh, "data") == 0:
            dims[bdim] = ("data",)
        if re.search(r"(^|/)(k|v|cross_k|cross_v|pos|cross_pos)$", pstr):
            sdim = bdim + 1
            if shape[sdim] % mdl == 0 and mdl > 1:
                dims[sdim] = "model"
        return P(*dims)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, l) for p, l in flat])


def prefill_cache_specs(cfg, cache_shape, mesh: Mesh):
    """Prefill-output caches (§Perf iteration 1): k/v keep the layout the
    attention matmul produces — batch over data, *head_dim* over model
    (head_dim is 64/128/256 for every assigned arch, always divisible) —
    instead of the decode cache's seq-over-model layout. The seq layout
    demanded a feature->seq reshard that GSPMD lowered to replicate-then-
    slice (~42 GB/layer/device on mistral-nemo prefill_32k); this layout is
    reachable with a local all-to-all. The prefill->decode layout switch
    happens once per request at admission, amortized over the whole decode.
    """
    bax = batch_axes(mesh)
    mdl = mesh_axis_size(mesh, "model")

    def spec(path, leaf):
        pstr = "/".join(_pkey(p) for p in path)
        lead = 1 if pstr.startswith("blocks") else 0
        dims = [None] * leaf.ndim
        b = leaf.shape[lead]
        size = dp_size(mesh)
        if bax and b % size == 0:
            dims[lead] = bax
        elif "data" in mesh.axis_names and b % mesh_axis_size(mesh, "data") == 0:
            dims[lead] = ("data",)
        if re.search(r"(^|/)(k|v|cross_k|cross_v)$", pstr):
            if leaf.shape[-1] % mdl == 0 and mdl > 1:
                dims[-1] = "model"
        return P(*dims)

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(treedef,
                                        [spec(p, l) for p, l in flat])


def to_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))
