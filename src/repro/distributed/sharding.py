"""Logical-axis sharding rules (MaxText-style) — the hillclimbing lever.

Model code annotates activations/params with LOGICAL axis names; a rule
table maps them to mesh axes. Swapping a rule re-shards the whole model
without touching model code. Rules are thread-local + context-managed so
the dry-run can sweep sharding variants.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[str, Tuple[str, ...], None]

# Default rules for the production meshes.
#   single-pod mesh axes: ("data", "model")
#   multi-pod mesh axes:  ("pod", "data", "model")
# "pod" appears in batch/dp rules only when present in the mesh (filtered).
DEFAULT_RULES: Dict[str, MeshAxes] = {
    # --- activation axes ---
    "act_batch": ("pod", "data"),      # DP over pod+data
    "act_seq": None,                    # seq inside attention/MLP (full per TP rank)
    "act_res_seq": "model",            # Megatron-style sequence parallelism on the
                                        # residual stream (scan carries shard 256-way)
    "act_q_seq": "model",              # query seq inside attention: seq-sharded
                                        # attention (scores S/16×T per device);
                                        # flip to None to restore head-TP attention
    "act_kv_seq": None,                 # KV-cache length (long-context override)
    "act_heads": "model",              # TP attention heads
    "act_kv_heads": None,               # GQA K/V replicated (small); set "model"
                                        # together with act_q_seq=None for head-TP
    "act_embed": None,
    "act_mlp": "model",
    "act_experts": "model",            # EP
    "act_mlp_inner": None,              # expert-FFN hidden dim (E already on model)
    "act_moe_groups": ("model", "pod", "data"),  # chunk-major MoE groups: the
                                        # (chunk, batch)-ordered group dim is
                                        # byte-identical to (batch:dp, seq:model)
    "act_moe_dispatch": ("pod", "data"),  # expert-buffer token dim (G) when the
                                        # model axis is spent on experts
    "act_vocab": "model",
    # --- parameter axes ---
    "embed": "data",                   # FSDP: shard the d_model dim of weights
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "vocab": "model",
    "layers": None,                     # scan-stacked dim
    "ssm_inner": "model",
    "unsharded": None,
}


class _State(threading.local):
    def __init__(self):
        self.rules: Dict[str, MeshAxes] = dict(DEFAULT_RULES)
        self.mesh: Optional[Mesh] = None
        self.enabled: bool = False


_STATE = _State()


@contextlib.contextmanager
def sharding_rules(mesh: Mesh, overrides: Optional[Dict[str, MeshAxes]] = None):
    """Activate logical-axis constraint application under ``mesh``."""
    prev = (_STATE.rules, _STATE.mesh, _STATE.enabled)
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    _STATE.rules, _STATE.mesh, _STATE.enabled = rules, mesh, True
    try:
        yield
    finally:
        _STATE.rules, _STATE.mesh, _STATE.enabled = prev


def logical_to_spec(logical: Sequence[Optional[str]],
                    mesh: Optional[Mesh] = None,
                    rules: Optional[Dict[str, MeshAxes]] = None) -> P:
    """Map logical axis names to a PartitionSpec valid for ``mesh``."""
    mesh = mesh or _STATE.mesh
    rules = rules or _STATE.rules
    mesh_axes = set(mesh.axis_names) if mesh is not None else set()
    spec = []
    for name in logical:
        if name is None:
            spec.append(None)
            continue
        target = rules.get(name)
        if target is None:
            spec.append(None)
        elif isinstance(target, str):
            spec.append(target if target in mesh_axes else None)
        else:
            filtered = tuple(a for a in target if a in mesh_axes)
            spec.append(filtered if filtered else None)
    return P(*spec)


def _axis_size(mesh: Mesh, target: MeshAxes) -> int:
    if target is None:
        return 1
    if isinstance(target, str):
        return mesh.shape[target]
    size = 1
    for a in target:
        size *= mesh.shape[a]
    return size


def logical_axis_size(name: str) -> int:
    """Devices the active mesh lays logical axis ``name`` over; 1 outside
    a mesh."""
    if not _STATE.enabled or _STATE.mesh is None:
        return 1
    entry = logical_to_spec((name,))[0]
    return 1 if entry is None else _axis_size(_STATE.mesh, entry)


def drop_indivisible(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Sanitize a spec against concrete dims: drop entries that don't divide
    the dim size (e.g. kv_heads=4 over a 16-way axis stays replicated) and
    drop repeated mesh axes (first occurrence wins) so rule overrides like
    kv_seq→("data","model") can coexist with batch→"data" on small batches.
    """
    used: set = set()
    out = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if entry is None:
            out.append(None)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        axes = tuple(a for a in axes if a not in used)
        # longest prefix of the axis tuple whose product divides the dim
        # (e.g. a 128-row GRAFT subset over ("pod","data","model")=512 chips
        # falls back to ("pod","data")=32-way instead of replicating)
        while axes and (dim % _axis_size(mesh, axes) != 0):
            axes = axes[:-1]
        if not axes:
            out.append(None)
            continue
        entry2: MeshAxes = axes[0] if len(axes) == 1 else axes
        used.update(axes)
        out.append(entry2)
    return P(*out)


def constrain(x: jax.Array, logical: Sequence[Optional[str]]) -> jax.Array:
    """``with_sharding_constraint`` by logical names; no-op outside a mesh."""
    if not _STATE.enabled or _STATE.mesh is None:
        return x
    spec = drop_indivisible(logical_to_spec(logical), x.shape, _STATE.mesh)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_STATE.mesh, spec))


def shard_kernel(fn, args: Sequence[jax.Array],
                 in_logical: Sequence[Sequence[Optional[str]]],
                 out_logical: Sequence[Optional[str]]):
    """``fn(*args)`` for a function that runs Pallas kernels.

    XLA cannot partition a Mosaic kernel, so under a multi-device mesh the
    call runs in a ``shard_map`` over the whole mesh: each device runs the
    kernel on its shard of the logical axes, and replicated operands are
    computed redundantly. ``out_logical`` names the axes of every output.
    Without a mesh, on one device, or already inside a ``shard_map``, this
    is the plain call.
    """
    mesh = _STATE.mesh if _STATE.enabled else None
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return fn(*args)

    def spec(logical, shape):
        return drop_indivisible(logical_to_spec(logical), shape, mesh)

    out_shapes = jax.eval_shape(fn, *args)
    out_specs = jax.tree_util.tree_map(lambda s: spec(out_logical, s.shape),
                                       out_shapes)
    in_specs = tuple(spec(lg, a.shape) for a, lg in zip(args, in_logical))
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)(*args)


def named_sharding(mesh: Mesh, logical: Sequence[Optional[str]],
                   rules: Optional[Dict[str, MeshAxes]] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical, mesh, rules))


def param_sharding_tree(params_logical, mesh: Mesh,
                        rules: Optional[Dict[str, MeshAxes]] = None):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree_util.tree_map(
        lambda lg: named_sharding(mesh, lg, rules), params_logical,
        is_leaf=lambda x: isinstance(x, tuple))


def param_shardings(abstract_params, params_logical, mesh: Mesh,
                    rules: Optional[Dict[str, MeshAxes]] = None):
    """NamedShardings for a param pytree, with indivisible axes dropped.

    ``abstract_params``: pytree of ShapeDtypeStruct (from ``jax.eval_shape``);
    ``params_logical``: matching pytree of logical-axis name tuples.
    """
    def one(abstract, logical):
        spec = logical_to_spec(logical, mesh, rules)
        spec = drop_indivisible(spec, abstract.shape, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map(
        one, abstract_params, params_logical,
        is_leaf=lambda x: isinstance(x, tuple))
