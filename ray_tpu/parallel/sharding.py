"""Logical-axis sharding rules (GSPMD layout declarations).

The reference's DP/FSDP come from torch DDP/FSDP wrappers
(ray: python/ray/train/torch/train_loop_utils.py:158,184); here every
parallelism strategy is a *layout*: logical array axes map to mesh axes and
XLA inserts the collectives (ZeRO-3 ≈ params sharded over "fsdp";
Megatron-TP ≈ hidden/heads sharded over "tensor"; sequence parallelism ≈
tokens sharded over "seq").
"""
from __future__ import annotations

import math

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axes (None = replicated).
# fsdp shards the *largest* param axis; tensor shards the Megatron axis.
LOGICAL_RULES: dict[str, tuple | str | None] = {
    "batch": ("data", "fsdp"),   # batch sharded over dp × fsdp (fsdp reuses
                                 # the data axis for activations, ZeRO style)
    "seq": "seq",                # sequence/context parallel axis
    "embed": "fsdp",             # param embedding dim: fsdp-sharded
    "mlp": "tensor",             # ffn hidden: Megatron column/row split
    "heads": "tensor",           # attention heads: tensor-parallel
    "kv_heads": "tensor",
    "head_dim": None,
    "vocab": "tensor",           # output projection vocab split
    "expert": "expert",          # MoE expert dimension
    "layers": None,              # scan-stacked layer dim stays replicated
}


def logical_spec(logical_axes: tuple[str | None, ...],
                 rules: dict | None = None) -> P:
    """Translate logical axis names to a PartitionSpec via the rules table."""
    rules = rules or LOGICAL_RULES
    spec = []
    used: set[str] = set()
    for name in logical_axes:
        if name is None:
            spec.append(None)
            continue
        mesh_axes = rules.get(name)
        if mesh_axes is None:
            spec.append(None)
        elif isinstance(mesh_axes, str):
            spec.append(None if mesh_axes in used else mesh_axes)
            used.add(mesh_axes)
        else:
            avail = tuple(a for a in mesh_axes if a not in used)
            used.update(avail)
            spec.append(avail if avail else None)
    return P(*spec)


def logical_sharding(mesh: Mesh, logical_axes: tuple[str | None, ...],
                     rules: dict | None = None) -> NamedSharding:
    spec = logical_spec(logical_axes, rules)
    # Drop mesh axes of size 1?  Not needed: XLA treats them as replicated.
    spec = P(*[_prune(mesh, s) for s in spec])
    return NamedSharding(mesh, spec)


def current_abstract_mesh():
    """The mesh in the current jit trace context, or None (shared probe —
    with_sharding_constraint, ring attention, and embed_lookup all need
    it)."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
    except Exception:  # noqa: BLE001 - outside jit / no mesh
        return None
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def logical_axis_size(logical: str, mesh=None,
                      rules: dict | None = None) -> int:
    """Product of mesh-axis sizes a logical axis maps to under the rules
    (1 = effectively unsharded).  Lets model code branch on layout
    without hardcoding physical axis names."""
    if mesh is None:
        mesh = current_abstract_mesh()
    if mesh is None:
        return 1
    entry = (rules or LOGICAL_RULES).get(logical)
    if entry is None:
        return 1
    names = (entry,) if isinstance(entry, str) else entry
    size = 1
    for a in names:
        size *= mesh.shape.get(a, 1)
    return size


def _prune(mesh: Mesh, entry, exclude: set | frozenset = frozenset()):
    """Remove axes not present in the mesh (lets one rules table serve
    meshes with fewer axes) or in `exclude` (manual shard_map axes)."""
    if entry is None:
        return None
    if isinstance(entry, str):
        return entry if entry in mesh.axis_names and entry not in exclude \
            else None
    kept = tuple(a for a in entry
                 if a in mesh.axis_names and a not in exclude)
    return kept if kept else None


def shard_params(params, axes_tree, mesh: Mesh, rules: dict | None = None):
    """Device-put a param pytree according to its logical-axes pytree."""
    shardings = jax.tree.map(
        lambda ax: logical_sharding(mesh, ax, rules), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple))
    return jax.device_put(params, shardings)


def param_shardings(axes_tree, mesh: Mesh, rules: dict | None = None):
    return jax.tree.map(
        lambda ax: logical_sharding(mesh, ax, rules), axes_tree,
        is_leaf=lambda x: isinstance(x, tuple))


def with_sharding_constraint(x, logical_axes: tuple[str | None, ...],
                             mesh: Mesh | None = None,
                             rules: dict | None = None):
    """Annotate an intermediate value's layout inside jit
    (jax.lax.with_sharding_constraint with logical names)."""
    if mesh is None:
        mesh = current_abstract_mesh()
        if mesh is None:
            return x
    if set(mesh.axis_names) <= _manual_axes(mesh):
        # Fully-manual shard_map: layout is already explicit per-shard
        # and constraints are meaningless there.
        return x
    spec = auto_axes_spec(mesh, logical_axes, rules)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec) if isinstance(mesh, Mesh) else spec)


def auto_axes_spec(mesh, logical_axes: tuple[str | None, ...],
                   rules: dict | None = None) -> P:
    """logical_spec on the axes of `mesh` that GSPMD still owns.  Inside
    a *partially* manual shard_map (e.g. the pipeline: "stage" manual,
    the rest auto) layouts still apply over the auto axes — the manual
    ones are stripped from the spec."""
    manual = _manual_axes(mesh)
    return P(*[_prune(mesh, s, exclude=manual)
               for s in logical_spec(logical_axes, rules)])


def attention_shard_specs(q_shape, kv_shape, mesh=None):
    """Layout for one attention-kernel call per shard.  GSPMD cannot
    partition a Mosaic kernel, so the caller wraps it in a shard_map
    over the axes GSPMD still owns; this is the one place that says how
    [b, s, h, d] q and k/v split over them.  Returns None when there is
    nothing to split (no mesh, or every auto axis of size 1), else
    `(mesh, axis_names, q_spec, kv_spec)`.

    Batch goes over the "batch" axes when they divide it; the sequence
    stays whole (a seq-sharded layout takes ring attention).  Heads are
    decided ONCE for q, k and v: contiguous head chunks keep every GQA
    group on one shard only if the head axes divide the kv-head count
    too, so q heads split when they do (k/v split alike) or when there
    is a single kv head (k/v stay whole, every shard reads it).  Any
    other count keeps heads whole on all three — splitting q alone
    would pair its heads with the wrong kv groups."""
    if mesh is None:
        mesh = current_abstract_mesh()
    if mesh is None:
        return None
    auto = set(mesh.axis_names) - _manual_axes(mesh)
    if all(mesh.shape[a] == 1 for a in auto):
        return None
    batch, _, heads, _ = auto_axes_spec(
        mesh, ("batch", None, "heads", None))

    def size(entry) -> int:
        names = (entry,) if isinstance(entry, str) else (entry or ())
        return math.prod(mesh.shape[a] for a in names)

    if q_shape[0] % size(batch):
        batch = None
    hq, hkv = q_shape[2], kv_shape[2]
    kv_heads = heads
    if hq % size(heads) or (hkv % size(heads) and hkv != 1):
        heads = kv_heads = None
    elif hkv == 1:
        kv_heads = None
    return (mesh, auto, P(batch, None, heads, None),
            P(batch, None, kv_heads, None))


def _manual_axes(mesh) -> set:
    """Axis names currently in Manual (shard_map) mode."""
    try:
        from jax.sharding import AxisType

        return {name for name, t in zip(mesh.axis_names, mesh.axis_types)
                if t == AxisType.Manual}
    except Exception:  # noqa: BLE001 - concrete Mesh / older API
        return set()
