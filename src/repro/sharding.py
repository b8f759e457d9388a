"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Every parameter / activation in the framework is annotated with *logical*
axis names (e.g. ``("embed", "ffn")``).  A rule table maps each logical axis
to one (or a tuple of) mesh axes.  ``spec_for`` resolves the logical names to
a concrete :class:`~jax.sharding.PartitionSpec`, silently dropping any mesh
axis whose size does not divide the corresponding dimension (e.g. 1 kv-head
on a 16-way ``model`` axis degrades to replication instead of erroring).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Mesh helpers: every mesh axis is explicitly Auto, so a change of JAX's
# default axis type cannot silently change sharding behaviour.
# ---------------------------------------------------------------------------


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis typed Auto."""
    kwargs: dict[str, Any] = {} if devices is None else {"devices": devices}
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kwargs)


def set_mesh(mesh: Mesh):
    """Context manager installing ``mesh`` as the ambient mesh."""
    return jax.set_mesh(mesh)


def ambient_mesh():
    """The ambient (abstract) mesh, or None when no mesh is installed."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or not mesh.axis_names:
        return None
    return mesh


def shard_map(f, mesh: Mesh, in_specs, out_specs, axis_names=None,
              check_vma: bool | None = None):
    """``jax.shard_map``; ``axis_names`` (manual axes) and ``check_vma``
    are passed on only when given."""
    kw: dict[str, Any] = {}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    if check_vma is not None:
        kw["check_vma"] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def _is_auto(axis_type) -> bool:
    return axis_type == jax.sharding.AxisType.Auto


def trials_mesh(max_devices: int | None = None) -> Mesh | None:
    """1-D ``("trials",)`` mesh over the local devices of the default
    backend — the scenario engine's data-parallel axis (trials are
    embarrassingly parallel).  Returns None on single-device hosts
    (plain jit is strictly cheaper there)."""
    from repro.obs import metrics as obmetrics

    devs = jax.local_devices()
    if max_devices is not None:
        devs = devs[:max(1, max_devices)]
    obmetrics.gauge("sharding.local_devices").set(len(devs))
    if len(devs) <= 1:
        return None
    return make_mesh((len(devs),), ("trials",), devices=devs)


def mesh_num_devices(mesh: Mesh) -> int:
    """Device count of a trials mesh — the chunk-rounding granularity
    the plan layer needs without importing jax (ExecutionPlan records
    it as ``n_devices``)."""
    return int(np.prod(list(mesh.shape.values())))


def trial_partition_spec(ndim: int, axis: int | None) -> P:
    """Full-rank PartitionSpec sharding ``axis`` over the ``"trials"``
    mesh axis (``None`` = fully replicated).  Shared by the scenario
    engine's shard_map in/out specs: every per-trial operand — problem
    slices, schedule arrays, and the on-device control plane's protocol
    state (active mask, kappa, stream keys) — shards on its trial axis,
    so the scan body needs no collectives."""
    spec: list[Any] = [None] * ndim
    if axis is not None:
        spec[axis] = "trials"
    return P(*spec)

# ---------------------------------------------------------------------------
# Default rule tables.
#
# `data`-like mesh axes carry the batch (DP) *and* the FSDP shard of the
# parameters / optimizer state (ZeRO-style); `model` carries TP (heads, ffn,
# vocab) and EP (experts).  On the multi-pod mesh the `pod` axis is an extra
# pure-DP axis: parameters are replicated across pods, gradients are reduced
# over (pod, data).
# ---------------------------------------------------------------------------

#: logical axis -> mesh axis (or tuple of mesh axes) for PARAMETERS.
PARAM_RULES: dict[str, Any] = {
    "embed": "data",          # FSDP shard of the d_model dim
    "embed_no_fsdp": None,    # d_model dim on params too small to FSDP-shard
    "vocab": "model",
    "heads": "model",         # merged H*head_dim (q / o projections)
    "kv": "model",            # merged K*head_dim (k / v projections)
    "ffn": "model",
    "experts": "model",       # expert-parallel axis
    "expert_ffn": None,       # per-expert ffn dim (model axis is taken by E)
    "conv": None,
    "ssm_inner": "model",     # mamba d_inner
    "ssm_state": None,
    "ssm_heads": "model",
    "layers": None,           # stacked-scan leading axis is never sharded
    "norm": None,
}

#: logical axis -> mesh axis for ACTIVATIONS / inputs.
ACT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),  # pod silently dropped on single-pod meshes
    "seq": None,
    "decode_seq": "data",      # KV-cache seq dim for long-context decode (SP)
    "embed": None,
    "heads": "model",
    "heads_forced": "model",   # padded sharding: divisibility NOT required
    "kv": "model",
    "ffn": "model",
    "experts": "model",
    "ssm_inner": "model",
    "vocab": "model",
}

#: logical names that shard even when the dim is not divisible by the mesh
#: axis (GSPMD pads the trailing shards).  Used for attention heads on
#: architectures whose head count doesn't divide the TP width (e.g.
#: starcoder2's 36 heads on model=16) — padded sharding wastes
#: ceil(H/tp)*tp/H compute on the padded head slots but avoids re-gathering
#: multi-GB activations every layer (EXPERIMENTS.md §Perf iteration 1).
FORCE_SHARD = {"heads_forced"}


def _mesh_axis_sizes(mesh: Mesh) -> Mapping[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(
    logical_axes: Sequence[str | None],
    mesh: Mesh,
    shape: Sequence[int] | None = None,
    rules: Mapping[str, Any] | None = None,
) -> P:
    """Resolve logical axis names to a PartitionSpec for ``mesh``.

    If ``shape`` is given, any mesh axis whose size does not evenly divide the
    corresponding dimension is dropped (replication fallback).
    """
    rules = PARAM_RULES if rules is None else rules
    sizes = _mesh_axis_sizes(mesh)
    out: list[Any] = []
    for i, name in enumerate(logical_axes):
        if name is None:
            out.append(None)
            continue
        mesh_axes = rules.get(name, None)
        if mesh_axes is None:
            out.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        kept = []
        divisor = 1
        for ax in mesh_axes:
            if ax not in sizes:
                continue  # e.g. "pod" on a single-pod mesh
            n = sizes[ax]
            if (
                name not in FORCE_SHARD
                and shape is not None
                and (shape[i] % (divisor * n)) != 0
            ):
                continue  # divisibility fallback -> replicate on this axis
            kept.append(ax)
            divisor *= n
        if not kept:
            out.append(None)
        elif len(kept) == 1:
            out.append(kept[0])
        else:
            out.append(tuple(kept))
    # PartitionSpec forbids trailing Nones mattering; fine to keep them.
    return P(*out)


@dataclasses.dataclass(frozen=True)
class Annotated:
    """A leaf-shape annotated with logical axes (used in param trees)."""

    shape: tuple[int, ...]
    logical: tuple[str | None, ...]
    dtype: Any
    init: str = "normal"  # normal | ones | zeros | ssm_a | ssm_dt

    def spec(self, mesh: Mesh, rules: Mapping[str, Any] | None = None) -> P:
        return spec_for(self.logical, mesh, self.shape, rules)


def tree_specs(annotated_tree, mesh: Mesh, rules=None):
    """Map a pytree of :class:`Annotated` to a pytree of PartitionSpec."""
    return jax.tree.map(
        lambda a: a.spec(mesh, rules),
        annotated_tree,
        is_leaf=lambda x: isinstance(x, Annotated),
    )


def tree_shardings(annotated_tree, mesh: Mesh, rules=None):
    return jax.tree.map(
        lambda a: NamedSharding(mesh, a.spec(mesh, rules)),
        annotated_tree,
        is_leaf=lambda x: isinstance(x, Annotated),
    )


def tree_structs(annotated_tree, mesh: Mesh | None = None, rules=None):
    """Annotated tree -> ShapeDtypeStruct tree (with shardings if mesh given)."""

    def mk(a: Annotated):
        if mesh is None:
            return jax.ShapeDtypeStruct(a.shape, a.dtype)
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, a.spec(mesh, rules))
        )

    return jax.tree.map(mk, annotated_tree, is_leaf=lambda x: isinstance(x, Annotated))


def constrain(x, mesh: Mesh, logical: Sequence[str | None]):
    """Apply a with_sharding_constraint from ACT_RULES (divisibility-safe)."""
    spec = spec_for(logical, mesh, x.shape, ACT_RULES)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def mesh_axis_size_here(name: str) -> int:
    """Size of a mesh axis in the ambient (abstract) mesh; 1 if absent or
    the axis is Manual (consumed by an enclosing shard_map)."""
    mesh = ambient_mesh()
    if mesh is None or not mesh.axis_names:
        return 1
    sizes = dict(
        zip(
            mesh.axis_names,
            mesh.shape.values() if isinstance(mesh.shape, dict) else mesh.shape,
        )
    )
    for n, t in zip(mesh.axis_names, mesh.axis_types):
        if n == name and not _is_auto(t):
            return 1
    return int(sizes.get(name, 1))


def constrain_here(x, logical: Sequence[str | None]):
    """Like :func:`constrain` but reads the ambient mesh (jax.set_mesh).

    No-op outside a mesh context — model code can call it unconditionally.
    """
    mesh = ambient_mesh()
    if mesh is None or not mesh.axis_names:
        return x
    sizes = dict(zip(mesh.axis_names, mesh.shape.values() if isinstance(mesh.shape, dict) else mesh.shape))
    # inside a shard_map body some axes are Manual — constraints may only
    # name Auto axes (the worker axes are already consumed by shard_map)
    auto = {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if _is_auto(t)}
    sizes = {n: s for n, s in sizes.items() if n in auto}
    if not sizes:
        return x

    class _M:  # duck-typed mesh for spec_for
        axis_names = tuple(sizes)
        devices = np.empty(tuple(sizes.values()))

    spec = spec_for(logical, _M, x.shape, ACT_RULES)
    return jax.lax.with_sharding_constraint(x, spec)


def param_bytes(annotated_tree) -> int:
    leaves = jax.tree.leaves(
        annotated_tree, is_leaf=lambda x: isinstance(x, Annotated)
    )
    return sum(int(np.prod(a.shape)) * np.dtype(a.dtype).itemsize for a in leaves)


def param_count(annotated_tree) -> int:
    leaves = jax.tree.leaves(
        annotated_tree, is_leaf=lambda x: isinstance(x, Annotated)
    )
    return sum(int(np.prod(a.shape)) for a in leaves)
