"""Sharding rules: how parameter and batch pytrees map onto the mesh.

Two mechanisms, usable together:

- :func:`apply_rules` — explicit per-parameter ``PartitionSpec`` rules keyed
  by path regex (the t5x/flax-partitioning idiom), for TP/expert layouts
  where placement is architectural.
- :func:`infer_fsdp_sharding` — automatic FSDP: shard each parameter's
  largest divisible axis over the ``fsdp`` mesh axis, replicate the rest.
  This is the role FSDP plays inside a reference replica group, expressed
  as shardings instead of a wrapper module.

``device_put``-ing params with these shardings + jitting the step function
is all that is needed — XLA inserts the all-gathers/reduce-scatters over
ICI.
"""

from __future__ import annotations

import re
from typing import Any, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Rules = Sequence[Tuple[str, PartitionSpec]]


def path_str(path: Any) -> str:
    """Flattened key path → "a/b/0/c" string for rule matching."""
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def apply_rules(
    tree: Any,
    mesh: Mesh,
    rules: Rules,
    default: Optional[PartitionSpec] = None,
) -> Any:
    """Map each leaf to a :class:`NamedSharding` by first-matching rule.

    ``rules`` entries are ``(path_regex, PartitionSpec)``; a spec axis that
    does not divide the corresponding dim raises (loudly, not silently
    replicating — a wrong TP rule should fail fast).
    """
    default = default if default is not None else PartitionSpec()

    def assign(path, leaf):
        p = path_str(path)
        for pat, spec in rules:
            if re.search(pat, p):
                _check_divisible(leaf, mesh, spec, p)
                return NamedSharding(mesh, spec)
        return NamedSharding(mesh, default)

    return jax.tree_util.tree_map_with_path(assign, tree)


def _check_divisible(leaf: Any, mesh: Mesh, spec: PartitionSpec,
                     path: str) -> None:
    shape = np.shape(leaf)
    if len(spec) > len(shape):
        raise ValueError(
            f"param '{path}' rank {len(shape)} < spec rank {len(spec)}")
    for dim, axis in enumerate(spec):
        if axis is None:
            continue
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        factor = int(np.prod([mesh.shape[a] for a in axes]))
        if dim >= len(shape) or shape[dim] % factor:
            raise ValueError(
                f"param '{path}' shape {shape} dim {dim} not divisible by "
                f"mesh axes {axes} (={factor})")


def infer_fsdp_sharding(
    tree: Any,
    mesh: Mesh,
    axis: str = "fsdp",
    min_size: int = 1024,
) -> Any:
    """Automatic FSDP layout: shard the largest divisible dim of each big
    parameter along ``axis``; small params stay replicated.

    ``min_size``: parameters with fewer elements are replicated (sharding
    tiny biases wastes collective latency for no memory win).
    """
    n = mesh.shape[axis]

    def assign(leaf):
        shape = np.shape(leaf)
        if int(np.prod(shape or (1,))) < min_size:
            return NamedSharding(mesh, PartitionSpec())
        # largest dim divisible by the axis size
        best = -1
        for d in np.argsort(shape)[::-1]:
            if shape[d] % n == 0:
                best = int(d)
                break
        if best < 0:
            return NamedSharding(mesh, PartitionSpec())
        spec = [None] * len(shape)
        spec[best] = axis
        return NamedSharding(mesh, PartitionSpec(*spec))

    return jax.tree_util.tree_map(assign, tree)


def combined_shardings(
    tree: Any,
    mesh: Mesh,
    rules: Rules = (),
    fsdp_axis: str = "fsdp",
    min_size: int = 1024,
    strict: bool = True,
) -> Any:
    """TP rules where they match, automatic FSDP on top and everywhere
    else — the standard 3D (dp × fsdp × tp) parameter layout. A leaf
    matched by a rule keeps the rule's axes and is additionally split over
    ``fsdp_axis`` along its largest still-unsharded divisible dim (so on an
    ``fsdp × tp`` mesh every chip holds ``1/(fsdp·tp)`` of a projection,
    not ``1/tp``); unmatched leaves get :func:`infer_fsdp_sharding`'s
    placement. Without an ``fsdp`` axis in the mesh, rules apply alone and
    the rest is replicated.

    ``strict=False`` (the degraded-mode re-derivation,
    :func:`degraded_shardings`): a rule whose axes no longer divide a
    dim FALLS BACK to the unmatched path (inferred FSDP, which itself
    replicates non-divisible leaves) instead of raising."""
    unmatched = object()  # sentinel (None would vanish from the pytree)
    has_fsdp = fsdp_axis in mesh.axis_names and mesh.shape[fsdp_axis] > 1

    def with_fsdp(leaf, spec):
        shape = np.shape(leaf)
        used = {a for e in spec if e is not None
                for a in ((e,) if isinstance(e, str) else e)}
        if (not has_fsdp or fsdp_axis in used
                or int(np.prod(shape or (1,))) < min_size):
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        for d in np.argsort(shape)[::-1]:
            if entries[d] is None and shape[d] % mesh.shape[fsdp_axis] == 0:
                entries[d] = fsdp_axis
                return PartitionSpec(*entries)
        return spec

    def mark(path, leaf):
        p = path_str(path)
        for pat, spec in rules:
            if re.search(pat, p):
                try:
                    _check_divisible(leaf, mesh, spec, p)
                except ValueError:
                    if strict:
                        raise
                    return unmatched  # rule no longer fits: fall back
                return NamedSharding(mesh, with_fsdp(leaf, spec))
        return unmatched

    ruled = jax.tree_util.tree_map_with_path(mark, tree)
    if has_fsdp:
        fsdp = infer_fsdp_sharding(tree, mesh, fsdp_axis, min_size)
    else:
        fsdp = jax.tree_util.tree_map(
            lambda _: NamedSharding(mesh, PartitionSpec()), tree)
    return jax.tree_util.tree_map(
        lambda r, f: f if r is unmatched else r, ruled, fsdp)


def degraded_shardings(
    tree: Any,
    submesh: Mesh,
    rules: Rules = (),
    fsdp_axis: str = "fsdp",
    min_size: int = 1024,
) -> Any:
    """Re-derive the parameter layout for a shrunken submesh
    (degraded-mode groups, docs/design/degraded_mode.md): exactly
    :func:`combined_shardings` in non-strict mode — a rule or FSDP
    axis that no longer divides a dim on the shrunken mesh FALLS BACK
    (rule -> inferred FSDP -> replicated) instead of raising, because
    partial chip loss must never be fatal when the surviving submesh
    can still hold the leaf replicated. The fallback costs memory,
    never correctness: ``device_put`` onto these shardings is the
    degrade path's re-``pjit`` (jit re-specializes on the new
    placement at the next step)."""
    return combined_shardings(tree, submesh, rules=rules,
                              fsdp_axis=fsdp_axis, min_size=min_size,
                              strict=False)


def batch_spec(mesh: Mesh, data_axes: Sequence[str] = ("dp", "fsdp"),
               seq_axis: Optional[str] = None) -> PartitionSpec:
    """PartitionSpec for a [batch, ...] input: batch dim sharded over every
    data-ish axis present in the mesh; optional sequence dim over
    ``seq_axis`` (sequence parallelism)."""
    present = [a for a in data_axes if a in mesh.axis_names
               and mesh.shape[a] > 1]
    batch_axis = tuple(present) if present else None
    if seq_axis and seq_axis in mesh.axis_names:
        return PartitionSpec(batch_axis, seq_axis)
    return PartitionSpec(batch_axis)


def shard_tree(tree: Any, shardings: Any) -> Any:
    """``device_put`` a pytree onto its shardings (initial placement or
    post-heal restore)."""
    return jax.device_put(tree, shardings)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def list_shardings(tree: Any) -> List[str]:
    """Debug helper: 'path: spec' lines for a sharded pytree."""
    out = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        s = getattr(leaf, "sharding", None)
        out.append(f"{path_str(path)}: {getattr(s, 'spec', s)}")
    return out
