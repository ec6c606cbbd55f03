"""Sharding helpers shared by models, serving, training and launch.

We use a MaxText-style *logical axis* scheme: model code annotates
activations/params with logical axis names ("batch", "seq", "model_heads",
"model_ff", "experts", "vocab", ...) and a rules table maps logical names to
physical mesh axes.  With no mesh active every annotation is a no-op, so the
same model code runs single-device (CPU smoke tests) and on the production
mesh (dry-run / multi-pod) unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# Logical -> physical rules
# ---------------------------------------------------------------------------

# Default production rules.  "batch" maps to both the pod axis and the data
# axis (pod-major); tensor-parallel dims map to "model".
DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),            # sequence unsharded by default (overridden for 500k)
    "ctx": ("data",),     # context parallelism for long-context decode caches
    "embed": (),
    "heads": ("model",),
    "kv_heads": ("model",),
    "ff": ("model",),
    "experts": ("model",),
    "expert_ff": (),      # FSDP axis for expert weights (launch overrides)
    "vocab": ("model",),
    "kv_lora": (),
    "state": (),
}

_tls = threading.local()


def _ctx():
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    return _tls.stack


@contextlib.contextmanager
def axis_rules(rules: dict[str, tuple[str, ...]], mesh: Optional[Mesh] = None):
    """Activate a logical->physical mapping (and optionally a mesh)."""
    _ctx().append((dict(rules), mesh))
    try:
        yield
    finally:
        _ctx().pop()


def current_rules() -> Optional[dict[str, tuple[str, ...]]]:
    stack = _ctx()
    return stack[-1][0] if stack else None


def current_mesh() -> Optional[Mesh]:
    stack = _ctx()
    return stack[-1][1] if stack else None


def logical_to_pspec(axes: tuple[Optional[str], ...],
                     rules: Optional[dict] = None,
                     shape: Optional[tuple[int, ...]] = None,
                     mesh: Optional[Mesh] = None) -> P:
    """Map a tuple of logical axis names (or None) to a PartitionSpec.

    If `shape` and `mesh` are given, any mapping whose mesh-axis product does
    not divide the corresponding dim is dropped (e.g. 4 GQA KV heads cannot
    shard over a 16-way model axis -> replicate instead)."""
    rules = rules if rules is not None else (current_rules() or {})
    parts = []
    used: set[str] = set()
    for i, name in enumerate(axes):
        if name is None:
            parts.append(None)
            continue
        phys = tuple(a for a in rules.get(name, ()) if a not in used)
        if shape is not None and mesh is not None and phys:
            n = 1
            for a in phys:
                n *= mesh.shape[a]
            if n == 0 or shape[i] % n != 0:
                parts.append(None)
                continue
        used.update(phys)
        if len(phys) == 0:
            parts.append(None)
        elif len(phys) == 1:
            parts.append(phys[0])
        else:
            parts.append(phys)
    # trim trailing Nones (canonical form)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def shard(x, *axes: Optional[str]):
    """Annotate an activation with logical axes; no-op without rules/mesh."""
    rules = current_rules()
    mesh = current_mesh()
    if rules is None or mesh is None:
        return x
    spec = logical_to_pspec(axes, rules, shape=x.shape, mesh=mesh)
    return jax.lax.with_sharding_constraint(x, spec)


def named_sharding(mesh: Mesh, *axes: Optional[str],
                   rules: Optional[dict] = None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_pspec(axes, rules or DEFAULT_RULES))


def pspec_tree_from_logical(logical_tree, rules: Optional[dict] = None,
                            shape_tree=None, mesh: Optional[Mesh] = None):
    """Map a pytree whose leaves are tuples of logical axis names to pspecs.

    With `shape_tree` (matching pytree of ShapeDtypeStructs/arrays) and
    `mesh`, indivisible mappings are dropped per-leaf."""
    rules = rules or DEFAULT_RULES
    is_leaf = lambda x: isinstance(x, tuple)  # noqa: E731
    if shape_tree is None:
        return jax.tree.map(lambda axes: logical_to_pspec(axes, rules),
                            logical_tree, is_leaf=is_leaf)
    return jax.tree.map(
        lambda axes, arr: logical_to_pspec(axes, rules, tuple(arr.shape), mesh),
        logical_tree, shape_tree, is_leaf=is_leaf)


def sharding_tree(logical_tree, shape_tree, mesh: Mesh,
                  rules: Optional[dict] = None):
    """NamedSharding pytree for jit in_shardings / device_put."""
    specs = pspec_tree_from_logical(logical_tree, rules or DEFAULT_RULES,
                                    shape_tree, mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))
