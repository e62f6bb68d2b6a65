"""Sharding rules: logical axes -> mesh axes, activation constraints.

Mesh axes (launch/mesh.py):
  * single-pod:  ("data", "model")            = (16, 16)
  * multi-pod:   ("pod", "data", "model")     = (2, 16, 16)

Logical rules (MaxText-style):
  batch       -> ("pod", "data")     activations' leading batch dim
  vocab       -> "model"             embedding/unembedding vocab dim
  heads       -> "model"             attention heads (TP)
  kv_heads    -> "model" if divisible else None (replicate small-GQA KV)
  mlp         -> "model"             d_ff / expert-ff dim (TP)
  experts     -> "model"             MoE expert dim (EP)
  fsdp        -> "data"              parameter FSDP shard dim (embed/d_model)
  seq         -> "model"             sequence parallelism (long-context)

The mesh is installed via ``use_mesh`` (a contextvar), so model code can call
``shard(x, *logical_axes)`` without threading mesh handles everywhere; with no
installed mesh the call is a no-op (CPU smoke tests see 1 device).
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Any, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_MESH: contextvars.ContextVar[Mesh | None] = contextvars.ContextVar(
    "repro_mesh", default=None)
_RULES: contextvars.ContextVar[dict | None] = contextvars.ContextVar(
    "repro_rules", default=None)


def default_rules(mesh: Mesh, *, kv_divisible: bool = True,
                  heads_divisible: bool = True,
                  seq_sharded: bool = False) -> dict[str, Any]:
    has_pod = "pod" in mesh.axis_names
    batch = ("pod", "data") if has_pod else ("data",)
    return {
        "batch": batch,
        "vocab": "model",
        "heads": "model" if heads_divisible else None,
        "kv_heads": "model" if (kv_divisible and heads_divisible) else None,
        "mlp": "model",
        "experts": "model",
        "fsdp": "data",
        "seq": "model" if seq_sharded else None,
        "none": None,
    }


@contextlib.contextmanager
def use_mesh(mesh: Mesh | None, rules: dict | None = None):
    tok1 = _MESH.set(mesh)
    tok2 = _RULES.set(rules if rules is not None else
                      (default_rules(mesh) if mesh is not None else None))
    try:
        yield
    finally:
        _MESH.reset(tok1)
        _RULES.reset(tok2)


def current_mesh() -> Mesh | None:
    return _MESH.get()


def inpod_axes(mesh: Mesh | None) -> tuple[tuple[str, ...], int]:
    """Non-'pod' mesh axes and their total device count.

    The consensus engine's in-pod shard grid: ``ConsensusTrainer`` and the
    dry-run roofline both derive ``n_shards`` from this ONE helper so the
    accounting can never disagree with the engine. Returns ``((), 1)``
    when there is no mesh or no pod axis (nothing to shard over).
    """
    if mesh is None or "pod" not in mesh.axis_names:
        return (), 1
    axes = tuple(a for a in mesh.axis_names if a != "pod")
    size = 1
    for a in axes:
        size *= int(mesh.shape[a])
    return axes, size


def shard_map(fn, mesh, *, in_specs, out_specs, manual_axes=None):
    """``jax.shard_map`` manual over ``manual_axes`` (all axes when None).

    Replication checking is off: regions here replicate over unmentioned
    in-pod axes on purpose.
    """
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs,
              check_vma=False)
    if manual_axes is not None:
        kw["axis_names"] = frozenset(manual_axes)
    return jax.shard_map(fn, **kw)


def logical_to_spec(axes: Sequence[str | None]) -> P:
    """Map logical axis names to a PartitionSpec under the current rules."""
    rules = _RULES.get()
    if rules is None:
        return P(*([None] * len(axes)))
    out = []
    for ax in axes:
        if ax is None:
            out.append(None)
        else:
            out.append(rules.get(ax))
    return P(*out)


def shard(x: jax.Array, *axes: str | None) -> jax.Array:
    """Constrain activation sharding by logical axis names (no-op w/o mesh)."""
    mesh = _MESH.get()
    if mesh is None:
        return x
    spec = logical_to_spec(axes)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(*axes: str | None) -> NamedSharding | None:
    mesh = _MESH.get()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_to_spec(axes))


def fit_spec(mesh: Mesh, shape: tuple[int, ...], spec: P) -> P:
    """Drop sharding on dims the axis size does not divide (e.g. batch=1)."""
    out = []
    entries = list(spec) + [None] * (len(shape) - len(spec))
    for dim, entry in zip(shape, entries):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        size = 1
        for a in axes:
            size *= mesh.shape[a]
        out.append(entry if dim % size == 0 else None)
    return P(*out)


def spec_tree_to_shardings(mesh: Mesh, tree: Any) -> Any:
    """Convert a pytree of PartitionSpec into NamedShardings on `mesh`."""
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        tree, is_leaf=lambda s: isinstance(s, P))
