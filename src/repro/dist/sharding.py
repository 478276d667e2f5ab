"""Mesh lifecycle + activation sharding constraints (the *mechanism* half
of ``repro.dist``).

Model code never imports jax.sharding directly: it calls
``act(x, ("batch", "seq", None))`` with *logical* axis names and this
module resolves them against whatever mesh is active — or does nothing
at all when no mesh is installed, so the exact same forward runs on a
single-host CPU test and a 512-chip multi-pod dry-run.

Logical axes:

* ``"batch"``  — the data-parallel direction; resolves to every
  batch-like mesh axis present (``('pod', 'data')`` on multi-pod
  meshes, ``'data'`` on single-pod ones).
* ``"seq"``    — sequence parallelism; resolves to ``'model'`` when
  enabled (``REPRO_SEQ_SHARD != '0'``), so the stored remat carry is
  1/|model| per device, else to ``None``.
* ``"expert"`` — expert parallelism; resolves to ``'model'``.
* ``"model"`` / ``"data"`` / ``"pod"`` — pass through to the mesh axis
  of the same name.
* ``None``     — dim left unconstrained-replicated.

Every resolution is divisibility-checked against the actual dim size:
a dim that does not divide its mesh axes falls back to replicated
instead of failing, mirroring the layout engine's relaxation rule.

:func:`make_mesh` builds every mesh of the repo with ``Auto`` axes, the
GSPMD partitioning the layout engine's shardings are written for.
"""

from __future__ import annotations

import contextlib
import math
import os
from typing import Callable, Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import telemetry

# ---------------------------------------------------------------------------
# Mesh lifecycle
# ---------------------------------------------------------------------------

_MESH_STACK: list = []


def current_mesh():
    """The innermost active mesh, or ``None`` outside any ``use_mesh``."""
    return _MESH_STACK[-1] if _MESH_STACK else None


@contextlib.contextmanager
def use_mesh(mesh):
    """Install ``mesh`` as the ambient mesh for the dynamic extent.

    Nestable and exception-safe: the previous mesh (or no-mesh state) is
    restored on exit.  ``mesh`` may be any object exposing
    ``axis_names`` + ``devices`` (a real ``jax.sharding.Mesh``, or a
    duck-typed stand-in in spec-level tests).
    """
    _MESH_STACK.append(mesh)
    try:
        yield mesh
    finally:
        _MESH_STACK.pop()


def axis_sizes(mesh) -> Dict[str, int]:
    """``{axis_name: size}`` for a (possibly duck-typed) mesh."""
    if mesh is None:
        return {}
    return dict(zip(tuple(mesh.axis_names), tuple(mesh.devices.shape)))


def mesh_devices(mesh) -> int:
    return int(mesh.devices.size) if mesh is not None else 1


# ---------------------------------------------------------------------------
# Logical-axis resolution
# ---------------------------------------------------------------------------

#: batch-like mesh axes, outermost first — "batch" binds to all present
DATA_AXES: Tuple[str, ...] = ("pod", "data")


def seq_shard_enabled() -> bool:
    return os.environ.get("REPRO_SEQ_SHARD", "1") != "0"


def _divides(dim: int, sizes: Dict[str, int], axes) -> bool:
    total = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        total *= sizes.get(a, 1)
    return total > 0 and dim % total == 0


def data_axes_for(dim: int, sizes: Dict[str, int]):
    """Batch-like mesh axes that divide ``dim``: the widest suffix of
    ``DATA_AXES`` whose product divides, else None (replicate).  Shared
    by the 'batch' logical axis here and the layout engine's batch/cache
    row sharding."""
    present = tuple(a for a in DATA_AXES if a in sizes)
    for start in range(len(present)):
        cand = present[start:]
        if _divides(dim, sizes, cand):
            return cand if len(cand) > 1 else cand[0]
    return None


def resolve_axis(logical: Optional[str], dim: int,
                 sizes: Dict[str, int]):
    """One logical axis -> mesh axis (or axes tuple), divisibility-checked.

    Returns ``None`` when the logical axis has no mesh backing or the
    dim does not divide it (relax-to-replicated).
    """
    if logical is None:
        return None
    if logical == "batch":
        return data_axes_for(dim, sizes)
    if logical == "seq":
        if not seq_shard_enabled():
            return None
        logical = "model"
    if logical == "expert":
        logical = "model"
    if logical in sizes and _divides(dim, sizes, logical):
        return logical
    return None


def logical_spec(shape: Sequence[int], axes: Sequence[Optional[str]],
                 sizes: Dict[str, int]) -> P:
    """Full-rank PartitionSpec for ``shape`` from logical axis names,
    dropping any axis claimed twice (a mesh axis can shard one dim)."""
    assert len(shape) == len(axes), (tuple(shape), tuple(axes))
    used: set = set()
    out = []
    for dim, name in zip(shape, axes):
        r = resolve_axis(name, int(dim), sizes)
        flat = r if isinstance(r, tuple) else (r,) if r else ()
        if any(a in used for a in flat):
            r = None
            flat = ()
        used.update(flat)
        out.append(r)
    return P(*out)


def act(x: jax.Array, *axes) -> jax.Array:
    """Constrain activation ``x`` to the logical ``axes`` layout.

    Accepts either ``act(x, ("batch", None, "model"))`` or
    ``act(x, "batch", None, "model")``.  A no-op when no mesh is active,
    when the active mesh is trivial (single device), or when the mesh is
    a duck-typed spec-level stand-in — so model code is unconditionally
    safe to run un-meshed.
    """
    if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
        axes = tuple(axes[0])
    mesh = current_mesh()
    if mesh is None or not isinstance(mesh, Mesh) or mesh_devices(mesh) <= 1:
        return x
    if len(axes) != x.ndim:          # rank drift (e.g. squeezed decode)
        return x
    spec = logical_spec(x.shape, axes, axis_sizes(mesh))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _spec_axes(entry) -> Tuple[str, ...]:
    return entry if isinstance(entry, tuple) else (entry,) if entry \
        else ()


def _per_device_event(name: str, args, specs, sizes) -> None:
    """The ``dist.per_device`` telemetry event of one call: each
    argument's global shape, spec, bytes per device and the dims left
    whole on every device."""
    rows = []
    for x, spec in zip(args, specs):
        local = [int(d) // math.prod(sizes[a] for a in _spec_axes(e))
                 for d, e in zip(x.shape, spec)]
        rows.append({
            "shape": list(x.shape), "spec": [list(_spec_axes(e))
                                             for e in spec],
            "bytes_per_device": math.prod(local) * x.dtype.itemsize,
            "whole_dims": [i for i, e in enumerate(spec) if not e]})
    telemetry.event("dist.per_device", fn=name, mesh=dict(sizes),
                    args=rows)


def per_device(fn: Callable, args: Sequence, axes: Sequence, out_axes, *,
               name: str):
    """``fn(*args)`` run as one program per device of the active mesh.

    GSPMD cannot partition a Mosaic (Pallas TPU) kernel, so under a
    multi-device mesh the call goes through ``jax.shard_map``.  Each
    ``axes`` entry gives an argument's logical axes as for :func:`act`
    (``None`` arguments pass through untouched); ``out_axes`` gives the
    result's, or a tuple of them for a tuple of results.  A dim named by
    no logical axis, or one its mesh axes do not divide, is whole on
    every device, so the logical axes may only split dims that ``fn``
    treats independently.  Outside a mesh, on one
    device, or already inside a ``shard_map``, ``fn`` runs as is.
    With telemetry on, a call through ``shard_map`` records a
    ``dist.per_device`` event named ``name`` when it is traced.
    """
    mesh = current_mesh()
    if (not isinstance(mesh, Mesh) or mesh_devices(mesh) <= 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return fn(*args)
    sizes = axis_sizes(mesh)
    live = [i for i, x in enumerate(args) if x is not None]
    in_specs = tuple(logical_spec(args[i].shape, axes[i], sizes)
                     for i in live)
    if telemetry.enabled():
        _per_device_event(name, [args[i] for i in live], in_specs, sizes)

    def local(*shards):
        full = list(args)
        for i, x in zip(live, shards):
            full[i] = x
        return fn(*full)

    out = jax.eval_shape(fn, *args)
    return jax.shard_map(
        local, mesh=mesh, in_specs=in_specs,
        out_specs=(tuple(logical_spec(o.shape, a, sizes)
                         for o, a in zip(out, out_axes))
                   if isinstance(out, tuple)
                   else logical_spec(out.shape, out_axes, sizes)),
        check_vma=False)(*(args[i] for i in live))


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              *, devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)
