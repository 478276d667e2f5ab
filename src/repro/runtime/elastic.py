"""Elastic re-mesh: resume a run on a different device count/topology.

Because checkpoints store logical (unsharded) arrays with a manifest
(:mod:`repro.checkpoint.checkpointer`) and shardings are derived from the
(config, mesh) pair by the layout engine, shrinking or growing the mesh
is just: build the new mesh -> re-derive shardings -> restore with
``device_put`` onto them.  The data pipeline is deterministic in
(step, row-range), so the global batch re-partitions cleanly too.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ModelConfig
from repro.dist import layout, sharding
from repro.optim import adafactor, adamw


def state_specs(target_state, cfg: ModelConfig, mesh: jax.sharding.Mesh,
                layout_name=None):
    """PartitionSpecs for a TrainState: params via the layout engine,
    optimizer state via the optimizer's own ``state_specs`` (Adafactor's
    factored stats need rank-adjusted specs — a 1T-param model cannot
    afford replicated row/col moments)."""
    p_specs = layout.param_specs(target_state.params, cfg, mesh,
                                 layout_name)
    opt = target_state.opt
    if isinstance(opt, adamw.AdamWState):
        opt_specs = adamw.state_specs(p_specs, target_state.params)
    elif isinstance(opt, adafactor.AdafactorState):
        opt_specs = adafactor.state_specs(p_specs, target_state.params)
    else:                                     # unknown: replicate
        opt_specs = jax.tree.map(lambda _: P(), opt)
    return type(target_state)(params=p_specs, opt=opt_specs, step=P())


def state_shardings(target_state, cfg: ModelConfig,
                    mesh: jax.sharding.Mesh, layout_name=None):
    """NamedShardings for a TrainState on ``mesh``.  With telemetry on,
    the layout resolved here is recorded as a ``dist.layout`` event:
    the strategy, the mesh's axis sizes and every strategy's scored
    bytes per device and collective bytes."""
    if layout_name is None:
        sizes = sharding.axis_sizes(mesh)
        layout_name = layout.choose_layout(cfg, sizes)
        if telemetry.enabled():
            scored = layout.score_layouts(cfg, sizes)
            telemetry.event("dist.layout", strategy=layout_name,
                            mesh=sizes, scores={
                                s: {k: v[k] for k in (
                                    "mem_bytes_per_device",
                                    "collective_bytes_per_device")}
                                for s, v in scored.items()})
    specs = state_specs(target_state, cfg, mesh, layout_name)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def remesh_restore(ckpt: Checkpointer, target_state, cfg: ModelConfig,
                   new_mesh: jax.sharding.Mesh,
                   step: Optional[int] = None):
    """Restore ``target_state`` (TrainState-shaped pytree of arrays or
    ShapeDtypeStructs) re-sharded onto ``new_mesh``."""
    shardings = state_shardings(target_state, cfg, new_mesh)
    return ckpt.restore(target_state, step=step, shardings=shardings)
