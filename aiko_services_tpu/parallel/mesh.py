# Device mesh management: the TPU pod is the device pool.
#
# The reference has no parallelism substrate at all (SURVEY.md §2: its only
# distribution primitive is MQTT pub/sub; reference aiko_services/message/
# mqtt.py).  This module is the TPU-native replacement's foundation: a
# jax.sharding.Mesh over the slice/pod with named axes for data, model
# (tensor), sequence and expert parallelism; collectives ride ICI inside a
# slice and DCN across slices (scaling-book recipe: pick a mesh, annotate
# shardings, let XLA insert collectives).

from __future__ import annotations

import math
from dataclasses import dataclass, field

__all__ = ["AXIS_DATA", "AXIS_MODEL", "AXIS_SEQUENCE", "AXIS_EXPERT",
           "AXIS_STAGE", "MeshSpec", "create_mesh", "single_device_mesh",
           "best_mesh_shape"]

# Canonical mesh axis names.  Shardings and models refer to these, so a
# pipeline definition only has to pick sizes.
AXIS_DATA = "data"          # batch / replica axis (DP)
AXIS_MODEL = "model"        # tensor-parallel axis (TP over ICI)
AXIS_SEQUENCE = "seq"       # sequence/context-parallel axis (ring attention)
AXIS_EXPERT = "expert"      # expert-parallel axis (MoE)
AXIS_STAGE = "stage"        # pipeline-parallel stage axis


@dataclass
class MeshSpec:
    """Declarative mesh request: axis name → size.  Size -1 on at most one
    axis means "all remaining devices"."""
    axes: dict = field(default_factory=dict)

    def resolve(self, device_count: int) -> dict:
        axes = {k: v for k, v in self.axes.items() if v != 1 or len(
            self.axes) == 1}
        wildcard = [k for k, v in axes.items() if v == -1]
        if len(wildcard) > 1:
            raise ValueError("at most one axis may be -1")
        fixed = math.prod(v for v in axes.values() if v != -1)
        if wildcard:
            if device_count % fixed:
                raise ValueError(
                    f"cannot fill axis {wildcard[0]}: {device_count} devices "
                    f"not divisible by {fixed}")
            axes[wildcard[0]] = device_count // fixed
        elif fixed != device_count:
            raise ValueError(
                f"mesh {axes} wants {fixed} devices, have {device_count}")
        return axes


def best_mesh_shape(device_count: int, model_parallel: int = 1) -> dict:
    """Default 2D layout: model axis innermost (contiguous devices share the
    fastest ICI links for TP collectives), data axis over the rest."""
    if device_count % model_parallel:
        raise ValueError(f"{device_count} devices not divisible by "
                         f"model_parallel={model_parallel}")
    return {AXIS_DATA: device_count // model_parallel,
            AXIS_MODEL: model_parallel}


def create_mesh(axes: dict | MeshSpec | None = None, devices=None):
    """Build a jax.sharding.Mesh.

    axes: {"data": 4, "model": 2} (ordering = mesh dims, model-like axes
    should be last/innermost for ICI locality).  None → 1D data mesh over
    all devices.
    """
    import jax

    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {AXIS_DATA: len(devices)}
    if isinstance(axes, MeshSpec):
        axes = axes.resolve(len(devices))
    elif isinstance(axes, dict):
        axes = MeshSpec(dict(axes)).resolve(len(devices))
    return _make_mesh(tuple(axes.values()), tuple(axes.keys()), devices)


def _make_mesh(shape: tuple, names: tuple, devices):
    """Auto axis types: shardings propagate from annotations
    (with_sharding_constraint) rather than the explicit-sharding type
    system jax.make_mesh defaults to — the classic pjit programming
    model."""
    import jax

    return jax.make_mesh(
        shape, names, devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(names))


def single_device_mesh(axis: str = AXIS_DATA):
    """1×1 mesh: lets single-chip code paths share the sharded code path."""
    import jax

    return _make_mesh((1,), (axis,), jax.devices()[:1])
