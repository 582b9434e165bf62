# Collective communication: the tensor-path replacement for the reference's
# MQTT data plane.
#
# The reference moves tensors as zlib+np.save blobs through a broker
# (reference: aiko_services/elements/audio_io.py:392-439); here co-located
# elements exchange jax.Arrays and cross-chip movement is XLA collectives
# over ICI/DCN.  These wrappers exist so runtime code (schedulers, pipeline
# data plane) has one seam for device communication — inside shard_map they
# are the jax.lax collectives; outside they are sharding-aware transfers.

from __future__ import annotations

__all__ = ["psum", "pmean", "pmax", "all_gather", "ppermute_ring",
           "reduce_scatter", "axis_index", "axis_size", "device_transfer",
           "ring_neighbours"]


def psum(x, axis_name):
    import jax
    return jax.lax.psum(x, axis_name)


def pmean(x, axis_name):
    import jax
    return jax.lax.pmean(x, axis_name)


def pmax(x, axis_name):
    import jax
    return jax.lax.pmax(x, axis_name)


def all_gather(x, axis_name, axis: int = 0, tiled: bool = True):
    import jax
    return jax.lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, axis: int = 0):
    import jax
    return jax.lax.psum_scatter(x, axis_name, scatter_dimension=axis,
                                tiled=True)


def axis_index(axis_name):
    import jax
    return jax.lax.axis_index(axis_name)


def axis_size(axis_name):
    import jax
    return jax.lax.psum(1, axis_name)


def ring_neighbours(n: int, reverse: bool = False):
    """Permutation table sending shard j → j+1 (mod n); the ICI ring."""
    if reverse:
        return [(j, (j - 1) % n) for j in range(n)]
    return [(j, (j + 1) % n) for j in range(n)]


def ppermute_ring(x, axis_name, n: int, reverse: bool = False):
    """Rotate x one hop around the ring of `axis_name` (ring attention,
    pipeline-parallel stage handoff)."""
    import jax
    return jax.lax.ppermute(x, axis_name,
                            perm=ring_neighbours(n, reverse))


def device_transfer(x, sharding):
    """Host-side: move/reshard an array (async under the hood — jax
    dispatches eagerly and the transfer overlaps host code)."""
    import jax
    return jax.device_put(x, sharding)


# -- mesh-aware helpers -------------------------------------------------------
# The two-plane design (SURVEY.md §5.8) needs host-side answers to "what
# does this collective cost and which fabric does it ride": axes whose
# devices share a host ride ICI; axes spanning hosts ride DCN.  Shardings
# should be laid out so the high-frequency axes (tensor/expert) are
# ICI-local and only data/pipeline axes cross DCN.

def axis_fabric(mesh, axis_name: str) -> str:
    """"ici" if every device along `axis_name` (for each fixed point of
    the other axes) lives on one host/process, else "dcn"."""
    import numpy as np

    axes = list(mesh.shape.keys())
    index = axes.index(axis_name)
    devices = np.moveaxis(mesh.devices, index, -1)
    for row in devices.reshape(-1, devices.shape[-1]):
        hosts = {getattr(d, "process_index", 0) for d in row}
        if len(hosts) > 1:
            return "dcn"
    return "ici"


def mesh_fabric_report(mesh) -> dict:
    """axis name → "ici"|"dcn" for every mesh axis (EC-shareable: the
    lifecycle manager and dashboard surface it as device-pool health)."""
    return {axis: axis_fabric(mesh, axis) for axis in mesh.shape.keys()}


def reshard(x, mesh, partition_spec):
    """Reshard an array onto `mesh` with a PartitionSpec — the host-side
    boundary transfer for cross-runtime tensor handoff (replaces the
    reference's zlib+np.save MQTT hop for co-scheduled runtimes)."""
    import jax
    from jax.sharding import NamedSharding

    return jax.device_put(x, NamedSharding(mesh, partition_spec))


def collective_bytes(x, axis_name, mesh, op: str = "all_gather") -> int:
    """Wire-byte estimate for a collective over `axis_name` — ring
    algorithms move ~(n-1)/n of the payload per hop; all_gather/
    reduce_scatter move the full gathered size, psum ~2x scatter.  Used
    by schedulers to choose batch shapes that keep collectives on ICI."""
    import numpy as np

    n = mesh.shape[axis_name]
    item_bytes = int(np.prod(x.shape)) * x.dtype.itemsize
    if op in ("all_gather",):
        return item_bytes * (n - 1)
    if op in ("reduce_scatter",):
        return item_bytes * (n - 1) // n
    if op in ("psum", "all_reduce"):
        return 2 * item_bytes * (n - 1) // n
    if op in ("ppermute", "ring"):
        return item_bytes
    raise ValueError(f"unknown collective {op!r}")
