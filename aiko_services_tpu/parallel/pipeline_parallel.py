# Pipeline parallelism: stages on distinct device groups, frames in
# flight overlapping.
#
# The reference's "pipeline parallelism" is a dataflow graph across OS
# processes with strictly sequential per-frame execution
# (reference: aiko_services/pipeline.py:650-712); SURVEY.md §2's
# obligations table requires TRUE PP here: each stage compiled onto its
# own device group, inter-stage handoffs as device-to-device transfers,
# and frame k+1 entering stage 0 while frame k occupies stage 1 — jax's
# async dispatch provides the overlap, device_put the ICI hop.
#
# Two granularities:
#   * StagedExecutor — inference PP for element pipelines: each stage is a
#     jitted fn pinned to a device group; submit() returns immediately
#     (device futures), so consecutive frames overlap across stages.
#   * gpipe_spmd — training-style PP inside one jit: stage weights sharded
#     over the "stage" mesh axis, microbatches rotated with ppermute
#     (GPipe schedule as a shard_map collective program).

from __future__ import annotations

from .mesh import AXIS_STAGE

__all__ = ["StagedExecutor", "stage_device_groups", "gpipe_spmd"]


def stage_device_groups(devices, num_stages: int):
    """Split a device list into contiguous per-stage groups (contiguous =
    neighbouring ICI links carry the inter-stage traffic)."""
    devices = list(devices)
    if len(devices) % num_stages:
        raise ValueError(f"{len(devices)} devices not divisible into "
                         f"{num_stages} stages")
    per_stage = len(devices) // num_stages
    return [devices[i * per_stage:(i + 1) * per_stage]
            for i in range(num_stages)]


class StagedExecutor:
    """Inference pipeline parallelism over device groups.

    stages: list of (fn, params) — fn(params, x) -> y, jitted per stage
    and pinned to its group's first device (single-device groups) or
    sharded submesh.  submit(x) dispatches asynchronously: jax enqueues
    the whole chain without blocking the host, so multiple frames occupy
    different stages concurrently; result(y) blocks for the value."""

    def __init__(self, stages, devices=None, donate: bool = False):
        import jax

        devices = list(devices if devices is not None else jax.devices())
        self.groups = stage_device_groups(devices, len(stages))
        self._fns = []
        self._params = []
        for (fn, params), group in zip(stages, self.groups):
            device = group[0]
            # placement follows the arguments: params live on the stage's
            # device and submit() device_puts x there, so jit compiles and
            # runs each stage on its group without the deprecated
            # jit(device=...) pin
            compiled = jax.jit(fn, donate_argnums=(1,) if donate else ())
            self._fns.append(compiled)
            self._params.append(jax.device_put(params, device))
        self.in_flight = 0

    def submit(self, x):
        """Enqueue one frame through all stages; returns the (device-
        resident, still-computing) final value immediately.  in_flight
        counts frames submitted but not yet collect()ed — the occupancy
        the dashboard/EC shares report."""
        import jax

        for index, fn in enumerate(self._fns):
            x = jax.device_put(x, self.groups[index][0])
            x = fn(self._params[index], x)
        self.in_flight += 1
        return x

    def collect(self, y):
        """Block for a submitted frame's value (host numpy) and retire it
        from the in-flight count."""
        value = self.result(y)
        self.in_flight = max(0, self.in_flight - 1)
        return value

    @staticmethod
    def result(y):
        """Block for a submitted frame's value (host numpy) without
        touching occupancy bookkeeping.  Stage outputs may be pytrees
        (e.g. a decode stage's (tokens, lengths, scores))."""
        import jax
        import numpy as np

        return jax.tree.map(np.asarray, y)

    def map(self, frames):
        """Pipeline a sequence: submit everything (filling all stages),
        then collect in order."""
        pending = [self.submit(frame) for frame in frames]
        return [self.collect(y) for y in pending]


def gpipe_spmd(stage_fn, mesh, num_microbatches: int,
               axis_name: str = AXIS_STAGE):
    """Build a GPipe-style SPMD step: weights sharded over the stage axis,
    microbatches streamed through with ppermute.

    stage_fn(stage_params, x) -> y must map one stage's computation; all
    stages share this code (uniform layers — the transformer case).

    Returns step(stage_params_stacked, microbatches) where
      stage_params_stacked: pytree with leading axis = num_stages, sharded
        over `axis_name`;
      microbatches: [num_microbatches, batch, ...] (replicated input);
    output: [num_microbatches, batch, ...] after every stage has processed
    every microbatch (activations rotate stage→stage over ICI)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    num_stages = mesh.shape[axis_name]

    def spmd(stage_params, microbatches):
        # stage_params leaves: [1, ...] (this stage's slice)
        params = jax.tree.map(lambda leaf: leaf[0], stage_params)
        stage_idx = jax.lax.axis_index(axis_name)
        n = num_microbatches
        steps = n + num_stages - 1
        perm = [(j, (j + 1) % num_stages) for j in range(num_stages)]

        # mark the loop state stage-varying up front (shard_map type
        # system: the fori_loop carry type must match its output)
        buffer = jax.lax.pcast(microbatches, axis_name, to="varying")
        carry = jnp.zeros_like(buffer[0])

        def step_fn(t, state):
            buffer, carry = state
            # stage 0 ingests microbatch t; others take the rotated carry
            mb_index = jnp.clip(t, 0, n - 1)
            x = jnp.where(stage_idx == 0, buffer[mb_index], carry)
            y = stage_fn(params, x)
            # emit: the LAST stage's result for microbatch (t - S + 1)
            out_index = jnp.clip(t - (num_stages - 1), 0, n - 1)
            done = (stage_idx == num_stages - 1) & \
                   (t >= num_stages - 1) & (t - (num_stages - 1) < n)
            buffer = jnp.where(
                done,
                jax.lax.dynamic_update_index_in_dim(buffer, y, out_index,
                                                    0),
                buffer)
            carry = jax.lax.ppermute(y, axis_name, perm)
            return buffer, carry

        buffer, _ = jax.lax.fori_loop(0, steps, step_fn, (buffer, carry))
        # only the last stage holds the final outputs: broadcast them
        result = jax.lax.psum(
            jnp.where(stage_idx == num_stages - 1, buffer, 0.0),
            axis_name)
        return result

    return jax.jit(jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=P()))
