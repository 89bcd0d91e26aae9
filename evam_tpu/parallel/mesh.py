"""Device mesh construction and sharding rules.

The reference scales by running N independent single-device pipelines
(stream-level parallelism, SURVEY.md §2d-1) across CPU/iGPU/VPU
devices. The TPU design inverts that: one engine per model, its batch
axis sharded over the ``data`` axis of a `jax.sharding.Mesh`, with
XLA inserting the collectives over ICI. A second ``model`` axis is
available for tensor-parallel sharding of large heads (unused by the
small zoo models, exercised by the training step in
evam_tpu.parallel.train and dryrun_multichip).

Multi-host: `initialize_distributed` wires `jax.distributed` so the
same mesh spans hosts over DCN — the TPU-native counterpart of the
reference's cross-host ZeroMQ data plane (SURVEY.md §5.8): tensor
traffic rides ICI/DCN inside XLA, frames/results keep riding
ZeroMQ/MQTT outside it.
"""

from __future__ import annotations

import dataclasses
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from evam_tpu.obs import get_logger

log = get_logger("parallel.mesh")


@dataclasses.dataclass
class MeshPlan:
    mesh: Mesh
    data_axis: str = "data"
    model_axis: str | None = None

    @property
    def data_size(self) -> int:
        return self.mesh.shape[self.data_axis]

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.data_axis))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def device_names(self) -> str:
        """Every device of the mesh, space-joined in mesh order — the
        /engines ``device`` column (a TPU's own name holds commas)."""
        return " ".join(str(d) for d in self.mesh.devices.flat)

    def pad_batch(self, n: int) -> int:
        """Round n up to a multiple of the data-axis size."""
        d = self.data_size
        return -(-n // d) * d

    def per_device_plans(self) -> list["MeshPlan"]:
        """One single-device data-mesh plan per device of this mesh,
        in mesh order — the fleet mode's shard plans (EVAM_FLEET):
        each shard engine jits over its own chip, so small buckets
        never pay a collective, and ``pad_batch`` is the identity
        (data size 1)."""
        return [
            MeshPlan(mesh=Mesh(np.asarray([dev]), (self.data_axis,)),
                     data_axis=self.data_axis)
            for dev in self.mesh.devices.flat
        ]


def require_requested_backend() -> None:
    """Refuse a CPU backend nobody asked for (serve and bench entry).

    JAX falls back to the CPU when libtpu finds no chip; a server that
    carried on would answer requests at CPU speed under a TPU name.
    ``JAX_PLATFORMS=cpu`` is the explicit opt-in for the hardware-free
    dry run (tests, rehearsals)."""
    asked = os.environ.get("JAX_PLATFORMS", "")
    if jax.default_backend() == "cpu" and asked.strip().lower() != "cpu":
        raise SystemExit(
            "evam-tpu: JAX came up on the CPU backend but JAX_PLATFORMS="
            f"{asked!r} did not ask for it — no accelerator was found. "
            "Set JAX_PLATFORMS=cpu to run the CPU dry run on purpose.")


def build_mesh(
    shape: list[int] | None = None,
    axes: list[str] | None = None,
    devices: list | None = None,
) -> MeshPlan:
    """Build a mesh over the available devices.

    Default: 1-D ``data`` mesh over all local devices (the right
    layout for inference serving — batch data-parallel, models
    replicated). ``shape`` may contain one -1 wildcard.
    """
    devices = devices if devices is not None else jax.devices()
    axes = list(axes or ["data"])
    shape = list(shape or [-1])
    if len(shape) != len(axes):
        raise ValueError(f"mesh shape {shape} does not match axes {axes}")
    n = len(devices)
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1])) if len(shape) > 1 else 1
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        shape[shape.index(-1)] = n // known
    if int(np.prod(shape)) != n:
        raise ValueError(f"mesh shape {shape} != device count {n}")
    mesh = Mesh(np.asarray(devices).reshape(shape), axes)
    model_axis = "model" if "model" in axes else None
    log.info("mesh: %s over %d devices (%s, %s)", dict(zip(axes, shape)), n,
             devices[0].platform, devices[0].device_kind)
    return MeshPlan(mesh=mesh, model_axis=model_axis)


def batch_sharding(plan: MeshPlan) -> NamedSharding:
    return plan.batch_sharding()


def replicated(plan: MeshPlan) -> NamedSharding:
    return plan.replicated()


def shard_batch(plan: MeshPlan, array) -> jax.Array:
    """Place a host batch onto the mesh, sharded along the data axis."""
    return jax.device_put(array, plan.batch_sharding())


def initialize_distributed() -> None:
    """Multi-host init from env (JAX_COORDINATOR, JAX_NUM_PROCESSES,
    JAX_PROCESS_ID) — no-op when unset or single-process."""
    coord = os.environ.get("JAX_COORDINATOR")
    nproc = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    if not coord or nproc <= 1:
        return
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=nproc,
        process_id=int(os.environ.get("JAX_PROCESS_ID", "0")),
    )
    log.info("jax.distributed initialized: %d processes", nproc)
