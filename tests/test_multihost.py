"""Multi-host loopback: the distributed backend across REAL process
boundaries (SURVEY §5.8 — the reference's NCCL/MPI analogue is XLA
collectives over ICI/DCN; jax.distributed is the DCN bootstrap).

Two OS processes × 4 virtual CPU devices each form one 8-device
global mesh via ``initialize_distributed`` (JAX_COORDINATOR env, the
deployment contract) and run a psum over a pjit-sharded global array.
This is strictly stronger than the 8-virtual-device single-process
tests: device-put of process-local shards, cross-process collective
compilation, and the coordinator handshake are all real.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
import jax

from evam_tpu.parallel.mesh import initialize_distributed

initialize_distributed()
assert jax.process_count() == 2, jax.process_count()

import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

devices = np.asarray(jax.devices()).reshape(8)   # 2 procs x 4 local
mesh = Mesh(devices, ("data",))
sharding = NamedSharding(mesh, P("data"))

# global [8, 16] array: each process provides its 4 local shards
local = jax.local_devices()
rows_per = 8 // jax.device_count() * len(local)  # 4 rows on this host
global_shape = (8, 16)
def row(i):
    return np.full((1, 16), float(i), np.float32)
# device ids are process-scoped; the shard index is the device's
# position in the global jax.devices() ordering (= mesh order)
pos = {d: i for i, d in enumerate(jax.devices())}
arrs = [
    jax.device_put(row(pos[d]), d) for d in local
]
garr = jax.make_array_from_single_device_arrays(
    global_shape, sharding, arrs)

@jax.jit
def total(x):
    return jnp.sum(x)

out = float(total(garr))
want = sum(range(8)) * 16.0
assert abs(out - want) < 1e-6, (out, want)
print(f"proc {jax.process_index()}: global sum ok ({out})", flush=True)
"""


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_global_mesh_psum(tmp_path):
    port = _free_port()
    procs = []
    for pid in range(2):
        env = dict(
            os.environ,
            JAX_COORDINATOR=f"127.0.0.1:{port}",
            JAX_NUM_PROCESSES="2",
            JAX_PROCESS_ID=str(pid),
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            PYTHONPATH=str(REPO),
        )
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        ))
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("multi-host worker timed out")
        outs.append(out)
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    assert any("global sum ok" in o for o in outs)
