"""Child process for the 2-process jax.distributed test.

Run by tests/test_multihost.py as:
    python tests/_multihost_child.py <port> <process_id> <num_processes>

Each process owns 2 virtual CPU devices (4 global), feeds its own
process-local batch stride through ``shard_batch`` (the
``make_array_from_process_local_data`` branch, parallel/mesh.py), and
checks that a jitted global-mean over the assembled array sees BOTH
hosts' data — the multi-host input path the reference covers with
DistributedDataParallel + DistributedSampler.
"""

import os
import sys

port, pid, nproc = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Multi-process CPU collectives go through a pluggable backend and jitted
# collectives fail without one ("Multiprocess computations aren't
# implemented on the CPU backend"); gloo ships in the jaxlib wheel.
jax.config.update("jax_cpu_collectives_implementation", "gloo")
jax.distributed.initialize(coordinator_address=f"localhost:{port}",
                           num_processes=nproc, process_id=pid)

import numpy as np  # noqa: E402

from raft_tpu.parallel.mesh import (batch_sharding, make_mesh,  # noqa: E402
                                    replicated_sharding, shard_batch)

assert jax.process_count() == nproc, jax.process_count()
n_global = jax.device_count()
n_local = jax.local_device_count()
assert n_global == nproc * n_local, (n_global, n_local)

mesh = make_mesh()  # all 4 global devices on the data axis

# Process p contributes rows filled with (p*local_batch + i) so the global
# mean uniquely identifies that every host's shard landed in the array.
local_batch = 2 * n_local
base = pid * local_batch
local = {
    "x": np.stack([np.full((4, 6), base + i, np.float32)
                   for i in range(local_batch)]),
}
global_batch = shard_batch(local, mesh)
assert global_batch["x"].shape == (nproc * local_batch, 4, 6), \
    global_batch["x"].shape

import jax.numpy as jnp  # noqa: E402

mean = jax.jit(jnp.mean,
               in_shardings=(batch_sharding(mesh),),
               out_shardings=replicated_sharding(mesh))

got = float(mean(global_batch["x"]))
want = float(np.mean(np.arange(nproc * local_batch)))
assert abs(got - want) < 1e-6, (got, want)
print(f"proc {pid}: global mean {got} OK", flush=True)
jax.distributed.shutdown()
