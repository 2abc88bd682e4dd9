"""Worker for the jax.distributed bootstrap test.

Launched twice with a kfrun-style KF_* env (2-peer list); each process
joins the global JAX runtime via `init_distributed`, then proves the
runtime is truly global: device_count spans both processes and a psum
over a global mesh sums contributions from each process's local shard.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=2")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from kungfu_tpu.parallel import init_distributed


def main():
    rank, n = init_distributed()
    assert n == 2, n
    assert jax.process_count() == 2
    local = jax.local_device_count()
    total = jax.device_count()
    assert total == 2 * local, (total, local)

    # global mesh over every device of both processes; each process
    # feeds its local shard, psum must see all of them
    mesh = Mesh(np.array(jax.devices()), ("data",))
    x = jnp.full((local,), float(rank + 1))  # local shard values
    arr = jax.make_array_from_process_local_data(
        NamedSharding(mesh, P("data")), np.asarray(x),
        (total,))
    mapped = shard_map(lambda a: jax.lax.psum(a.sum(), "data"),
                       mesh=mesh, in_specs=P("data"), out_specs=P(),
                       check_vma=False)
    got = float(jax.jit(mapped)(arr))
    want = float(local * 1 + local * 2)  # rank0 ones + rank1 twos
    assert got == want, (got, want)
    print(f"JAX_DIST_OK rank={rank} devices={total} psum={got}",
          flush=True)


if __name__ == "__main__":
    main()
