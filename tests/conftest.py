"""Test harness config.

All JAX tests run on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``) so multi-chip sharding logic
is exercised without TPU hardware, mirroring the reference's single-machine
multi-process emulation strategy (reference: scripts/tests/*), with
Pallas kernels in interpret mode. `JAX_PLATFORMS=cpu`, set here before
the first `import jax`, is what holds the suite (and the worker
processes it spawns, which inherit it) to the CPU.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("KF_LOG_LEVEL", "warn")
