"""Test harness: fake 8-device CPU mesh.

The TPU-native analogue of the reference's "gloo on localhost" test mode
(SURVEY.md §4): ``--xla_force_host_platform_device_count=8`` gives every test
an 8-device CPU backend, so all sharding/collective paths (the code DDP would
exercise via multi-process gloo) run in a single pytest process.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax  # noqa: E402

# The suite is a CPU suite (eight virtual devices) wherever it runs: hold
# the platform to CPU before any backend initializes, so that on a chip
# host it neither takes the chip nor finds one device where it needs eight.
jax.config.update("jax_platforms", "cpu")
# One execution in flight at a time, as far as a flag can: XLA:CPU runs a
# collective's rendezvous on pool threads, and a thread that waits there
# holds its place in the pool. With two executions of the eight virtual
# devices in flight, the threads waiting at the later one's rendezvous can
# keep the earlier one's last participant from ever getting a thread: a
# deadlock, not a slow box, and after 40 s XLA ABORTS the process (a lost
# xdist worker; a limit of 150 s through
# --xla_cpu_collective_call_terminate_timeout_seconds aborts just the same,
# PR 31). This flag keeps eager dispatch in line, but a multi-device
# execution still returns before its devices finish, so a LOOP of many
# train steps must also wait for each step's output
# (test_sharded_generate's 60-step loop lost its worker in the driver's
# runs of PR 29 and PR 30 until it did).
jax.config.update("jax_cpu_enable_async_dispatch", False)

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    ds = jax.devices()
    assert len(ds) == 8, f"expected 8 fake CPU devices, got {ds}"
    return ds


@pytest.fixture()
def mesh_1d(devices):
    from distributed_pytorch_example_tpu.runtime import make_mesh

    return make_mesh()


@pytest.fixture()
def mesh_2x2x2(devices):
    from distributed_pytorch_example_tpu.runtime import MeshSpec, make_mesh

    return make_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
