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
# One step in flight at a time. With asynchronous dispatch a loop of train
# steps queues several executions, each needing all eight virtual devices'
# threads at its collectives; under six loaded xdist workers a later
# execution's threads can sit in the pool waiting at a rendezvous whose
# other participants cannot get a thread, and after 40 s XLA:CPU ABORTS
# the process (a lost worker, and with --dist loadfile the rest of its
# file). Seen in PR 22 on test_sharded_generate's 60-step loop.
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
