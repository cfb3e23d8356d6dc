"""Run by hand, apart from the repo's tier-1 tests:

    python -m pytest benchmark/tests -q

Everything runs on the CPU, on four virtual devices, at tiny sizes."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=4"
)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny  # noqa: E402,F401  (puts the checkout and benchmark/ on sys.path)
