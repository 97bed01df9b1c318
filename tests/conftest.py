"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on host CPU devices, and the chip's compiler is exercised on a
described v5e in tests/test_chip_compile.py. ``JAX_PLATFORMS`` is read
by jax at import, so setting it here (before any test imports jax) is
enough, libtpu installed or not.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The persistent compilation cache follows the one rule
# (parallel/compile_cache.py), applied at package import: the suite
# builds many fresh DeviceEngines with IDENTICAL configs across test
# files, and the HLO-keyed on-disk cache compiles each program once per
# machine instead of once per file.
import madsim_tpu  # noqa: E402,F401
