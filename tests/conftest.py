"""Test configuration: force an 8-device virtual CPU backend.

Multi-device sharding/collective tests run on a virtual CPU mesh (JAX's
standard fake-backend trick) so the full SPMD path is exercised without TPU
pod hardware. The variables are set here, before jax is imported, so a
bare ``pytest`` needs no environment of its own.
"""

import gc
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

# XLA's CPU backend routes f32 convs/matmuls through oneDNN at reduced
# precision by default (~2e-3 relative error) — numerical-parity tests
# against torch need true f32
jax.config.update("jax_default_matmul_precision", "highest")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop every compiled executable at the end of each test module.

    Every XLA:CPU executable holds about fifteen memory mappings for its
    jitted code, and jax's jit caches and the program registry keep
    executables alive for the life of the process.
    One tier-1 process compiles thousands: by ``test_partition.py`` it
    held 48,627 mappings of the kernel's 65,530 (``vm.max_map_count``),
    the next large SPMD compile failed to map its code, and the run died
    with a segmentation fault inside ``backend_compile_and_load``.
    (Releasing only past a threshold of mappings was tried: no
    measurable difference in wall time, 969 s against 972 s.)
    """
    yield
    from raft_meets_dicl_tpu import compile as programs

    programs.reset()
    jax.clear_caches()
    gc.collect()
