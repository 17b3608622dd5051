"""Shared example plumbing: platform selection before jax import.

Examples run on the devices jax finds (the TPU on a machine that has
one); pass --cpu-mesh N (or set HPX_TPU_EXAMPLE_CPU=N) to run on an
N-device virtual CPU mesh — the same environment the test suite uses,
so every example is runnable anywhere. Must be imported BEFORE jax.
"""

import os
import sys


def setup_platform(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    n = os.environ.get("HPX_TPU_EXAMPLE_CPU")
    if "--cpu-mesh" in argv:
        i = argv.index("--cpu-mesh")
        n = argv[i + 1] if i + 1 < len(argv) else "8"
        del argv[i:i + 2]
    if n:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={n}").strip()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from hpx_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    return argv
