"""Real-chip kernel tests (SURVEY.md §4).

Unlike tests/conftest.py this does NOT force the CPU platform — the
whole point is compiling the pallas kernels through Mosaic on the real
TPU, so a Mosaic regression fails a test instead of silently showing up
as a bench drop. Every test is marked `tpu`. They skip only where the
caller names the CPU (`JAX_PLATFORMS=cpu`); anywhere else a missing
chip is a FAILURE, not a skip. One process: a chip belongs to whoever
touched jax first, so nothing here probes from a child.

Run through the chip tool:  python -m pytest tests_tpu -q
"""

import os

import pytest


def pytest_collection_modifyitems(config, items):
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    skip = pytest.mark.skip(reason="JAX_PLATFORMS=cpu: chip tests need "
                            "the TPU")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(autouse=True)
def _needs_tpu(request):
    if "tpu" in request.keywords:
        import jax
        assert jax.default_backend() == "tpu", (
            f"chip test on platform {jax.default_backend()!r}: no TPU "
            "found")


@pytest.fixture
def float32_products():
    """For tests that pin a float32 kernel to a float32 XLA oracle at a
    few 1e-4: XLA's default on the TPU multiplies float32 in bfloat16
    passes (2^-8 per product — the first chip run of this suite, PR 22,
    saw 6e-3 between the two), so both sides get real float32 products.
    Not for bfloat16 operands: Mosaic refuses the precision there."""
    import jax
    with jax.default_matmul_precision("float32"):
        yield
