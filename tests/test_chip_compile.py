"""The chip's compiler on the main path's Pallas kernels, with no chip.

The only file in the tree that describes the TPU: a `v5e:2x2` topology
is DESCRIBED (nothing is attached), each kernel is lowered at the real
widths for one of its devices with ``interpret=False``, and the TPU
compiler either accepts it or raises what the chip would raise. The
interpret-mode tests next door cannot see a refused block shape, an
unaligned store or too much VMEM; this file can, at about two seconds a
case. A compile that passes is not a chip run — `chip_smoke.py` is.

The topology is described inside a module-scoped fixture (never at
import, in a skipif or in parametrize arguments): only one process may
hold the TPU library, and every xdist worker imports every test file.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from hpx_tpu.ops import attention_pallas as ap
from hpx_tpu.ops import stencil

_POOL_DTYPES = {"bf16": jnp.bfloat16, "int8": jnp.int8,
                "fp8": jnp.float8_e4m3fn}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable is written to the persistent cache
    # but cannot be read back without a chip (every later compile warns)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sds(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _kernel_text(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


# -- fused paged attention (the serving decode/verify kernels) -----------

_SLOTS, _NQ, _SMAX = 8, 16, 2048


def _paged_case(sds, kernel, kv, nkv, hd, w):
    bs = ap.resolve_paged_block(hd, kv)
    maxb = _SMAX // bs
    nb = 2 * _SLOTS * maxb + 1          # ContinuousServer's auto sizing
    pool = sds((nb, nkv, bs, hd), _POOL_DTYPES[kv])
    shapes = [sds((_SLOTS, w, _NQ, hd), jnp.bfloat16), pool, pool,
              sds((_SLOTS, maxb), jnp.int32), sds((_SLOTS,), jnp.int32)]
    if kv != "bf16":
        shapes += [sds((nb, nkv), jnp.float32)] * 2
    fpa = (ap.fused_paged_online_attention if kernel == "fused_online"
           else ap.fused_paged_attention)

    def call(q, kp, vp, table, pos, ks=None, vs=None):
        return fpa(q, kp, vp, table, pos, k_scale=ks, v_scale=vs,
                   interpret=False)
    return (call, *shapes)


@pytest.mark.parametrize("nkv", [1, 4, 16])
@pytest.mark.parametrize("kv", ["bf16", "int8", "fp8"])
@pytest.mark.parametrize("kernel", ["fused", "fused_online"])
def test_paged_attention_decode_compiles(sds, kernel, kv, nkv):
    _kernel_text(*_paged_case(sds, kernel, kv, nkv, 128, 1))


@pytest.mark.parametrize("kernel,kv,nkv,hd,w", [
    ("fused", "bf16", 4, 128, 4),           # spec-verify window
    ("fused_online", "int8", 4, 128, 4),
    ("fused", "bf16", 2, 64, 1),            # the tests_tpu head shape
    ("fused_online", "fp8", 2, 64, 4),
])
def test_paged_attention_window_and_hd64_compile(sds, kernel, kv, nkv,
                                                 hd, w):
    _kernel_text(*_paged_case(sds, kernel, kv, nkv, hd, w))


# -- flash attention (training forward/backward, ring chunk) -------------

@pytest.mark.parametrize("n,nkv", [(8, 8), (16, 4)],
                         ids=["mha8", "gqa16q4kv"])
@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_attention_compiles(sds, grad, n, nkv):
    b, s, h = 2, 4096, 128
    q = sds((b, s, n, h), jnp.bfloat16)
    kv = sds((b, s, nkv, h), jnp.bfloat16)

    def fwd(q, k, v):
        return ap.flash_attention(q, k, v, causal=True, interpret=False)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    text = _kernel_text(fn, q, kv, kv)
    if grad:        # forward + dQ + dK/dV kernels
        assert text.count("tpu_custom_call") >= 3


def test_flash_attention_chunk_compiles(sds):
    bn, s, h = 16, 2048, 128
    qkv = sds((bn, s, h), jnp.bfloat16)
    acc = sds((bn, s, h), jnp.float32)
    ml = sds((bn, s, 128), jnp.float32)

    def chunk(q, k, v, acc, m, l, d):
        return ap.flash_attention_chunk(q, k, v, acc, m, l, d,
                                        causal=True, interpret=False)
    _kernel_text(chunk, qkv, qkv, qkv, acc, ml, ml, sds((), jnp.int32))


# -- stencils (BASELINE config #2) ----------------------------------------

def test_pallas_heat_step_compiles(sds):
    u = sds((1 << 24,), jnp.float32)
    _kernel_text(lambda u: stencil.pallas_heat_step(u, np.float32(0.1)), u)


def test_pallas_multistep_compiles(sds):
    u = sds((1 << 19,), jnp.float32)
    _kernel_text(
        lambda u: stencil.pallas_multistep(u, np.float32(0.1), 1024), u)
