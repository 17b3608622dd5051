"""The chip's compiler on the main path's Pallas kernels, with no chip.

The only file in the tree that describes the TPU: a `v5e:2x2` topology
is DESCRIBED (nothing is attached), each kernel is lowered at the real
widths for one of its devices with ``interpret=False``, and the TPU
compiler either accepts it or raises what the chip would raise. The
interpret-mode tests next door cannot see a refused block shape, an
unaligned store or too much VMEM; this file can, at about two seconds a
case. A compile that passes is not a chip run — `chip_smoke.py` is.
It also reads the compiled text for what the compiler ADDS around a
kernel: the pool-layout guard below fails on a whole-pool `copy(`.

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
from hpx_tpu.ops import paged_attention as pa
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

    def make(shape, dtype, row_major=False):
        # row_major: the parameter arrives in {n-1, .., 0}, as an array
        # a program left behind does (else the compiler picks)
        where = one_chip
        if row_major:
            from jax.experimental.layout import Format, Layout
            where = Format(Layout(major_to_minor=tuple(
                range(len(shape)))), one_chip)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)
    return make


def _kernel_text(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


# -- fused paged attention (the serving decode/verify kernels) -----------

_SLOTS, _NQ, _SMAX = 8, 16, 2048


def _paged_case(sds, kernel, kv, nkv, hd, w):
    bs = ap.resolve_paged_block(hd, kv)[0]
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


# the benchmark's two serving cells at their real widths: 32 slots;
# StarCoder2-3B 24 q / 2 kv over 128 entries of 8,193 blocks; Laguna's
# full layers 48 q / 8 kv over 304 entries of 19,457 blocks, its window
# layers 64 q / 8 kv over a ring of 34 in 1,217 blocks; EvaByte 32 q /
# 32 kv over a two-grain run of 50 entries of 1,201 blocks of 64 rows
# (24 slots in its cell; a slot more or less changes no kernel)
_CELL_WIDTHS = {"sc2-3b": (24, 2, 128, 8193, 0),
                "laguna-full": (48, 8, 304, 19457, 0),
                "laguna-window": (64, 8, 34, 1217, 512),
                "evabyte": (32, 32, 50, 1201, 0)}
_CELL_BLOCK = {"evabyte": 64}


def _cell_case(make, cell, w=1, hd=128, **pool_kw):
    """(`fused` call, pool, argument shapes) of one of `_CELL_WIDTHS`,
    32 slots; `make(shape, dtype)` places a shape."""
    nq, nkv, maxb, nb, window = _CELL_WIDTHS[cell]
    pool = make((nb, nkv, _CELL_BLOCK.get(cell, 16), hd), jnp.bfloat16,
                **pool_kw)

    def call(q, kp, vp, table, pos):
        return ap.fused_paged_attention(q, kp, vp, table, pos,
                                        interpret=False, window=window)
    return call, pool, (make((32, w, nq, hd), jnp.bfloat16), pool, pool,
                        make((32, maxb), jnp.int32), make((32,), jnp.int32))


def _walk_grids(fn, *shapes) -> list:
    """The grid of every `pallas_call` in fn's jaxpr, `shard_map`'s and
    `jit`'s bodies included."""
    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                yield tuple(e.params["grid_mapping"].grid)
            for v in e.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    yield from walk(inner)
    return list(walk(jax.make_jaxpr(fn)(*shapes).jaxpr))


# heads a copy carries at the cells' widths (W = 1 and 4 alike), two
# sets of banks counted: EvaByte's 32 heads x 3,200 rows are 26 MB a
# pool, so a slot is two grid steps of 16
_CELL_HG = {"sc2-3b": 2, "laguna-full": 8, "laguna-window": 8,
            "evabyte": 16}


@pytest.mark.parametrize("w", [1, 4], ids=["decode", "window4"])
@pytest.mark.parametrize("cell", sorted(_CELL_WIDTHS))
def test_bounded_walk_compiles_at_the_cells_widths(sds, cell, w):
    """`_paged_live_kernel` (pools left in HBM, a data-dependent loop
    of block copies in one grid step a (slot, group of kv heads), one
    copy an entry for the whole group, into the set of banks the step
    before is not reading) at the K/V
    serving cells' widths, full table and ring, under both names, with
    nothing pool-shaped copied around it, and a stated VMEM limit
    (both sets, a head's finish, 8 MB) inside the chip's 128 MiB."""
    call, pool, shapes = _cell_case(sds, cell, w)
    nq, nkv, maxb = _CELL_WIDTHS[cell][:3]
    hg = _CELL_HG[cell]
    assert _walk_grids(call, *shapes) == [(32, nkv // hg)]
    assert ap._walk_vmem_bytes(
        hg, maxb * pool.shape[2], 128, w * nq // nkv, 2, 2) + (8 << 20) \
        < 128 << 20
    text = _kernel_text(call, *shapes)
    assert ("hpx_paged_fused_win" in text) == (cell == "laguna-window")
    assert "hpx_paged_fused" in text
    assert _pool_ops(text, pool) == []


@pytest.mark.parametrize("cell", ["sc2-3b", "laguna-full"])
def test_bounded_walk_compiles_under_the_serving_mesh(topo, cell):
    """The mesh form: the same call inside `shard_map` on Mesh(dp=2,
    tp=2) of the described chips, slots over dp, kv heads over tp, the
    pools' block axis replicated (serving._paged_shard_specs) — the
    pools stay in each chip's HBM and the kernel's copies index them by
    the table's global block ids. The group divides the SHARD's kv
    heads: one of StarCoder2-3B's two, four of Laguna's eight."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    specs = {4: P("dp", None, "tp", None), 2: P("dp", None), 1: P("dp")}
    pool_sp = P(None, "tp", None, None)

    def on(shape, dtype):
        # the pools lead with their blocks, all else with the 32 slots
        spec = pool_sp if shape[0] != 32 else specs[len(shape)]
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    call, _, shapes = _cell_case(on, cell)
    sharded = jax.shard_map(call, mesh=mesh, out_specs=specs[4],
                            in_specs=(specs[4], pool_sp, pool_sp, specs[2],
                                      specs[1]))
    shard_nkv = _CELL_WIDTHS[cell][1] // 2
    (slots, groups), = _walk_grids(sharded, *shapes)
    assert slots == 16 and shard_nkv % groups == 0
    assert shard_nkv // groups == {"sc2-3b": 1, "laguna-full": 4}[cell]
    assert "hpx_paged_fused" in _kernel_text(sharded, *shapes)


@pytest.mark.parametrize("nkv,smax,dtype,hg", [
    (8, 4864, jnp.float32, 4),       # 8 KB a head and entry, 2 x 20 MB
    (8, 9728, jnp.float32, 2),
    (8, 16384, jnp.bfloat16, 2),
    (6, 16384, jnp.bfloat16, 2),
    (1, 28672, jnp.bfloat16, 1),     # ROADMAP A11: must not move down
    (8, 28672, jnp.bfloat16, 1),
    (1, 114688, jnp.bfloat16, 1),    # the stated VMEM limit's reach: two
                                     # sets compile through 126,976
], ids=lambda v: getattr(v, "__name__", str(v)))
def test_bounded_walk_compiles_at_every_group_the_rule_picks(
        sds, nkv, smax, dtype, hg):
    """The launch states its VMEM limit from the banks it allocates, so
    whatever group `walk_heads_per_copy` picks compiles: float32 pools,
    a group that two sets of banks halve, and the long tables at which
    only one head fits (smax 28,672 compiled before this path grouped
    heads, and still does with two sets of its banks)."""
    maxb, b, g = smax // 16, 8, 3
    item = jnp.dtype(dtype).itemsize
    assert ap.walk_heads_per_copy(nkv, smax, 128, g, item, item) == hg
    pool = sds((b * maxb + 1, nkv, 16, 128), dtype)

    def call(q, kp, vp, table, pos):
        return ap.fused_paged_attention(q, kp, vp, table, pos,
                                        interpret=False)
    shapes = (sds((b, 1, g * nkv, 128), dtype), pool, pool,
              sds((b, maxb), jnp.int32), sds((b,), jnp.int32))
    assert _walk_grids(call, *shapes) == [(b, nkv // hg)]
    text = _kernel_text(call, *shapes)
    if dtype == jnp.bfloat16:
        assert _pool_ops(text, pool) == []


def test_a_head_of_64_streams_its_pools_without_a_copy(sds):
    """A head narrower than the 128 lanes the chip copies out of an
    HBM array keeps the grid walk, whose BlockSpec streams (block, 64)
    tiles in place: given pools in the layout the kernels pin, no
    pool-shaped `pad` or `copy` around the kernel (widening the pools
    for the bounded walk would cost two a call)."""
    call, pool, shapes = _cell_case(sds, "sc2-3b", hd=64, row_major=True)
    assert _pool_ops(_kernel_text(call, *shapes), pool) == []


# -- the pool layout rule: no whole-pool copy around a row write ---------
#
# The fused kernels pin their pool operands to `{3,2,1,0}`; a write that
# the compiler runs in another layout costs two copies of the WHOLE pool
# a pool, layer and step (ops/paged_attention's docstring). Shapes of
# the benchmark's serving cell: 32 slots, 24 q / 2 kv x 128, block 16,
# smax 2,048 (8,193 blocks), pools donated as the server donates them.

_C_SLOTS, _C_NQ, _C_NKV, _C_HD, _C_BS = 32, 24, 2, 128, 16


def _pool_ops(text, pool) -> list:
    """The `copy(` / `pad(` ops of a compiled module whose RESULT has
    the pool's leading dimensions (a pad widens the last one)."""
    tag = {"bfloat16": "bf16", "int8": "s8"}[jnp.dtype(pool.dtype).name]
    shape = f"{tag}[{','.join(map(str, pool.shape[:-1]))},"
    return [ln.strip() for ln in text.splitlines() for op in ("copy", "pad")
            if f" {op}(" in ln and shape in ln.split(f" {op}(")[0]]


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("w", [1, 4], ids=["decode", "window4"])
def test_paged_write_leaves_the_pool_where_it_lies(sds, w, kv):
    b, maxb = _C_SLOTS, _SMAX // _C_BS
    nb = 2 * b * maxb + 1
    pool = sds((nb, _C_NKV, _C_BS, _C_HD), _POOL_DTYPES[kv])
    new = (b, _C_NKV, _C_HD) if w == 1 else (b, w, _C_NKV, _C_HD)
    shapes = [sds((b, w, _C_NQ, _C_HD), jnp.bfloat16),
              sds(new, jnp.bfloat16), sds(new, jnp.bfloat16), pool, pool,
              sds((b, maxb), jnp.int32), sds((b,), jnp.int32)]
    donate = (3, 4)
    if kv != "bf16":
        shapes += [sds((nb, _C_NKV), jnp.float32)] * 2
        donate += (7, 8)
    attend = (pa.paged_decode_attention if w == 1
              else pa.paged_window_attention)

    def call(q, kn, vn, kp, vp, table, pos, ks=None, vs=None):
        return attend(q, kn, vn, kp, vp, table, pos, k_scale=ks,
                      v_scale=vs, fused=True, interpret=False)
    text = jax.jit(call, donate_argnums=donate).lower(
        *shapes).compile().as_text()
    assert "tpu_custom_call" in text
    assert _pool_ops(text, pool) == []


# the window block group of the benchmark's Laguna cell: 32 slots, 64 q /
# 8 kv x 128 on a window layer, window 512 = a ring of 34 columns over
# a pool of 32 x (34 + 4) + 1 blocks (serving._init_paged)
_W_NQ, _W_NKV, _W_WIN, _W_RING, _W_NB = 64, 8, 512, 34, 1217


@pytest.mark.parametrize("kernel", ["fused", "fused_online"])
def test_window_group_write_and_kernel_leave_the_pool_where_it_lies(
        sds, kernel):
    """The ring write (`scatter_token(ring=True)`) keeps the layout
    rule, and `hpx_paged_fused_win` lowers over a 34-column table."""
    b = _C_SLOTS
    pool = sds((_W_NB, _W_NKV, _C_BS, _C_HD), jnp.bfloat16)
    new = sds((b, _W_NKV, _C_HD), jnp.bfloat16)

    def call(q, kn, vn, kp, vp, table, pos):
        return pa.paged_decode_attention(
            q, kn, vn, kp, vp, table, pos, interpret=False,
            fused=True if kernel == "fused" else "online", window=_W_WIN)
    text = jax.jit(call, donate_argnums=(3, 4)).lower(
        sds((b, 1, _W_NQ, _C_HD), jnp.bfloat16), new, new, pool, pool,
        sds((b, _W_RING), jnp.int32), sds((b,), jnp.int32)
    ).compile().as_text()
    assert "tpu_custom_call" in text and "_win" in text
    assert _pool_ops(text, pool) == []


@pytest.mark.parametrize("tokens", [32, 128, 512],
                         ids=["decode32", "chunk128", "chunk512"])
def test_moe_gmm_compiles_at_the_cells_widths(sds, tokens, monkeypatch):
    """`hpx_moe_gmm` inside the whole drop-free sparse FFN at Laguna's
    widths: 256 experts of 2048 x 512 in bfloat16, top-8, a decode
    step's 32 tokens, a prefill chunk's 128 and the 512 of a chunk as
    wide as the model's ridge (`serving._ridge_chunk`'s ceiling)."""
    from hpx_tpu.models import moe
    cfg = moe.MoeConfig(n_experts=256, top_k=8, d_model=2048, d_ff=512,
                        dtype=jnp.bfloat16, mlp="swiglu", router="sigmoid",
                        renorm=True, scale=2.5, shared_d_ff=512)
    params = jax.tree.map(
        lambda x: sds(x.shape, x.dtype),
        jax.eval_shape(lambda: moe.init_moe_params(
            cfg, jax.random.PRNGKey(0))))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _kernel_text(lambda x, p: moe.moe_ffn_serve(x, p, cfg),
                        sds((tokens, 2048), jnp.bfloat16), params)
    assert "hpx_moe_gmm" in text


def _two_group_model():
    """Two layers of the Laguna cell's kinds: full attention (48 q
    heads, YaRN on half the head, dense MLP) and window attention (64 q
    heads, window 512, sparse FFN), 8 kv heads x 128, at a width a test
    compiles in seconds."""
    from hpx_tpu.models.transformer import RopeSpec, TransformerConfig
    return TransformerConfig(
        vocab=512, d_model=256, n_heads=48, head_dim=_C_HD, n_layers=2,
        d_ff=512, n_kv_heads=_W_NKV, rope=True, dtype=jnp.bfloat16,
        norm="rmsnorm", norm_eps=1e-6, mlp="swiglu", tied=False,
        attn_gate=True, layer_heads=(48, _W_NQ),
        layer_window=(0, _W_WIN),
        layer_rope=(RopeSpec(5e5, 64, 64.0, 4096, 64.0, 1.0, 1.4159),
                    RopeSpec(1e4)),
        layer_sparse=(False, True), n_experts=16, moe_top_k=8,
        moe_d_ff=128, moe_shared_d_ff=128, moe_router="sigmoid",
        moe_renorm=True, moe_scale=2.5)


def _one_group_model():
    from hpx_tpu.models.transformer import TransformerConfig
    return TransformerConfig(vocab=512, d_model=256, n_heads=_C_NQ,
                             head_dim=_C_HD, n_layers=2, d_ff=512,
                             n_kv_heads=_C_NKV, rope=True,
                             dtype=jnp.bfloat16)


@pytest.mark.parametrize("model", [_one_group_model, _two_group_model],
                         ids=["one-group", "full+window"])
def test_server_step_program_has_no_pool_copy(sds, monkeypatch, model):
    """The server's own `jit_step` (two layers of a cell's widths,
    built by `_paged_step_prog`) for the described chip, for a model
    with one block group and for one with a full and a window group.
    The server is built here on the CPU from parameter SHAPES;
    `jax.default_backend` is steered so that it picks the `fused`
    kernel and the kernels lower for the chip, as they do there."""
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.models.transformer import init_params
    cfg = model()
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    srv = ContinuousServer(params, cfg, paged=True, slots=_C_SLOTS,
                           smax=_SMAX)
    assert srv._paged_kernel == "fused" and srv.block_size == _C_BS

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    s = srv.slots
    tables = (sds((s, srv._maxb), jnp.int32),)
    if srv._win:
        assert srv._ring == _W_RING and \
            srv._pools[1][0].shape[0] == _W_NB
        tables += (sds((s, srv._ring), jnp.int32),)
    text = srv._paged_step_prog().lower(
        on_chip(params), on_chip(srv._pools), None,
        sds((s,), jnp.int32), sds((s,), jnp.int32), tables,
        sds((s,), jnp.float32), sds((s, 2), jnp.uint32)).compile().as_text()
    assert text.count("tpu_custom_call") >= cfg.n_layers
    for pools in srv._pools:
        assert _pool_ops(text, pools[0]) == []
    assert ("hpx_paged_fused_win" in text) == bool(srv._win)
    assert ("hpx_moe_gmm" in text) == bool(srv._win)


# -- the recurrent state and the latent pool (kimi-linear.reason-closed) --
#
# 48 slots; a KDA layer's state 32 heads x 128 x 128 float32 a slot; an
# MLA layer's pool 48 x 264 + 1 blocks of 16 rows of 640 (512 + 64 and
# the pad to whole lanes), 32 query heads; both donated as the server
# donates its cache pytree.

_K_SLOTS, _K_HEADS, _K_HD, _K_ROW, _K_RANK, _K_MAXB = 48, 32, 128, 640, 512, 264


def _copies_of(text, shape: str) -> list:
    return [ln.strip() for ln in text.splitlines()
            if " copy(" in ln and shape in ln.split(" copy(")[0]]


def test_the_states_update_runs_in_place(sds):
    """`hpx_kda_step` over the cell's state: the kernel's state operand
    is its own output (`input_output_aliases`), so the donated 201 MB
    of a layer are neither copied nor re-laid."""
    from hpx_tpu.ops import kda
    b, h, d = _K_SLOTS, _K_HEADS, _K_HD
    vec = sds((b, h, d), jnp.float32)
    state = sds((b, h, d, d), jnp.float32)
    compiled = jax.jit(
        lambda q, k, v, g, beta, s: kda.kda_step(
            q, k, v, g, beta, s, kernel="pallas", interpret=False),
        donate_argnums=(5,)).lower(
        vec, vec, vec, vec, sds((b, h), jnp.float32), state).compile()
    text = compiled.as_text()
    assert "hpx_kda_step" in text
    assert _copies_of(text, f"f32[{b},{h},{d},{d}]") == []
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        b * h * d * d * 4


# (slots, query heads, table width): Kimi-Linear's cell, and
# DeepSeek-V2's (128 heads over a table of 1,576 entries = 25,216 rows)
_LATENT_SHAPES = {"kimi": (_K_SLOTS, _K_HEADS, _K_MAXB, 12673),
                  "deepseek-v2": (64, 128, 1576, 20000)}


@pytest.mark.parametrize("cell", sorted(_LATENT_SHAPES))
def test_the_latent_rows_write_leaves_the_pool_where_it_lies(sds, cell):
    """The latent row's write under the pool layout rule (block, the
    one head and row indexed together) and `hpx_mla_paged` over the
    cell's pool: no pool-shaped copy around either, and the kernel's
    VMEM does not hold the table's width (two buffers and a carry:
    the same bytes under 128 heads at 25,216 rows as a bank of 4,224
    rows alone took)."""
    b, heads, maxb, nb = _LATENT_SHAPES[cell]
    pool = sds((nb, 1, _C_BS, _K_ROW), jnp.bfloat16)
    text = jax.jit(
        lambda q, row, p, table, pos: pa.paged_latent_attention(
            q, row, p, table, pos, rank=_K_RANK, scale=192 ** -0.5,
            fused=True, interpret=False),
        donate_argnums=(2,)).lower(
        sds((b, heads, _K_ROW), jnp.bfloat16),
        sds((b, _K_ROW), jnp.bfloat16), pool,
        sds((b, maxb), jnp.int32), sds((b,), jnp.int32)
    ).compile().as_text()
    assert "hpx_mla_paged" in text
    assert _pool_ops(text, pool) == []
    need = ap.latent_vmem_bytes(
        heads, _K_ROW, _K_RANK, ap.LATENT_WALK_ENTRIES * _C_BS, 2)
    assert need <= 16 << 20 and need == ap.latent_vmem_bytes(
        heads, _K_ROW, _K_RANK, min(ap.LATENT_WALK_ENTRIES, 4 * maxb)
        * _C_BS, 2)
    # with the copies coalesced (PR 51: a fold of `LATENT_RUN` entries
    # that name neighbours lands as one descriptor in a buffer that is
    # now (2, entries, block, R)) the kernel asks the scoped VMEM it
    # asked before, and takes the four operands it took: table,
    # positions, q, pool
    assert ap.latent_walk_sizes(maxb) == (32, ap.LATENT_RUN) == (32, 32)
    call = next(ln for ln in text.splitlines()
                if "custom-call(" in ln and "hpx_mla_paged" in ln)
    assert need == 16 << 20 and f'"size":"{need}"' in call
    operands = call.split("custom-call(")[1].split(")")[0]
    assert len(operands.split(",")) == 4, operands


def test_a_wide_chunks_latent_accumulator_stays_in_fast_memory(sds):
    """`_latent_attention` for a 256-wide chunk of DeepSeek-V2's 128
    heads over the cell's scratch of 25,216 rows: walked in groups of
    `LATENT_PAIRS_A_GROUP` pairs the compiler gives every float32
    array of a block loop (running state, scores, the accumulator)
    fast memory, `S(1)` in the compiled text, and the program's
    temporaries are the joined output (17 MB). As ONE walk (up to PR
    46) the accumulator `f32[1,128,256,512]` alone stays in HBM, 67 MB
    of temporaries that every block reads and writes whole: a 256-wide
    chunk cost three 128-wide ones on the chip."""
    import re
    from hpx_tpu.models import transformer as tfm
    compiled = jax.jit(
        lambda q, lat, qpos: tfm._latent_attention(q, lat, qpos, _K_RANK,
                                                   0.1)).lower(
        sds((1, 256, 128, _K_ROW), jnp.bfloat16),
        sds((1, 25216, _K_ROW), jnp.bfloat16), sds((256,), jnp.int32)
    ).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 40e6
    loops = [ln for ln in compiled.as_text().splitlines()
             if re.search(r" while\(", ln)]
    assert len(loops) == tfm.latent_groups(1, 256, 128) == 2
    carried = [a for ln in loops
               for a in re.findall(rf"f32\[[\d,]*{_K_RANK}\]\{{[^}}]*\}}", ln)]
    assert carried and all("S(1)" in a for a in carried), carried


def test_hybrid_server_step_program_copies_neither_state_nor_pool(
        sds, monkeypatch):
    """The server's own `jit_step` for a KDA layer and an MLA layer of
    the cell's mixer widths (a narrow d_model, a sparse FFN that holds
    a share of its experts), built by `_paged_step_prog` from parameter
    SHAPES: one Pallas call a mixer and one for the experts, the state
    and the latent pool donated and left where they lie."""
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.models.transformer import TransformerConfig, init_params
    cfg = TransformerConfig(
        vocab=512, d_model=256, n_heads=_K_HEADS, n_layers=2, d_ff=512,
        dtype=jnp.bfloat16, norm="rmsnorm", mlp="swiglu", tied=False,
        layer_mixer=("kda", "mla"), kda_heads=_K_HEADS,
        kda_head_dim=_K_HD, kda_conv=4, kda_rank=128, mla_rank=_K_RANK,
        mla_nope_dim=128, mla_rope_dim=64, mla_v_dim=128,
        layer_sparse=(False, True), n_experts=64, moe_held=(16, 32),
        moe_top_k=8, moe_d_ff=128, moe_shared_d_ff=128,
        moe_router="sigmoid", moe_renorm=True, moe_scale=2.446,
        moe_bias=True)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    srv = ContinuousServer(params, cfg, paged=True, slots=_K_SLOTS,
                           smax=_K_MAXB * _C_BS)
    assert srv._paged_kernel == "fused" and srv.block_size == _C_BS
    assert srv._alloc.num_blocks == _K_SLOTS * _K_MAXB + 1
    assert srv._pools[1][0].shape[-1] == _K_ROW

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    s = srv.slots
    # the operands `jit_step` has had since PR 40, operand for operand
    # (parameters, pools, scales, tokens, positions, tables, the two
    # sampling lanes): whether a table's groups are runs is read off
    # the table INSIDE the kernel, so no program gains an input or an
    # output a ladder width (PR 43's lesson)
    lowered = srv._paged_step_prog().lower(
        on_chip(params), on_chip(srv._pools), None,
        sds((s,), jnp.int32), sds((s,), jnp.int32),
        (sds((s, srv._maxb), jnp.int32),),
        sds((s,), jnp.float32), sds((s, 2), jnp.uint32))
    pools_out, scales_out, tokens_out, moe_out = lowered.out_info
    assert scales_out is None and tokens_out.shape == (s,)
    assert jax.tree.map(lambda x: x.shape, pools_out) == jax.tree.map(
        lambda x: x.shape, srv._pools)
    assert len(jax.tree.leaves(moe_out)) == 1
    text = lowered.compile().as_text()
    for name in ("hpx_kda_step", "hpx_mla_paged", "hpx_moe_gmm"):
        assert name in text
    assert _copies_of(
        text, f"f32[{s},{_K_HEADS},{_K_HD},{_K_HD}]") == []
    assert _pool_ops(text, srv._pools[1][0]) == []


# -- learned-sparse and lightning layers (the MiniCPM-SALA cell) ---------

# 64 slots of up to 35,840 rows: tables of 560 blocks of 64 rows over a
# pool of 24,576; 32 q / 2 kv x 128; a walk of at most 128 entries; 32
# linear heads of 128 x 128
_S_SLOTS, _S_NQ, _S_NKV, _S_HD, _S_BS, _S_MAXB, _S_NB = 64, 32, 2, 128, 64, \
    560, 24576


def _sala_spec():
    from hpx_tpu.ops.sparse_attention import SparseSpec
    return SparseSpec()


def test_the_lightning_states_update_runs_in_place(sds):
    """`hpx_lightning_step` over the cell's state: read once, written
    once, the donated 134 MB of a layer neither copied nor re-laid."""
    from hpx_tpu.ops import lightning
    b, h, d = _S_SLOTS, 32, 128
    vec = sds((b, h, d), jnp.float32)
    compiled = jax.jit(
        lambda q, k, v, g, s: lightning.lightning_step(
            q, k, v, g, s, kernel="pallas", interpret=False),
        donate_argnums=(4,)).lower(
        vec, vec, vec, sds((h,), jnp.float32),
        sds((b, h, d, d), jnp.float32)).compile()
    text = compiled.as_text()
    assert "hpx_lightning_step" in text
    assert _copies_of(text, f"f32[{b},{h},{d},{d}]") == []
    assert compiled.memory_analysis().alias_size_in_bytes >= \
        b * h * d * d * 4


def test_the_sparse_walk_and_its_index_write_leave_the_pools_where_they_lie(
        sds):
    """A sparse layer's decode at the cell's shapes: the row's write,
    the index entry's write (every axis ahead of head_dim indexed: the
    layout rule), the selection and `hpx_paged_sparse` over pools left
    in HBM. No pool-shaped copy around any of the three pools."""
    from hpx_tpu.ops import sparse_attention as sa
    spec = _sala_spec()
    pool = sds((_S_NB, _S_NKV, _S_BS, _S_HD), jnp.bfloat16)
    idx = sds((_S_NB, _S_BS // spec.stride * _S_NKV, _S_HD), jnp.float32)
    new = sds((_S_SLOTS, _S_NKV, _S_HD), jnp.bfloat16)
    text = jax.jit(
        lambda q, kn, vn, kp, vp, ip, table, pos: sa.paged_sparse_decode(
            q, kn, vn, kp, vp, ip, table, pos, spec),
        donate_argnums=(3, 4, 5)).lower(
        sds((_S_SLOTS, 1, _S_NQ, _S_HD), jnp.bfloat16), new, new, pool,
        pool, idx, sds((_S_SLOTS, _S_MAXB), jnp.int32),
        sds((_S_SLOTS,), jnp.int32)).compile().as_text()
    assert "hpx_paged_sparse" in text
    assert _pool_ops(text, pool) == []
    assert [ln for ln in text.splitlines() if " copy(" in ln
            and f"f32[{_S_NB}," in ln.split(" copy(")[0]] == []


def _sala_server(monkeypatch):
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.models.transformer import (RopeSpec, TransformerConfig,
                                            init_params)
    cfg = TransformerConfig(
        vocab=512, d_model=256, n_heads=_S_NQ, head_dim=_S_HD,
        n_kv_heads=_S_NKV, n_layers=2, d_ff=512, dtype=jnp.bfloat16,
        norm="rmsnorm", mlp="swiglu", tied=False,
        layer_mixer=("sparse", "lightning"),
        layer_rope=(None, RopeSpec(10000.0)), lightning_heads=32,
        lightning_head_dim=128, qk_norm=True, emb_scale=12.0,
        residual_scale=0.2475, logit_scale=0.0625,
        layer_published=(9, 10), published_layers=32)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    srv = ContinuousServer(params, cfg, paged=True, slots=_S_SLOTS,
                           smax=_S_MAXB * _S_BS, num_blocks=1024,
                           prefill_chunk=512)
    assert srv._paged_kernel == "fused" and srv.block_size == _S_BS
    return cfg, params, srv


def test_sala_server_step_program_copies_neither_state_nor_pool(
        sds, monkeypatch):
    """The server's own `jit_step` for a sparse layer and a lightning
    layer of the cell's mixer widths (a narrow d_model), built by
    `_paged_step_prog` from parameter SHAPES: one Pallas call a mixer,
    the state and the three pools donated and left where they lie."""
    cfg, params, srv = _sala_server(monkeypatch)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    s = srv.slots
    text = srv._paged_step_prog().lower(
        on_chip(params), on_chip(srv._pools), None,
        sds((s,), jnp.int32), sds((s,), jnp.int32),
        (sds((s, srv._maxb), jnp.int32),),
        sds((s,), jnp.float32), sds((s, 2), jnp.uint32)).compile().as_text()
    for name in ("hpx_paged_sparse", "hpx_lightning_step"):
        assert name in text
    assert _copies_of(text, f"f32[{s},32,128,128]") == []
    assert _pool_ops(text, srv._pools[0][0]) == []
    assert [ln for ln in text.splitlines() if " copy(" in ln
            and "f32[1024,8,128]" in ln.split(" copy(")[0]] == []


def test_a_sparse_layers_prefill_chunk_builds_no_array_over_the_scratch(
        sds, monkeypatch):
    """`jit_chunk` at 512 rows over a scratch of 35,840: the chunk's
    attention is walked in blocks of rows, so no array holds heads x
    rows x smax scores (2.3 GB in float32 here); the largest the
    selection builds is heads x rows x smax / 16."""
    import re
    cfg, params, srv = _sala_server(monkeypatch)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    scratch = on_chip(jax.eval_shape(srv._fresh_scratch))
    compiled = srv._chunk_prog(512).lower(
        on_chip(params), scratch, sds((1, 512), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    smax, rows, heads = srv.smax, 512, _S_NQ
    sizes = [int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"\b(?:f32|bf16|pred|s32)\[([0-9,]+)\]",
                                    compiled.as_text())]
    assert max(sizes) <= heads * rows * (smax // 16)
    assert max(sizes) < heads * rows * smax // 8
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 30


# -- selective-scan layers (the Jamba2-3B cell) ---------------------------

# 256 slots; a Mamba layer's state 16 x 5120 float32 a slot (the channels
# on the minor axis) and a flat conv tail of 3 x 5120; chunks of 512 rows
# over the b=1 scratch; ONE K/V head of 128 under 20 query heads, pages
# of 64 rows
_M_SLOTS, _M_C, _M_N = 256, 5120, 16


def test_the_mamba_states_update_runs_in_place(sds):
    """`hpx_mamba_step` over the cell's state: read once, written once,
    the donated 84 MB of a layer neither copied nor re-laid; u, dt and
    y ride as [slots, C] rows, eight slots a grid step, with no copy
    into a one-row tiling."""
    from hpx_tpu.ops import mamba
    b, c, n = _M_SLOTS, _M_C, _M_N
    row, col = sds((b, c), jnp.float32), sds((b, n), jnp.float32)
    compiled = jax.jit(
        lambda u, dt, bm, cm, a, s: mamba.mamba_step(
            u, dt, bm, cm, a, s, kernel="pallas", interpret=False),
        donate_argnums=(5,)).lower(
        row, row, col, col, sds((n, c), jnp.float32),
        sds((b, n, c), jnp.float32)).compile()
    text = compiled.as_text()
    assert "hpx_mamba_step" in text
    assert _copies_of(text, f"f32[{b},{n},{c}]") == []
    assert _copies_of(text, f"f32[{b},{c}]") == []
    assert _copies_of(text, f"f32[{b},1,{c}]") == []
    assert compiled.memory_analysis().alias_size_in_bytes >= b * n * c * 4


@pytest.mark.parametrize("rows", [8, 512])
def test_the_mamba_scan_compiles_at_the_cells_widths(sds, rows):
    """`hpx_mamba_scan` over a chunk of the ladder's narrowest and
    widest width: a true scan over the rows with a channel block's
    state in registers, no temporary over rows x state."""
    from hpx_tpu.ops import mamba
    c, n = _M_C, _M_N
    row, col = sds((1, rows, c), jnp.float32), sds((1, rows, n), jnp.float32)
    compiled = jax.jit(
        lambda u, dt, bm, cm, a, s, v: mamba.mamba_chunk(
            u, dt, bm, cm, a, s, v, kernel="pallas", interpret=False),
        donate_argnums=(5,)).lower(
        row, row, col, col, sds((n, c), jnp.float32),
        sds((1, n, c), jnp.float32), sds((), jnp.int32)).compile()
    assert "hpx_mamba_scan" in compiled.as_text()
    # the lane-spread B and C columns, and nothing of rows x N x C
    assert compiled.memory_analysis().temp_size_in_bytes \
        < 4 * rows * 2 * n * 128 * 4 + (1 << 20)


def test_ssm_server_programs_copy_neither_state_nor_tail_nor_pool(
        sds, monkeypatch):
    """The server's own programs for a Mamba layer, an attention layer
    over ONE K/V head and a Mamba LAST layer of the cell's mixer widths
    (a narrow d_model), built from parameter SHAPES at 256 slots:
    `jit_step` holds one Pallas call a mixer, the state, the flat tail
    and the K/V pools donated and left where they lie; the splice
    writes a slot's row of the state and the tail in place; `jit_probe`
    behind the recurrent last layer reads ln and the tied head alone."""
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.models.transformer import TransformerConfig, init_params
    c, n, s = _M_C, _M_N, _M_SLOTS
    cfg = TransformerConfig(
        vocab=512, d_model=256, n_heads=20, head_dim=128, n_kv_heads=1,
        n_layers=3, d_ff=512, dtype=jnp.bfloat16, norm="rmsnorm",
        norm_eps=1e-6, mlp="swiglu", tied=True,
        layer_mixer=("mamba", "attn", "mamba"), mamba_d_inner=c,
        mamba_d_state=n, mamba_d_conv=4, mamba_dt_rank=160)
    params = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    srv = ContinuousServer(params, cfg, paged=True, slots=s, smax=1792,
                           block_size=64, prefill_chunk=512)
    assert srv._paged_kernel == "fused"
    assert srv._alloc.num_blocks == s * 28 + 1
    assert [a.shape for a in srv._pools[0]] == [(s, n, c), (s, 3 * c)]

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    text = srv._paged_step_prog().lower(
        on_chip(params), on_chip(srv._pools), None,
        sds((s,), jnp.int32), sds((s,), jnp.int32),
        (sds((s, srv._maxb), jnp.int32),),
        sds((s,), jnp.float32), sds((s, 2), jnp.uint32)).compile().as_text()
    for name in ("hpx_mamba_step", "hpx_paged_fused"):
        assert name in text
    assert _copies_of(text, f"f32[{s},{n},{c}]") == []
    assert _copies_of(text, f"bf16[{s},{3 * c}]") == []
    assert _copies_of(text, f"bf16[{s},3,{c}]") == []
    assert _pool_ops(text, srv._pools[1][0]) == []
    scratch = on_chip(jax.eval_shape(srv._fresh_scratch))
    text = srv._paged_splice_prog().lower(
        on_chip(srv._pools), None, scratch,
        (sds((srv._maxb,), jnp.int32),), sds((), jnp.int32)
    ).compile().as_text()
    assert _copies_of(text, f"f32[{s},{n},{c}]") == []
    assert _copies_of(text, f"bf16[{s},{3 * c}]") == []
    lane = (sds((s,), jnp.int32), sds((s,), jnp.float32),
            sds((s, 2), jnp.uint32), sds((), jnp.int32),
            sds((), jnp.float32), sds((2,), jnp.uint32))
    probe = srv._probe_prog().lower(
        on_chip(srv._tail_params), sds((1, 1, cfg.d_model), cfg.dtype),
        scratch[-1], sds((), jnp.int32), *lane).compile()
    assert "jit_probe" in probe.as_text().splitlines()[0]
    assert "dot" not in "".join(
        ln for ln in probe.as_text().splitlines() if "5120" in ln
        and (" dot(" in ln or " convolution(" in ln))
    chunk = srv._chunk_prog(512).lower(
        on_chip(params), scratch, sds((1, 512), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    assert "hpx_mamba_scan" in chunk.as_text()
    assert "jit_chunk" in chunk.as_text().splitlines()[0]


def test_two_grain_server_programs_walk_once_and_copy_no_pool(
        sds, monkeypatch):
    """The server's own programs for EVA layers at the cell's widths
    (32 K/V heads x 128, a group of ONE query head, windows of 2,048
    pooled every 16; two layers of the eight), built from parameter
    SHAPES at 24 slots: `jit_step` holds one `hpx_paged_fused` a layer
    over the slot's one run of rows and leaves the pools where they
    lie; the splice writes both grains and `jit_roll` a window's
    summaries in place; `jit_chunk` at 512 columns over the two-grain
    scratch compiles and keeps its temporaries under 1 GB."""
    import json
    from chipbench.drivers import serving_eva as drv
    from hpx_tpu.models import transformer as tfm
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.ops.eva import summary_rows
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root,
                           "chipbench/configs/evabyte-6.5b.json")) as f:
        conf = json.load(f)
    cfg = drv.build_cfg({**conf, "num_hidden_layers": 2})
    params = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    srv = ContinuousServer(params, cfg,
                           **{**conf["server"], "num_blocks": 80})
    s, nb = srv.slots, conf["server"]["num_blocks"]
    assert srv._paged_kernel == "fused" and srv._maxb == 50
    # a table entry is copied once for 16 of the 32 heads: two sets of
    # 16 heads' banks are the bytes one set of 32 is
    assert srv._walk_group() == (16, 32)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    pools = [tuple(sds((nb,) + p.shape[1:], p.dtype) for p in pl)
             for pl in srv._pools]
    pool = f"bf16[{nb},32,64,128]"
    text = srv._paged_step_prog().lower(
        on_chip(params), pools, None, sds((s,), jnp.int32),
        sds((s,), jnp.int32), (sds((s, srv._maxb), jnp.int32),),
        sds((s,), jnp.float32), sds((s, 2), jnp.uint32)).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") == 2
    assert "hpx_paged_fused" in text and _copies_of(text, pool) == []
    scratch = on_chip(jax.eval_shape(srv._fresh_scratch))
    assert [a.shape[1] for a in scratch[0]] == [2048, 2048, 1152, 1152]
    text = srv._paged_splice_prog().lower(
        pools, None, scratch,
        (sds((summary_rows(srv.smax, 16, 2048) // 64,), jnp.int32),
         sds((32,), jnp.int32)), sds((), jnp.int32)).compile().as_text()
    assert _copies_of(text, pool) == []
    roll = srv._eva_roll_prog().lower(
        pools, on_chip(srv._eva_params), sds((32,), jnp.int32),
        sds((2,), jnp.int32)).compile()
    assert "jit_roll" in roll.as_text().splitlines()[0]
    assert _copies_of(roll.as_text(), pool) == []
    chunk = srv._chunk_prog(512).lower(
        on_chip(params), scratch, sds((1, 512), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32)).compile()
    assert "jit_chunk" in chunk.as_text().splitlines()[0]
    assert chunk.memory_analysis().temp_size_in_bytes < 1e9


# -- the one-layer probe (PR 44): chunks hand back a hidden row ----------

# cell -> (driver under chipbench/drivers, the chunk's rows on the chip,
# server sizes cut to what neither program reads: pools, slots).
# StarCoder2-3B's 256 is the ridge rule's width there; DeepSeek-V2's
# cell STATES 256
_PROBE_CELLS = {
    "starcoder2-3b": ("serving", 256, dict(num_blocks=512)),
    "deepseek-v2": ("serving_latent", 256,
                    dict(num_blocks=2048, radix_budget_blocks=64, slots=2)),
}


def _nbytes(tree):
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def _cell_server(monkeypatch, cell, driver, rows, cut):
    """(cfg, parameter SHAPES, server) of a benchmark cell at its real
    widths and depth: the configuration's file through its driver's
    `build_cfg`, the server's sizes cut by `cut`, chunks of `rows`;
    `jax.default_backend` steered so that the kernels are the chip's."""
    import importlib
    import json
    from hpx_tpu.models import transformer as tfm
    from hpx_tpu.models.serving import ContinuousServer
    drv = importlib.import_module(f"chipbench.drivers.{driver}")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "chipbench/configs", cell + ".json")) as f:
        conf = json.load(f)
    cfg = drv.build_cfg(conf)
    params = jax.eval_shape(
        lambda: tfm.init_params(cfg, jax.random.PRNGKey(0)))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return cfg, params, ContinuousServer(
        params, cfg, **{**conf["server"], **cut, "prefill_chunk": rows})


@pytest.mark.parametrize("cell", sorted(_PROBE_CELLS))
def test_a_chunk_costs_what_it_cost_and_the_probe_reads_one_layer(
        sds, monkeypatch, cell):
    """`jit_chunk` and `jit_probe` at the cell's real size, built by the
    server from parameter SHAPES. The chunk, which now also returns the
    hidden row of its last real column, is the program it was (the
    parent's form: the same window, the scratch alone returned): FLOPs
    and argument bytes equal to 0.1 %, so the last layer's `wo` and FFN
    are still no arguments of it, and no array over the vocabulary but
    the embedding table. The probe's arguments are ONE layer's leaves,
    final ln, the head, one scratch entry and the per-slot vectors, and
    the head sees one row: no `[rows, vocab]` array in either."""
    import re
    from hpx_tpu.models import transformer as tfm
    driver, rows, cut = _PROBE_CELLS[cell]
    cfg, params, srv = _cell_server(monkeypatch, cell, driver, rows, cut)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)
    scratch = on_chip(jax.eval_shape(srv._fresh_scratch))
    chunk = (on_chip(params), scratch, sds((1, rows), jnp.int32),
             sds((), jnp.int32), sds((), jnp.int32))
    compiled = srv._chunk_prog(rows).lower(*chunk).compile()
    parents = jax.jit(
        lambda params, caches, toks, pos0, n: tfm._decode_window(
            params, caches, toks, pos0, cfg, need_logits=False,
            valid=n)[0], donate_argnums=(1,)).lower(*chunk).compile()

    def flops(c):
        cost = c.cost_analysis()
        return (cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"]

    def args(c):
        return c.memory_analysis().argument_size_in_bytes
    assert abs(flops(compiled) / flops(parents) - 1) < 1e-3
    assert abs(args(compiled) / args(parents) - 1) < 1e-3
    # and under the whole tree by the last layer's dropped leaves
    assert args(compiled) < _nbytes(params) + _nbytes(scratch)
    text = compiled.as_text()
    assert "jit_chunk" in text.splitlines()[0]      # the metrics' name
    over_vocab = rf"\b\w+\[((?:\d+,)*{cfg.vocab})\]"
    assert not re.findall(over_vocab, text)

    s = srv.slots
    tail = on_chip(srv._tail_params)
    lane = (sds((s,), jnp.int32), sds((s,), jnp.float32),
            sds((s, 2), jnp.uint32), sds((), jnp.int32),
            sds((), jnp.float32), sds((2,), jnp.uint32))
    probe = srv._probe_prog().lower(
        tail, sds((1, 1, cfg.d_model), cfg.dtype), scratch[-1],
        sds((), jnp.int32), *lane).compile()
    read = {k: v for k, v in srv._tail_params.items()
            if k != ("emb" if "head" in params else "head")}
    want = _nbytes(read) + _nbytes(scratch[-1]) + 2 * cfg.d_model
    assert want < _nbytes(params) / (cfg.n_layers / 2.5)
    assert want <= args(probe) < want + 4096 * (len(lane) + 2)
    text = probe.as_text()
    assert "jit_probe" in text.splitlines()[0]
    rows_over = set(re.findall(over_vocab, text))
    assert rows_over and all(
        int(np.prod([int(d) for d in dims.split(",")])) == cfg.vocab
        for dims in rows_over), rows_over


# cell -> (driver, the chunk's rows, the server cut to what no program
# reads, the operations `jit_step` / `jit_chunk` / `jit_probe` lower to
# at the cell's real widths and depth). The counts are PR 44's
# (91e4982), read by lowering that checkout and PR 45's side by side:
# a third recurrent kind, a bias on `short_conv` and two host counters
# left all fifteen programs as they were. A PR that changes one of
# these programs changes its number here, knowingly: PR 47 made
# DeepSeek-V2's 256-wide chunk walk each latent layer's scratch in two
# groups of 64 heads (2189 -> 2619: a second loop a layer and the
# join); its step and probe, and Kimi's 512-wide chunk of 32 heads (one
# group), stayed as they were.
_OLD_CELLS = {
    "starcoder2-3b": ("serving", 256, dict(num_blocks=512, slots=4),
                      (9438, 6410, 805)),
    "laguna-xs2": ("serving_mixed", 512, dict(num_blocks=1024, slots=4),
                   (2818, 1398, 985)),
    "kimi-linear-48b": ("serving_hybrid", 512,
                        dict(num_blocks=1024, slots=2), (10204, 11782, 1040)),
    "deepseek-v2": ("serving_latent", 256,
                    dict(num_blocks=2048, radix_budget_blocks=64, slots=2),
                    (3220, 2619, 1129)),
    "minicpm-sala": ("serving_sparse", 512, dict(num_blocks=2048, slots=2),
                     (3211, 2443, 1085)),
}


@pytest.mark.parametrize("cell", sorted(_OLD_CELLS))
def test_an_old_cells_programs_lower_to_the_operations_they_had(
        sds, monkeypatch, cell):
    """The step, chunk and probe programs of a cell the benchmark had
    before the selective-scan kind, LOWERED for the described chip (not
    compiled: ~5 s a cell) from parameter shapes at the cell's real
    widths: each holds the operations it held at the parent commit, so
    a new mixer kind costs the old cells no operand, no output and no
    operation (PR 41 and PR 43 were refused for their `setup_s`)."""
    import re
    driver, rows, cut, want = _OLD_CELLS[cell]
    cfg, params, srv = _cell_server(monkeypatch, cell, driver, rows, cut)

    def on_chip(tree):
        return jax.tree.map(lambda x: sds(x.shape, x.dtype), tree)

    def ops(lowered):
        return len(re.findall(
            r"^\s+(?:%\S+(?:, %\S+)* = )?\"?(?:stablehlo|func|chlo)\.",
            lowered.as_text(), re.M))
    s = srv.slots
    tables = (sds((s, srv._maxb), jnp.int32),)
    if srv._win:
        tables += (sds((s, srv._ring), jnp.int32),)
    lane = (sds((s,), jnp.int32), sds((s,), jnp.float32),
            sds((s, 2), jnp.uint32))
    step = srv._paged_step_prog().lower(
        on_chip(params), on_chip(srv._pools), None, lane[0], lane[0],
        tables, *lane[1:])
    scratch = on_chip(jax.eval_shape(srv._fresh_scratch))
    chunk = srv._chunk_prog(rows).lower(
        on_chip(params), scratch, sds((1, rows), jnp.int32),
        sds((), jnp.int32), sds((), jnp.int32))
    probe = srv._probe_prog().lower(
        on_chip(srv._tail_params), sds((1, 1, cfg.d_model), cfg.dtype),
        scratch[-1], sds((), jnp.int32), *lane, sds((), jnp.int32),
        sds((), jnp.float32), sds((2,), jnp.uint32))
    assert (ops(step), ops(chunk), ops(probe)) == want


def test_a_cells_warm_up_compiles_one_chunk_a_width_and_one_probe():
    """`chipbench`'s `Loop.warm()` (one tiny request a ladder width,
    one prompt of `prefill_chunk + widths[0] + 1`) meets every program
    of a cell: one chunk program a ladder width, ONE probe, step, splice
    and gather, the parent's count (no chunk carries a head or a pick,
    so none has a second variant); mixed traffic after it compiles
    nothing."""
    from chipbench.drivers.serving import Loop
    from hpx_tpu.models import transformer as tfm
    from hpx_tpu.models.serving import ContinuousServer
    from hpx_tpu.utils.compilemon import count_compiles
    cfg = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                                n_layers=2, d_ff=60)
    srv = ContinuousServer(tfm.init_params(cfg, jax.random.PRNGKey(0)), cfg,
                           slots=3, smax=96, paged=True, prefill_chunk=32)
    loop = Loop.__new__(Loop)
    loop.server = srv
    with count_compiles() as c:
        loop.warm()
    mine = [k for k in tfm._PROGRAMS if cfg in k[1:2]]
    widths = srv.prefill_buckets
    assert sorted(k[2] for k in mine if k[0] == "cb_chunk") == list(widths)
    assert sum(k[0] == "cb_probe" for k in mine) == 1
    assert int(c) == srv._prog_misses == len(widths) + 1 + 3
    r = np.random.RandomState(0)
    with count_compiles() as window:
        for plen in (3, 9, 31, 33, 50, 64, 70):
            srv.submit([int(t) for t in r.randint(1, 64, plen)], max_new=5)
        srv.run()
    assert int(window) == 0 and not srv.failed


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


def test_heat_part_kernel_streams_the_partition_once(sds):
    """What `stencil1d.heat_part` hands its arguments to on a TPU (here
    the backend is the CPU, so `heat_part` itself takes the XLA path), at
    the benchmark's partition: the kernel is there, no XLA fusion makes
    a partition-sized result beside it, and nothing partition-sized is
    materialised (one stream in, one out)."""
    import re
    n = 1 << 27
    assert stencil.takes_kernel(n, jnp.float32, "tpu")
    compiled = jax.jit(stencil.heat_step_halo).lower(
        sds((1,), jnp.float32), sds((n,), jnp.float32),
        sds((1,), jnp.float32), sds((), jnp.float32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    assert not re.search(rf"= f32\[{n}\]\S* fusion\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < (1 << 20)


def test_pallas_multistep_compiles(sds):
    u = sds((1 << 19,), jnp.float32)
    _kernel_text(
        lambda u: stencil.pallas_multistep(u, np.float32(0.1), 1024), u)
