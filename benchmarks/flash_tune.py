"""Flash-attention forward block-size tuning sweep (real TPU).

Sweeps (block_q, block_k) per shape class — S in {2k, 4k, 8k, 16k},
causal x non-causal at the bench head layout (N8 H128 bf16) — with the
same slope-timing discipline as bench.py, prints one JSON line per
measurement, and writes the winners to
hpx_tpu/ops/flash_blocks.json, which ops/attention_pallas.resolve_blocks
consults whenever callers don't pass blocks explicitly.

With --paged the sweep instead covers the PAGED DECODE knob grid —
cache block_size {8, 16, 32, 64} x kv_dtype {bf16, int8, fp8} x
kernel {gather, fused, fused_online} — on a serving-decode shape
(8 slots near a 2k horizon, N8 H128), and banks each kv_dtype's
winning block size (best across kernels) to
hpx_tpu/ops/paged_blocks.json keyed ``hd<head_dim>x<kv_dtype>``, which
`ops/attention_pallas.resolve_paged_block` (and through it
``hpx.cache.block_size=auto``) consults. An unknown kv_dtype string is
a hard error, never a silent fall-through to bf16 byte accounting.

Usage: python benchmarks/flash_tune.py [--quick] [--paged]
                                       [--positions P[,P...]
                                        [--heads [B x]QxKV] [--smax S]
                                        [--block N]]
  --quick: S in {2k, 4k} only and fewer samples (smoke/dev loops).
  --paged: tune the paged decode kernel instead of flash forward.
  --positions 15,511,2047: with --paged, no sweep and nothing banked:
    time the `fused` kernel (bf16, block 16, S 2048) once per listed
    position, every slot AT that position and the table's tail on one
    trash block as the server lays it out: 32 slots, 24 q / 2 kv heads
    (the StarCoder2-3B cell's shape, `--heads 32x24x2`). [--heads
    [B x]QxKV] [--smax S] give another cell's: 32x48x8 and 4864 (304
    entries) are Laguna's full layers, 24x32x32, 3200 and `--block 64`
    (50 entries of 64 rows) EvaByte's. Times are the DEVICE's
    (`paged_measure`), so a call under the host's dispatch latency reads
    as what it is.
"""

import functools
import json
import os
import sys
import time

# repo root (this file lives in benchmarks/), regardless of the cwd
sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from bench import slope_time  # noqa: E402 — one timing discipline


def measure(jax, jnp, flash, S, causal, bq, bk, samples=3):
    B, N, H = (2, 8, 128) if S <= 8192 else (1, 8, 128)
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(  # noqa: E731
        rng.standard_normal((B, S, N, H), np.float32), jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    f = jax.jit(functools.partial(flash, causal=causal, block_q=bq,
                                  block_k=bk))
    out = f(q, k, v)
    jax.block_until_ready(out)

    def chain(kk):
        qq = q
        t0 = time.perf_counter()
        for _ in range(kk):
            qq = f(qq, k, v)
        _ = float(qq[0, 0, 0, 0])
        return time.perf_counter() - t0

    pers = sorted(slope_time(chain, 4, 20) for _ in range(samples))
    per = pers[(samples - 1) // 2]     # median (odd) / faster-of-2
    flops = 4 * B * N * S * S * H * (0.5 if causal else 1.0)
    return flops / per / 1e12, (pers[-1] - pers[0]) / per


def _arg(name):
    if name in sys.argv:
        return sys.argv[sys.argv.index(name) + 1]
    return None


def _bank(table, blocks_file) -> int:
    """Merge `table` into the on-disk table atomically; returns total.
    Called after EVERY shape class: a sweep cut short (a chip call has
    a time limit) must not discard classes already tuned."""
    try:
        with open(blocks_file) as f:
            merged = json.load(f)
    except (OSError, ValueError):
        merged = {}
    merged.update(table)
    tmp = blocks_file + ".tmp"
    with open(tmp, "w") as f:
        json.dump(merged, f, indent=1, sort_keys=True)
    os.replace(tmp, blocks_file)
    return len(merged)


# Pool-row bytes per element by kv_dtype string. KeyError here is a
# BUG GUARD: an unrecognized dtype must fail the sweep, not silently
# get bf16 byte accounting (which would corrupt the banked winners).
_PAGED_ITEMSIZE = {"bf16": 2, "int8": 1, "fp8": 1}
_PAGED_KERNELS = ("gather", "fused", "fused_online")


def _paged_pools(jnp, S, bs, kvd, heads):
    """(q, K pool, V pool, K scales, V scales) of one shape, seeded.
    A cell's pools are gigabytes, seconds to draw: `paged_positions`
    draws them once for all its positions."""
    from hpx_tpu.ops.paged_attention import quantize_blocks
    (B, nq, nkv), H = heads, 128
    nb = B * (S // bs) + 1             # + a trash-style spare block
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((B, 1, nq, H), np.float32),
                    jnp.bfloat16)
    # pool layout: heads ahead of rows (ops/paged_attention)
    kp = jnp.asarray(rng.standard_normal((nb, nkv, bs, H), np.float32))
    vp = jnp.asarray(rng.standard_normal((nb, nkv, bs, H), np.float32))
    if kvd == "bf16":
        return q, kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16), \
            None, None
    pool_dt = jnp.int8 if kvd == "int8" else jnp.float8_e4m3fn
    kq, ks = quantize_blocks(kp, pool_dt)
    vq, vs = quantize_blocks(vp, pool_dt)
    return q, kq, vq, ks, vs


def _paged_case(jax, jnp, S, bs, kvd, kern, pos=None, heads=(8, 8, 8),
                pools=None):
    """One paged decode attention step at the serving shape, unjitted:
    (step(q, *operands), operands, q, HBM bytes one call has to read).
    The pools are OPERANDS: a jitted step that closes over them
    compiles gigabytes of constants into its program (minutes a
    position at a cell's shape). See `paged_step`."""
    from hpx_tpu.ops.attention_pallas import (fused_paged_attention,
                                              fused_paged_online_attention)
    from hpx_tpu.ops.paged_attention import gather_block_kv
    try:
        itemsize = _PAGED_ITEMSIZE[kvd]
    except KeyError:
        raise ValueError(
            f"flash_tune --paged: unknown kv_dtype {kvd!r} (expected one "
            f"of {sorted(_PAGED_ITEMSIZE)}) — refusing to fall back to "
            "bf16 byte accounting") from None
    if kern not in _PAGED_KERNELS:
        raise ValueError(
            f"flash_tune --paged: unknown kernel {kern!r} (expected one "
            f"of {_PAGED_KERNELS})")
    (B, nq, nkv), H = heads, 128
    maxb = S // bs
    q, kq, vq, ks, vs = pools or _paged_pools(jnp, S, bs, kvd, heads)
    pos = np.broadcast_to(
        np.asarray(S - 1 if pos is None else pos, np.int32), (B,))
    live = pos // bs + 1               # entries reached (S - 1: all)
    table = np.arange(1, B * maxb + 1, dtype=np.int32).reshape(B, maxb)
    table = jnp.asarray(
        np.where(np.arange(maxb)[None, :] < live[:, None], table, 0))
    pos = jnp.asarray(pos)
    if kern == "gather":
        g = nq // nkv

        def step(qq, kq, vq, table, pos, ks, vs):
            kc = gather_block_kv(kq, table, ks, qq.dtype)
            vc = gather_block_kv(vq, table, vs, qq.dtype)
            qg = qq.reshape(B, 1, nkv, g, H)
            s = jnp.einsum("bqngh,bknh->bngqk", qg, kc) / (H ** 0.5)
            live = jnp.arange(kc.shape[1])[None, :] <= pos[:, None]
            s = jnp.where(live[:, None, None, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1
                               ).astype(qq.dtype)
            return jnp.einsum("bngqk,bknh->bqngh", p, vc).reshape(
                B, 1, nq, H)
    else:
        fpa = (fused_paged_online_attention if kern == "fused_online"
               else fused_paged_attention)

        def step(qq, kq, vq, table, pos, ks, vs):
            return fpa(qq, kq, vq, table, pos, k_scale=ks, v_scale=vs)
    nlive = int(live.sum())                         # mapped entries
    hbm = 2 * nlive * bs * nkv * H * itemsize       # K + V pool reads
    if kvd in ("int8", "fp8"):
        hbm += 2 * nlive * nkv * 4                  # scale sidecars
    return step, (kq, vq, table, pos, ks, vs), q, hbm


def paged_step(jax, jnp, S, bs, kvd, kern, pos=None, heads=(8, 8, 8)):
    """Build one paged decode attention step at the serving shape:
    8 slots, every table fully mapped to DISTINCT pool blocks at a
    near-S horizon (the steady-state worst case — block-size effects
    show up as grid/tiling overhead, not masked work). `kern` picks
    the formulation: gather (XLA oracle), fused (bitwise Pallas), or
    fused_online (O(block)-scratch online softmax). `pos` puts every
    slot at that position instead (an int, or one per slot) and lays
    the table out as the server does: the entries a slot's position
    has reached map to distinct blocks, the tail to ONE trash block
    (block 0). `heads` is (slots, q heads, kv heads). Returns (jitted
    step of q alone, its q operand, HBM bytes one call has to read)."""
    step, operands, q, hbm = _paged_case(jax, jnp, S, bs, kvd, kern,
                                         pos=pos, heads=heads)
    f = jax.jit(step)
    return (lambda qq: f(qq, *operands)), q, hbm


def paged_measure(jax, jnp, S, bs, kvd, kern, samples=3, **layout):
    """Time `paged_step` ON THE DEVICE. Returns (HBM-read GB/s, us per
    call, spread).

    `n` calls run inside ONE jitted `lax.fori_loop` whose carry feeds q
    (no call can be dropped or overlap the next), so a timing costs one
    dispatch whatever `n`, and the slope over two `n` is the device's
    time a call. One dispatch a call would put the host's 0.2-0.3 ms a
    dispatch under every reading as a floor, and the cells' calls take
    0.1 ms."""
    step, operands, q, hbm = _paged_case(jax, jnp, S, bs, kvd, kern,
                                         **layout)

    @jax.jit
    def loop(qq, n, *ops):
        return jax.lax.fori_loop(
            0, n, lambda _, x: step(x, *ops).astype(qq.dtype), qq)

    def chain(kk):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(q, kk, *operands))
        return time.perf_counter() - t0

    chain(8)                           # compiles (n is data: once)
    # enough calls that the slope stands on >= 0.25 s of device work
    k2 = 8 + min(max(int(0.25 * 64 / chain(64)), 64), 4096)
    pers = sorted(slope_time(chain, 8, k2) for _ in range(samples))
    per = pers[(samples - 1) // 2]
    return hbm / per / 1e9, per * 1e6, (pers[-1] - pers[0]) / per


def paged_positions(jax, jnp, positions, heads, S, bs=16) -> int:
    """`--positions`: one line per position, nothing banked."""
    kvd, kern = "bf16", "fused"
    pools = _paged_pools(jnp, S, bs, kvd, heads)
    for p in positions:
        gbs, us, spread = paged_measure(jax, jnp, S, bs, kvd, kern,
                                        pos=p, heads=heads, pools=pools)
        print(json.dumps({"S": S, "block_size": bs, "kv_dtype": kvd,
                          "kernel": kern, "slots": heads[0],
                          "q_heads": heads[1], "kv_heads": heads[2],
                          "position": p, "us_per_step": round(us, 1),
                          "hbm_gb_per_s": round(gbs, 1),
                          "spread": round(spread, 3)}), flush=True)
    return 0


def paged_main(jax, jnp, quick: bool) -> int:
    from hpx_tpu.ops.attention_pallas import _PAGED_BLOCKS_FILE
    S = 1024 if quick else 2048
    samples = 2 if quick else 3
    kernels = ("fused", "fused_online") if quick else _PAGED_KERNELS
    H = 128
    table = {}
    for kvd in ("bf16", "int8", "fp8"):
        best = None                    # (us, block_size, kernel)
        for kern in kernels:
            for bs in (8, 16, 32, 64):
                try:
                    gbs, us, spread = paged_measure(jax, jnp, S, bs,
                                                    kvd, kern,
                                                    samples=samples)
                except Exception as e:  # noqa: BLE001 — eg VMEM OOM
                    print(json.dumps({"S": S, "kv_dtype": kvd,
                                      "kernel": kern, "block_size": bs,
                                      "error": str(e)[:120]}),
                          flush=True)
                    continue
                print(json.dumps({"S": S, "kv_dtype": kvd,
                                  "kernel": kern, "block_size": bs,
                                  "hbm_gb_per_s": round(gbs, 1),
                                  "us_per_step": round(us, 1),
                                  "spread": round(spread, 3)}),
                      flush=True)
                if best is None or us < best[0]:
                    best = (us, bs, kern)
        if best:
            table[f"hd{H}x{kvd}"] = best[1]
            total = _bank(table, _PAGED_BLOCKS_FILE)
            print(json.dumps({"kv_dtype": kvd, "winner": best[1],
                              "kernel": best[2],
                              "us_per_step": round(best[0], 1),
                              "banked": total}), flush=True)
    print(json.dumps({"wrote": _PAGED_BLOCKS_FILE, "new": len(table)}))
    return 0


def main() -> int:
    quick = "--quick" in sys.argv
    # single-class mode: tune ONE (S, causal) per invocation, e.g.
    # --shape 4096 --causal 1 (the bench shape)
    shape_only = _arg("--shape")
    causal_only = _arg("--causal")
    import jax
    import jax.numpy as jnp
    from hpx_tpu.ops.attention_pallas import _BLOCKS_FILE, flash_attention
    from hpx_tpu.utils.compile_cache import enable_compile_cache

    if jax.default_backend() != "tpu":
        print(json.dumps({"error": "flash_tune needs a real TPU; "
                          f"backend={jax.default_backend()}"}))
        return 1
    enable_compile_cache()

    if "--paged" in sys.argv and _arg("--positions"):
        heads = tuple(map(int, (_arg("--heads") or "24x2").split("x")))
        return paged_positions(
            jax, jnp, [int(p) for p in _arg("--positions").split(",")],
            (32,) * (3 - len(heads)) + heads, int(_arg("--smax") or 2048),
            int(_arg("--block") or 16))
    if "--paged" in sys.argv:
        return paged_main(jax, jnp, quick)

    seqs = (2048, 4096) if quick else (2048, 4096, 8192, 16384)
    if shape_only:
        seqs = (int(shape_only),)
    causals = (True, False) if causal_only is None else \
        (bool(int(causal_only)),)
    cand = (256, 512, 1024, 2048)
    samples = 2 if quick else 3
    table = {}
    for S in seqs:
        for causal in causals:
            best = None
            for bq in cand:
                if bq > S:
                    continue
                for bk in cand:
                    if bk > S:
                        continue
                    try:
                        tf, spread = measure(jax, jnp, flash_attention,
                                             S, causal, bq, bk,
                                             samples=samples)
                    except Exception as e:  # noqa: BLE001 — eg VMEM OOM
                        print(json.dumps({"S": S, "causal": causal,
                                          "bq": bq, "bk": bk,
                                          "error": str(e)[:120]}),
                              flush=True)
                        continue
                    print(json.dumps({"S": S, "causal": causal,
                                      "bq": bq, "bk": bk,
                                      "tflops": round(tf, 1),
                                      "spread": round(spread, 3)}),
                          flush=True)
                    if best is None or tf > best[0]:
                        best = (tf, bq, bk)
            if best:
                table[f"{S}x{S}x{int(causal)}"] = [best[1], best[2]]
                total = _bank(table, _BLOCKS_FILE)
                print(json.dumps({"S": S, "causal": causal,
                                  "winner": best[1:],
                                  "tflops": round(best[0], 1),
                                  "banked": total}),
                      flush=True)

    print(json.dumps({"wrote": _BLOCKS_FILE, "new": len(table)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
