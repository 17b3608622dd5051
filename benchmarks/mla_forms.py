"""Latent attention's open choices, timed on the chip (device clock).

Two questions a cell cannot split, one line of JSON a reading:

1. `hpx_mla_paged` alone (the blocked walk of ops/attention_pallas.py)
   at a cell's shape, every slot at one position: how its time follows
   the live length, under DeepSeek-V2's 128 heads (64 slots, a table of
   1,576 entries) and under Kimi-Linear's 32 (48 slots, 264 entries).
   With `--entries N[,N...]` at other sizes of a fold
   (`LATENT_WALK_ENTRIES` table entries a buffer), with `--block 64`
   over pools of another block size (the same rows), with `--run
   N[,N...]` at other sizes of a coalesced copy (`LATENT_RUN` table
   entries ONE descriptor carries where they name neighbours; 1 = a
   copy an entry, the kernel up to PR 50), and with `--table` over
   tables of another shape: `run` (every slot's blocks consecutive,
   the default), `shuffled` (a permutation of the pool: no group is a
   run) or `mixed:<pct>` (<pct> % of the aligned groups of 32 entries
   stay where `run` has them, the other entries are shuffled among
   themselves); `run_pct` is the share of the live entries that a
   coalesced copy carried (a slot's first and last folds are single
   copies whatever its table holds).
2. A prefill chunk of W queries over `--rows` cached rows: the ABSORBED
   form the program keeps (`transformer._latent_attention`: every head
   scores the 640-wide row and weighs its first 512 columns) against
   the EXPANDED form (K and V of every head materialised from the
   latent a block, then 192-wide scores and 128-wide values), both
   walked in the same blocks under the same online softmax. The
   absorbed form walks the scratch a GROUP of heads at a time,
   `LATENT_PAIRS_A_GROUP` (head, query) pairs at most: `--pairs
   N[,N...]` times it at other sizes of a group, 0 = one walk of the
   whole chunk (what the program did up to PR 46: past 16,384 pairs
   its float32 accumulator leaves the chip's fast memory and a
   256-wide chunk cost three 128-wide ones), and `row_groups` is the
   same chunk cut by query rows instead (slower: PERF.md section 6,
   PR 47). `--heads 32` is Kimi-Linear's shape (a scratch of 4,224
   rows; give `--rows 3584 --widths 512`).

Times as benchmarks/flash_tune.py `paged_measure` takes them: `n` calls
inside ONE jitted loop, the slope over two `n`. Exits non-zero without
a TPU.

Usage: python benchmarks/mla_forms.py [--kernel] [--chunk]
           [--entries 16,32,64] [--block 16] [--run 1,4,8,32]
           [--table run|shuffled|mixed:<pct>]
           [--positions 1535,15231] [--rows 15360] [--widths 128,256]
           [--pairs 0,4096,8192,16384,32768] [--heads 128]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))

from bench import slope_time  # noqa: E402 — one timing discipline

SHAPES = {"deepseek-v2": (64, 128, 1576, (1023, 8191, 15231, 25215)),
          "kimi-linear": (48, 32, 264, (511, 1535, 4223))}
ROW, RANK, BS = 640, 512, 16
CHUNK_SMAX = {128: 25216, 32: 4224}     # heads -> the cell's scratch rows


def device_us(jax, step, x, operands, samples=3):
    @jax.jit
    def loop(xx, n, *ops):
        return jax.lax.fori_loop(
            0, n, lambda _, y: step(y, *ops).astype(xx.dtype), xx)

    def chain(k):
        t0 = time.perf_counter()
        jax.block_until_ready(loop(x, k, *operands))
        return time.perf_counter() - t0
    chain(4)
    k2 = 4 + min(max(int(0.25 * 16 / chain(16)), 8), 2048)
    pers = sorted(slope_time(chain, 4, k2) for _ in range(samples))
    return pers[(samples - 1) // 2] * 1e6


def make_table(kind: str, b: int, maxb: int):
    """The [b, maxb] table of `--table kind` over a pool of b * maxb + 1
    blocks (block 0 is no slot's), the same at every call."""
    import numpy as np
    table = 1 + np.arange(b * maxb, dtype=np.int32)
    rng = np.random.default_rng(0)
    if kind == "shuffled":
        table = rng.permutation(table)
    elif kind.startswith("mixed:"):
        grain = 32                      # a fold of the kept size, a row's
        first = (np.arange(b)[:, None] * maxb
                 + np.arange(maxb // grain)[None] * grain).ravel()
        moved = rng.permutation(first)[
            :round(len(first) * (1 - float(kind[6:]) / 100))]
        at = (moved[:, None] + np.arange(grain)).ravel()
        table[at] = rng.permutation(table[at])
    elif kind != "run":
        raise SystemExit(f"mla_forms: unknown --table {kind!r}")
    return table.reshape(b, maxb)


def kernel_lines(jax, jnp, entries, block=BS, positions=None, runs=(),
                 kind="run"):
    from hpx_tpu.ops import attention_pallas as ap
    for name, (b, h, maxb16, own) in SHAPES.items():
        maxb = maxb16 * BS // block
        nb = b * maxb + 1
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        pool = jax.random.normal(ks[0], (nb, 1, block, ROW), jnp.bfloat16)
        q = (jax.random.normal(ks[1], (b, h, ROW)) * 0.3).astype(
            jnp.bfloat16)
        table_np = make_table(kind, b, maxb)
        table = jnp.asarray(table_np)
        for fold, run in ((f, r) for f in entries
                          for r in runs or (ap.LATENT_RUN,)):
            ap.LATENT_WALK_ENTRIES, ap.LATENT_RUN = fold, run
            fold, run = ap.latent_walk_sizes(maxb)  # what the launch takes
            for p in positions or own:
                if p >= maxb * block:
                    continue
                pos = jnp.full((b,), p, jnp.int32)

                def step(qq, pool, table, pos):
                    o = ap.fused_latent_attention(
                        qq, pool, table, pos, rank=RANK, scale=0.1)
                    return jnp.pad(o, ((0, 0), (0, 0), (0, ROW - RANK)))
                us = device_us(jax, step, q, (pool, table, pos))
                rows = b * (p + 1)
                live = p // block + 1
                print(json.dumps({
                    "what": "hpx_mla_paged", "shape": name, "slots": b,
                    "heads": h, "position": p, "block_size": block,
                    "fold_entries": fold, "run_entries": run,
                    "table": kind, "run_pct": round(
                        100 * ap.latent_entries_coalesced(
                            table_np, live, fold, run).sum()
                        / (b * live), 1),
                    "us_per_call": round(us, 1),
                    "live_gb_per_s": round(rows * 1152 / us / 1e3, 1),
                    "live_tflop_per_s": round(
                        rows * h * 2 * 1088 / us / 1e6, 1)}), flush=True)


def expanded_chunk(jax, jnp, blk):
    """The expanded form of a chunk, blocked like `_latent_attention`:
    q [1, W, H, dn + dr] against K = [lat W_uk ; k^R], V = lat W_uv."""
    def attend(q, lat, wuk, wuv, qpos, scale):
        b, nq, h, _ = q.shape
        dn, dv = wuk.shape[-1], wuv.shape[-1]
        n_blk = jnp.max(qpos) // blk + 1

        def body(j, carry):
            m, l, acc = carry
            rows = jax.lax.dynamic_slice_in_dim(lat, j * blk, blk, axis=1)
            c, kr = rows[..., :RANK], rows[..., RANK:RANK + q.shape[-1] - dn]
            k = jnp.einsum("bkr,rhn->bkhn", c, wuk)
            v = jnp.einsum("bkr,rhv->bkhv", c, wuv)
            s = (jnp.einsum("bqhn,bkhn->bhqk", q[..., :dn], k,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bqhd,bkd->bhqk", q[..., dn:], kr,
                              preferred_element_type=jnp.float32)) * scale
            kpos = j * blk + jnp.arange(blk)
            s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, -1))
            p = jnp.exp(s - m_new[..., None])
            fade = jnp.exp(m - m_new)
            acc = acc * fade[..., None] + jnp.einsum(
                "bhqk,bkhv->bhqv", p.astype(q.dtype), v,
                preferred_element_type=jnp.float32)
            return m_new, l * fade + jnp.sum(p, -1), acc
        m, l, acc = jax.lax.fori_loop(
            0, n_blk, body,
            (jnp.full((b, h, nq), -jnp.inf, jnp.float32),
             jnp.zeros((b, h, nq), jnp.float32),
             jnp.zeros((b, h, nq, dv), jnp.float32)))
        return (acc / l[..., None]).astype(q.dtype)
    return attend


def chunk_lines(jax, jnp, rows, widths, pairs, h=128):
    from hpx_tpu.models import transformer as tfm
    dn, dr, dv = 128, 64, 128
    smax = CHUNK_SMAX[h]
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    lat = jax.random.normal(ks[0], (1, smax, ROW), jnp.bfloat16)
    wuk = (jax.random.normal(ks[1], (RANK, h, dn)) * RANK ** -0.5).astype(
        jnp.bfloat16)
    wuv = (jax.random.normal(ks[2], (RANK, h, dv)) * RANK ** -0.5).astype(
        jnp.bfloat16)
    blk = tfm.LATENT_ROWS_A_BLOCK
    kept = tfm.LATENT_PAIRS_A_GROUP
    expanded = expanded_chunk(jax, jnp, blk)

    def absorbed_by(row_groups):
        def absorbed(qq, lat, wuk, wuv, qpos):
            qa = jnp.einsum("bqhn,rhn->bqhr", qq[..., :dn], wuk)
            qf = jnp.concatenate(
                [qa, qq[..., dn:],
                 jnp.zeros(qa.shape[:-1] + (ROW - RANK - dr,), qq.dtype)], -1)
            rs = qq.shape[1] // row_groups
            o = jnp.concatenate(
                [tfm._latent_attention(qf[:, i:i + rs], lat, qpos[i:i + rs],
                                       RANK, 0.1)
                 for i in range(0, qq.shape[1], rs)], 1)
            o = jnp.einsum("bqhr,rhv->bqhv", o, wuv)
            return jnp.pad(o, ((0, 0),) * 3 + ((0, dn + dr - dv),))
        return absorbed

    def expand(qq, lat, wuk, wuv, qpos):
        o = expanded(qq, lat, wuk, wuv, qpos, 0.1)
        return jnp.pad(jnp.moveaxis(o, 1, 2),
                       ((0, 0),) * 3 + ((0, dn + dr - dv),))
    for w in widths:
        qpos = rows + jnp.arange(w)
        q = (jax.random.normal(ks[3], (1, w, h, dn + dr)) * 0.3).astype(
            jnp.bfloat16)
        line = {"what": "prefill_chunk_attention", "width": w, "rows": rows,
                "block_rows": blk, "heads": h}
        # past the kept constant also the same chunk cut by ROWS (one
        # walk a group of rows: the constant is out of the way)
        forms = [(p, 1) for p in pairs] + [
            (0, rg) for rg in (2, 4) if w * h > kept]
        timed = {}                      # (head groups, row groups) -> us
        for p, rg in forms:
            tfm.LATENT_PAIRS_A_GROUP = p or w * h
            hg = tfm.latent_groups(1, w // rg, h)
            if (hg, rg) not in timed:
                timed[hg, rg] = device_us(jax, absorbed_by(rg), q,
                                          (lat, wuk, wuv, qpos))
            print(json.dumps({**line, "form": "absorbed",
                              "pairs_a_group": p, "kept": p == kept,
                              "groups": hg, "row_groups": rg,
                              "us_per_layer": round(timed[hg, rg], 1)}),
                  flush=True)
        tfm.LATENT_PAIRS_A_GROUP = kept
        us = device_us(jax, expand, q, (lat, wuk, wuv, qpos))
        print(json.dumps({**line, "form": "expanded",
                          "us_per_layer": round(us, 1)}), flush=True)


def main() -> int:
    ap_ = argparse.ArgumentParser()
    ap_.add_argument("--kernel", action="store_true")
    ap_.add_argument("--chunk", action="store_true")
    ap_.add_argument("--entries", default="32")
    ap_.add_argument("--block", type=int, default=BS)
    ap_.add_argument("--run", default="",
                     help="entries a coalesced copy carries; 1 = a copy "
                          "an entry (default: the kernel's constant)")
    ap_.add_argument("--table", default="run",
                     help="run | shuffled | mixed:<pct>")
    ap_.add_argument("--positions", default="")
    ap_.add_argument("--rows", type=int, default=15360)
    ap_.add_argument("--widths", default="128,256")
    ap_.add_argument("--pairs", default="",
                     help="sizes of a group; 0 = one walk (default: 0 "
                          "and the program's constant)")
    ap_.add_argument("--heads", type=int, default=128,
                     choices=sorted(CHUNK_SMAX))
    args = ap_.parse_args()
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"mla_forms: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    print(json.dumps({"device": dev.device_kind}), flush=True)
    both = not (args.kernel or args.chunk)
    if args.kernel or both:
        kernel_lines(jax, jnp, [int(e) for e in args.entries.split(",")],
                     args.block,
                     [int(p) for p in args.positions.split(",") if p],
                     [int(r) for r in args.run.split(",") if r],
                     args.table)
    if args.chunk or both:
        from hpx_tpu.models.transformer import LATENT_PAIRS_A_GROUP
        chunk_lines(jax, jnp, args.rows,
                    [int(w) for w in args.widths.split(",")],
                    [int(p) for p in args.pairs.split(",") if p]
                    or [0, LATENT_PAIRS_A_GROUP], args.heads)
    return 0


if __name__ == "__main__":
    sys.exit(main())
