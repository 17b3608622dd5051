"""`hpx_mla_paged` copies an aligned group of table entries that name
NEIGHBOURS in one descriptor (`attention_pallas.LATENT_RUN`), any other
group an entry at a time: the same rows at the same buffer offsets, so
the output is the single-copy form's TO THE BIT whatever the table
holds. Interpret mode, at DeepSeek-V2's 128 heads and Kimi-Linear's 32,
in groups of half a fold and of a whole fold (the kept size), against
the gather oracle and against the kernel at `LATENT_RUN` 1; and
the host's `latent_run_pct`, which reads the same rule off the same
tables."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.models.transformer import TransformerConfig, init_params
from hpx_tpu.ops import attention_pallas as ap
from hpx_tpu.ops import paged_attention as pa
from hpx_tpu.svc import performance_counters as pc

FOLD, BS, MAXB = 8, 16, 24              # three folds of 8 entries
RUNS = (4, 8)                           # two groups a fold, and one
RANK, ROPE = 128, 64
ROW = 256


def _ids(kind: str, base: int) -> np.ndarray:
    """A slot's MAXB block ids from `base` on, by the table's shape."""
    run = base + np.arange(MAXB)
    if kind == "run":
        return run
    if kind == "permuted":              # no entry's successor is its
        out = run.reshape(2, -1).T.ravel()[::-1]      # id's neighbour
        assert not (np.diff(out) == 1).any()
        return out
    if kind.startswith("broken@"):      # every group of 4 jumps at an
        k = int(kind[-1])               # offset (and so every larger one)
        out = run.reshape(-1, 4).copy()
        out[:, k:] = out[::-1, k:]
        assert not any(ap.latent_groups_coalesced(out.ravel(), r).any()
                       for r in RUNS)
        return out.ravel()
    raise ValueError(kind)


# (table's shape, live entries): one fold, two folds, a last fold with a
# tail of whole groups and of single entries; a run that ends at the
# live length, one entry short of it and one past it; a live length
# under one group
CASES = [(kind, n) for kind in ("run", "permuted") for n in
         (8, 16, 19, 22, 24)] \
    + [(f"broken@{k}", n) for k in range(1, 4) for n in (13, 24)] \
    + [("run", n) for n in (11, 12, 13, 1, 2, 3)] \
    + [("last", 20)]
NB = 1 + len(CASES) * MAXB              # block 0: the trash block


def _inputs(h):
    """One slot a case: its blocks are its own, rows past its position
    and the trash block hold NaN, entries past the live length name the
    trash block. The LAST slot's live run ends at the pool's last
    block."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    b = len(CASES)
    table = np.zeros((b, MAXB), np.int32)
    pos = np.zeros(b, np.int32)
    for i, (kind, n) in enumerate(CASES):
        base = 1 + i * MAXB
        if kind == "last":          # the last group ends the pool
            ids = NB - n + np.arange(MAXB)
        else:
            ids = _ids(kind, base)
        table[i, :n] = ids[:n]
        pos[i] = (n - 1) * BS + (5, 15, 0)[i % 3]
    assert table[-1, CASES[-1][1] - 1] == NB - 1
    pool = jax.random.normal(ks[0], (NB, 1, BS, ROW)).at[..., RANK + ROPE:] \
        .set(0.0)
    rows = np.arange(MAXB * BS)[None, :] > pos[:, None]
    dead = jnp.asarray(rows.reshape(b, MAXB, 1, BS, 1))
    live_tab = jnp.asarray(np.where(table > 0, table, NB))  # drop trash
    pool = pool.at[live_tab].set(
        jnp.where(dead, jnp.nan, pool[jnp.asarray(table)]), mode="drop")
    pool = pool.at[0].set(jnp.nan)
    q = jnp.pad(jax.random.normal(ks[1], (b, h, RANK + ROPE)) * 0.3,
                ((0, 0), (0, 0), (0, ROW - RANK - ROPE)))
    new = jnp.pad(jax.random.normal(ks[2], (b, RANK + ROPE)),
                  ((0, 0), (0, ROW - RANK - ROPE)))
    return q, new, pool, jnp.asarray(table), jnp.asarray(pos)


def _step(mp, args, run: int, fused: bool) -> np.ndarray:
    """One decode step over every case, traced under `LATENT_RUN` =
    `run` (a module constant the launch reads when it is traced)."""
    mp.setattr(ap, "LATENT_RUN", run)
    assert ap.latent_walk_sizes(MAXB) == (FOLD, run)
    return np.asarray(jax.jit(lambda *a: pa.paged_latent_attention(
        *a, rank=RANK, scale=0.1, fused=fused)[0])(*args))


@pytest.fixture(scope="module", params=[128, 32],
                ids=["deepseek-v2", "kimi"])
def forms(request):
    """The forms of one decode step over every case at once: the
    gather oracle, the kernel a copy an entry (`LATENT_RUN` 1, the
    kernel up to PR 50) and the kernel in groups of each of `RUNS`."""
    args = _inputs(request.param)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ap, "LATENT_WALK_ENTRIES", FOLD)
        out = {"gather": _step(mp, args, 1, False),
               "single": _step(mp, args, 1, True)}
        out.update({run: _step(mp, args, run, True) for run in RUNS})
    return out, np.asarray(args[3]), np.asarray(args[4])


@pytest.mark.parametrize("run", RUNS)
@pytest.mark.parametrize("case", range(len(CASES)),
                         ids=[f"{k}-{n}" for k, n in CASES])
def test_grouped_copies_give_the_single_copy_forms_bits(forms, case, run):
    out, table, pos = forms
    kind, n = CASES[case]
    assert pos[case] // BS + 1 == n
    # the case is what its name says: how many entries a coalesced
    # copy carries (the folds between the slot's first and last)
    want = max(-(-n // FOLD) - 2, 0) * FOLD \
        if kind in ("run", "last") else 0
    assert ap.latent_entries_coalesced(table, pos // BS + 1, FOLD,
                                       run)[case] == want
    assert np.isfinite(out[run][case]).all()
    np.testing.assert_array_equal(out[run][case], out["single"][case])
    np.testing.assert_allclose(out[run][case], out["gather"][case],
                               atol=2e-5, rtol=0)


def test_a_fold_that_is_no_whole_number_of_groups_copies_singly(
        monkeypatch):
    # the two cells' tables: a whole fold a copy
    assert ap.latent_walk_sizes(1576) == ap.latent_walk_sizes(264) \
        == (ap.LATENT_WALK_ENTRIES, ap.LATENT_RUN) == (32, 32)
    assert ap.latent_walk_sizes(24) == (24, 1)  # a table under a fold
    monkeypatch.setattr(ap, "LATENT_RUN", 4)
    monkeypatch.setattr(ap, "LATENT_WALK_ENTRIES", 6)
    assert ap.latent_walk_sizes(24) == (6, 1)
    assert ap.latent_walk_sizes(4) == (4, 4)    # the table's width
    table = np.arange(1, 25)[None]
    assert ap.latent_entries_coalesced(table, [24], 8, 1)[0] == 0
    # folds of 8: one whole fold between the first and the last
    assert ap.latent_entries_coalesced(table, [23], 8, 4)[0] == 8
    assert ap.latent_entries_coalesced(table, [24], 8, 4)[0] == 8
    assert ap.latent_entries_coalesced(table, [16], 8, 4)[0] == 0


# -- the server's counter ---------------------------------------------------

@pytest.fixture(scope="module")
def latent_toy():
    cfg = TransformerConfig(
        vocab=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        norm="rmsnorm", mlp="swiglu", tied=False,
        layer_mixer=("mla", "mla"), mla_rank=RANK, mla_nope_dim=16,
        mla_rope_dim=ROPE, mla_v_dim=16)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _server(latent_toy, kernel="fused"):
    cfg, params = latent_toy
    return ContinuousServer(params, cfg, paged=True, slots=2, smax=1024,
                            block_size=4, prefill_chunk=64,
                            prefix_reuse=False, paged_kernel=kernel)


def test_a_long_prompt_on_a_fresh_allocator_is_one_run(latent_toy):
    """A prompt's blocks are allocated in one loop from ascending ids:
    its table is a run, and every fold between the slot's first and
    its last is one copy (`latent_run_pct`: 6 folds of the 8 a table
    of 251 entries has); two slots that GROW together take the
    allocator's ids in turn, and the folds they grow into are no
    runs."""
    srv = _server(latent_toy)
    assert ap.latent_walk_sizes(srv._maxb) == (32, 32)
    assert srv.hbm_read_stats()["latent_run_pct"] == 0.0     # nothing live
    srv.submit(list(range(1, 64)) * 15 + [7] * 55, max_new=3)  # 1,000
    while srv.step():
        live = srv.live_positions()
        if live:
            st = srv.hbm_read_stats()
            n = live[0] // 4 + 1                    # 251 entries
            assert st["latent_run_pct"] == pytest.approx(
                100.0 * 6 * 32 / n)
    recs = [r for r in srv.step_accounts() if r.latent_entries]
    assert recs and all(r.latent_coalesced == 6 * 32 for r in recs)
    assert all(r.latent_run_pct > 76.0 for r in recs)
    cs = srv.cache_stats()
    assert cs["latent_entries_walked"] == sum(
        r.latent_entries for r in srv.step_accounts())
    assert cs["latent_entries_coalesced"] == sum(
        r.latent_coalesced for r in srv.step_accounts())
    assert "block_size_source" in cs and "latent_run_pct" in cs
    for name, want in (("latent/run-pct", cs["latent_run_pct"]),
                       ("latent/entries-walked",
                        cs["latent_entries_walked"]),
                       ("latent/entries-coalesced",
                        cs["latent_entries_coalesced"])):
        assert pc.query_counter(pc.counter_name(
            "cache", name, srv.counter_instance)).value == want
    # ... two requests whose blocks interleave as they decode (on an
    # allocator of their own: a free list that has been through a
    # request hands its ids out in another order)
    srv = _server(latent_toy)
    for seed in (1, 2):
        srv.submit([seed] * 258, max_new=250)
    seen = []
    while srv.step():
        if len(srv.live_positions()) == 2:
            seen.append(srv.hbm_read_stats()["latent_run_pct"])
    # 258 prompt tokens a slot = 65 blocks, a run: the second fold is
    # one copy (32 of 66 entries); 62 more blocks a slot whose ids
    # alternate between the two: the third fold is no run, and a slot
    # ends on 32 of ~127 entries
    assert seen[0] == pytest.approx(48.5, abs=1.0)
    assert seen[-1] == pytest.approx(25.4, abs=1.0)
    assert all(a >= b - 1e-9 for a, b in zip(seen, seen[1:]))
    last = [r for r in srv.step_accounts() if r.live == 2][-1]
    assert last.latent_run_pct == pytest.approx(25.4, abs=1.0)


def test_the_gather_form_coalesces_nothing(latent_toy):
    srv = _server(latent_toy, "gather")
    srv.submit(list(range(1, 64)) * 5, max_new=2)
    while srv.step():
        assert srv.hbm_read_stats()["latent_run_pct"] == 0.0
    assert srv.cache_stats()["latent_entries_walked"] == 0
