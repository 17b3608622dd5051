"""Speculative decoding in ContinuousServer: the draft + window-verify
path must be BYTE-IDENTICAL to both plain generate() and the
non-speculative server — over blocks a verify window fits in and blocks
it straddles, greedy and sampled, for every draft source — because
acceptance compares draft tokens against the EXACT token the sequential
step would have picked (same `_pick_row` contract, same fold_in key
schedule). Throughput may vary with draft quality; tokens never do.

Also pins the compile story: verify programs ride the prefill bucket
ladder, so a spec workload builds O(buckets) programs, not O(distinct
window widths).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.utils.compilemon import count_compiles

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=64)
# a real (smaller) draft checkpoint over the same vocab
DCFG = tfm.TransformerConfig(vocab=64, d_model=16, n_heads=2, head_dim=8,
                             n_layers=1, d_ff=32)

REQS = [dict(prompt=[3, 1, 4], max_new=9),
        dict(prompt=[2, 7], max_new=5),
        dict(prompt=[5, 6, 7, 8, 9], max_new=12),
        dict(prompt=[1], max_new=7),
        dict(prompt=[9, 9, 2, 1], max_new=3),
        dict(prompt=[4, 4], max_new=10)]

SAMPLED = [dict(prompt=[3, 1, 4], max_new=8, temperature=0.9,
                key=jax.random.PRNGKey(7)),
           dict(prompt=[2, 7, 9], max_new=8, temperature=0.7,
                key=jax.random.PRNGKey(8)),
           dict(prompt=[5, 5], max_new=6, temperature=1.3,
                key=jax.random.PRNGKey(9))]


@pytest.fixture(scope="module")
def params():
    return tfm.init_params(CFG, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def draft_params():
    return tfm.init_params(DCFG, jax.random.PRNGKey(1))


def _ref(params, cfg, prompt, max_new, eos_id=None):
    out = tfm.generate(params, cfg,
                       jnp.asarray([prompt], jnp.int32),
                       max_new=max_new, eos_id=eos_id)
    return [int(t) for t in np.asarray(out)[0]]


def _serve(params, reqs, *, smax=64, slots=3, **kw):
    srv = ContinuousServer(params, CFG, slots=slots, smax=smax, **kw)
    for r in reqs:
        srv.submit(**r)
    return srv.run(), srv


# -- equivalence sweep -------------------------------------------------------

# the block geometry: the default block of 16 rows holds a whole request
# here, so a window never leaves it; over blocks of 4 a window of up to
# 5 tokens crosses a seam, `_ensure_window` extends the table under the
# drafts and a rejection hands blocks back (`PageTable.rollback`)
BLOCKS = pytest.mark.parametrize("block_size", [None, 4],
                                 ids=["paged", "block4"])

@BLOCKS
@pytest.mark.parametrize("k", [1, 2, 4])
def test_greedy_matches_nonspec_and_generate(params, block_size, k):
    base, _ = _serve(params, REQS, block_size=block_size)
    spec, srv = _serve(params, REQS, block_size=block_size, spec=True,
                       spec_k=k)
    assert spec == base
    for rid, r in enumerate(REQS):
        assert spec[rid] == _ref(params, CFG, r["prompt"], r["max_new"])
    st = srv.spec_stats()
    assert st["steps"] > 0 and st["emitted"] > 0
    # every spec step emits at least the sequential token
    assert st["tokens_per_step"] >= 1.0


@BLOCKS
@pytest.mark.parametrize("k", [1, 2, 4])
def test_sampled_matches_nonspec(params, block_size, k):
    """temperature > 0: acceptance still reduces to exact token match
    because `_sample_row` is deterministic given (key, pos, row)."""
    base, _ = _serve(params, SAMPLED, slots=2, block_size=block_size)
    spec, _ = _serve(params, SAMPLED, slots=2, block_size=block_size,
                     spec=True, spec_k=k)
    assert spec == base


@BLOCKS
def test_eos_inside_window(params, block_size):
    """An eos accepted mid-window must truncate the emission exactly
    where the sequential server would have stopped."""
    probe = _ref(params, CFG, [3, 1, 4], 9)
    eos = probe[3]
    reqs = [dict(prompt=[3, 1, 4], max_new=9, eos_id=eos),
            dict(prompt=[2, 7], max_new=5)]
    base, _ = _serve(params, reqs, slots=2, block_size=block_size)
    spec, _ = _serve(params, reqs, slots=2, block_size=block_size,
                     spec=True, spec_k=4)
    assert spec == base
    assert spec[0] == _ref(params, CFG, [3, 1, 4], 9, eos_id=eos)


@BLOCKS
def test_rejection_at_first_token(params, draft_params, block_size):
    """A deliberately bad draft model (random tiny checkpoint): most
    windows reject at the first draft, yet output stays identical and
    every step still lands the sequential token."""
    base, _ = _serve(params, REQS, block_size=block_size)
    spec, srv = _serve(params, REQS, block_size=block_size, spec=True,
                       spec_k=4, draft_params=draft_params, draft_cfg=DCFG)
    assert spec == base
    st = srv.spec_stats()
    assert st["drafted"] > 0
    assert st["acceptance_rate"] < 0.5      # it IS a bad draft model
    assert st["tokens_per_step"] >= 1.0     # but never below sequential


@BLOCKS
def test_draft_model_vs_prompt_lookup_same_tokens(params, draft_params,
                                                  block_size):
    """The two draft sources may accept wildly different fractions,
    but both must decode the exact same tokens."""
    kw = dict(block_size=block_size, spec=True, spec_k=3)
    lookup, _ = _serve(params, REQS, **kw)
    model, _ = _serve(params, REQS, draft_params=draft_params,
                      draft_cfg=DCFG, **kw)
    assert lookup == model


def test_self_draft_full_acceptance(params):
    """Draft == target: every draft token matches, so acceptance is
    1.0 and steps emit full windows (the speedup upper bound)."""
    spec, srv = _serve(params, REQS, spec=True, spec_k=4,
                       draft_params=params, draft_cfg=CFG)
    for rid, r in enumerate(REQS):
        assert spec[rid] == _ref(params, CFG, r["prompt"], r["max_new"])
    st = srv.spec_stats()
    assert st["acceptance_rate"] == pytest.approx(1.0)
    assert st["tokens_per_step"] > 1.5


def test_max_new_one_and_tiny_k(params):
    """Edge: nothing to draft (max_new=1) and k=1 windows."""
    reqs = [dict(prompt=[3, 1, 4], max_new=1),
            dict(prompt=[2, 7], max_new=2)]
    base, _ = _serve(params, reqs, slots=2)
    spec, _ = _serve(params, reqs, slots=2, spec=True, spec_k=1)
    assert spec == base


def test_spec_k_validation(params):
    with pytest.raises(ValueError):
        ContinuousServer(params, CFG, spec=True, spec_k=0)
    with pytest.raises(ValueError):
        ContinuousServer(params, CFG, spec=True, spec_draft="oracle")


def test_rollback_frees_rejected_blocks(params):
    """Paged spec serving must not leak pool blocks on rejection:
    rollback decrefs every block the rejected window had appended, so
    the post-run pool state matches the non-speculative run exactly."""
    base, bsrv = _serve(params, REQS, paged=True)
    spec, srv = _serve(params, REQS, paged=True, spec=True, spec_k=4)
    assert len(spec) == len(REQS)
    bst, st = bsrv.cache_stats(), srv.cache_stats()
    assert st["in_use"] == bst["in_use"]
    assert st["blocks_held"] == bst["blocks_held"]


# -- compile guard: verify programs are O(buckets) ---------------------------

GUARD_CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4,
                                  head_dim=8, n_layers=2, d_ff=56)


def test_spec_programs_o_buckets():
    """Mixed adaptive-k workload: verify windows bucket on the prefill
    ladder, so program builds stay O(buckets) — one verify program per
    rung touched, NOT one per distinct (1 + k) width."""
    params = tfm.init_params(GUARD_CFG, jax.random.PRNGKey(2))
    r = np.random.RandomState(3)
    reqs = [dict(prompt=[int(t) for t in r.randint(1, 64, p)],
                 max_new=8) for p in (3, 5, 9, 12, 4, 8)]
    with count_compiles() as c:
        srv = ContinuousServer(params, GUARD_CFG, slots=4, smax=64,
                               prefill_chunk=8, prefill_buckets="4,8",
                               spec=True, spec_k=4)
        out = {}
        for req in reqs:
            srv.submit(**req)
        out = srv.run()
    assert len(out) == len(reqs)
    buckets = len(srv.prefill_buckets)
    # chunk-per-bucket + probe + splice + step + one verify program
    # per rung a window landed on (≤ buckets)
    assert srv._prog_misses <= 2 * buckets + 3
    assert int(c) <= 2 * buckets + 24
    # warm server, fresh lengths: everything reuses
    with count_compiles() as c2:
        srv2 = ContinuousServer(params, GUARD_CFG, slots=4, smax=64,
                                prefill_chunk=8, prefill_buckets="4,8",
                                spec=True, spec_k=4)
        for p in (7, 11):
            srv2.submit([int(t) for t in r.randint(1, 64, p)],
                        max_new=6)
        out2 = srv2.run()
    assert len(out2) == 2
    assert srv2._prog_misses == 0
    assert int(c2) <= 2
