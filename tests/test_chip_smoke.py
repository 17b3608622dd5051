"""`chip_smoke.py` without the chip: it must refuse the CPU, and its
phase functions — imported, and run at tiny widths — must agree with
their references here, so a chip call is never spent on a wrong path,
argument or mesh. The chip's own facts (kernels in the compiled text,
bfloat16, real widths) are `chip_smoke.py`'s to prove, on the chip."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke as cs  # noqa: E402
from hpx_tpu.models import transformer as tfm  # noqa: E402

TINY = tfm.TransformerConfig(vocab=256, d_model=64, n_heads=4,
                             head_dim=16, n_kv_heads=2, d_ff=128,
                             n_layers=2, rope=True, dtype=jnp.float32)
SERVE = dict(slots=4, smax=64, rungs=(8, 16), max_new=6)


def test_exits_nonzero_and_names_the_platform_on_cpu():
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok": true' not in r.stdout


def test_phase_hpx_agrees_with_numpy():
    out = cs.phase_hpx(log2_n=14, fused_log2=12, fused_steps=16, chain=3)
    assert out["scheduler"] in ("native", "python")
    assert set(out["kernels"]) == {"heat_step_best", "heat_part",
                                   "multistep"}


def test_phase_serve_fused_interpret_equals_generate():
    """The one-chip serve phase with the kernel `auto` picks on a TPU,
    here in interpret mode (steered through the existing config key —
    the smoke itself passes no kernel)."""
    from hpx_tpu.core.config import runtime_config
    rc = runtime_config()
    rc.set("hpx.serving.paged_kernel", "fused")
    try:
        out = cs.phase_serve(TINY, **SERVE)
    finally:
        rc.set("hpx.serving.paged_kernel", "auto")
    assert out["paged_kernel"] == "fused"
    assert out["tokens_equal_generate"] == out["requests"] == 10
    assert out["near_ties"] == []
    assert out["block_size_source"] == "default"


def test_phase_train_first_loss_equals_plain_forward():
    out = cs.phase_train(TINY, batch=2, seq=32, steps=3)
    assert abs(out["losses"][0] - out["reference_loss"]) < 1e-4
    assert out["losses"][2] < out["losses"][0]        # it learns


def test_phase_mesh4_on_four_virtual_devices():
    out = cs.phase_mesh4(TINY, payload=1024, **SERVE)
    assert out["tokens_equal_single_device"] == out["requests"] == 10
    assert out["param_devices"] == out["pool_devices"] == [0, 1, 2, 3]


@pytest.mark.parametrize("tol,passes", [(1e9, True), (0.0, False)])
def test_divergence_must_be_a_near_tie(monkeypatch, tol, passes):
    """Where tokens part from the reference decoder, every emitted
    token is judged by the plain float32 forward: within TIE_TOL of its
    best the divergence is recorded, beyond it the smoke fails."""
    params = tfm.init_params(TINY, jax.random.PRNGKey(0))
    reqs = cs.make_requests(TINY.vocab, (8,), 6, n_greedy=1, n_sampled=1)
    want = {rid: cs.generate_tokens(params, TINY, req)
            for rid, req in enumerate(reqs)}
    got = {rid: list(toks) for rid, toks in want.items()}
    for rid in got:                     # part at step 2, both requests
        got[rid][2] = (got[rid][2] + 1) % TINY.vocab
    monkeypatch.setattr(cs, "TIE_TOL", tol)
    if passes:
        exact, ties = cs.compare_tokens(params, TINY, reqs, got, want, "t")
        assert exact == 0 and [t["step"] for t in ties] == [2, 2]
        assert all(t["gap"] > 0 for t in ties)
    else:
        with pytest.raises(cs.SmokeFailure, match="near-tie"):
            cs.compare_tokens(params, TINY, reqs, got, want, "t")


def test_reference_scores_pick_what_generate_emits():
    """The plain forward and the sampling contract `reference_scores`
    rebuilds agree with generate(): teacher-forced, the reference's
    best token at every step IS the emitted one, greedy and sampled."""
    params = tfm.init_params(TINY, jax.random.PRNGKey(1))
    for req in cs.make_requests(TINY.vocab, (8,), 4, n_greedy=1,
                                n_sampled=1, seed=3):
        toks = cs.generate_tokens(params, TINY, req)
        scores = cs.reference_scores(params, TINY, req, toks)
        assert scores.argmax(axis=-1).tolist() == toks


def test_compile_cache_dir_is_placed_from_outside(monkeypatch, tmp_path):
    from hpx_tpu.utils import compile_cache as cc
    keep = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        # variable unset: the fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert cc.compile_cache_dir() == (
            os.path.join(REPO, ".jax_cache"), False)
        # variable set: jax reads it itself; no directory set in code
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == \
            keep["jax_compilation_cache_dir"]
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
