"""Inside `ContinuousServer.step()` the only programs that reach the
device are the ones `_program()` hands out (PR 40): the seed token is
picked and the slot's lane of the per-slot vectors is set inside
`cb_probe` (one layer deep since PR 44: it starts from the hidden row
the last chunk handed back), the vectors' host copies are NumPy, and
every operand of a named program is host NumPy. Measured at the two real boundaries:

* compiles: a fresh server driven through a mixed-length workload
  compiles exactly `srv._prog_misses` XLA modules, i.e. not one
  first-touch eager op (`utils/compilemon.count_compiles`);
* executions: under the profiler every `serving.step` span holds as
  many `PjRtCpuExecutable::Execute` events (one a program the CPU
  client is handed: what `dev_programs_per_step` counts on the chip)
  as the step's record has `dispatches`.

A config of its own (d_ff=40) keeps other modules' warm program caches
out of the counts, as `test_compile_guard.py` does."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hpx_tpu.models import serving
from hpx_tpu.models import transformer as tfm
from hpx_tpu.models.serving import ContinuousServer
from hpx_tpu.utils.compilemon import count_compiles

CFG = tfm.TransformerConfig(vocab=64, d_model=32, n_heads=4, head_dim=8,
                            n_layers=2, d_ff=40)
# a model whose layers choose their blocks (an index pool beside K/V)
# and keep a linear state: its admission resets a state, its splice
# writes an index, its step selects on the device
SALA = tfm.TransformerConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=8, n_kv_heads=2, n_layers=2,
    d_ff=40, norm="rmsnorm", mlp="swiglu", tied=False,
    layer_mixer=("sparse", "lightning"),
    layer_rope=(None, tfm.RopeSpec(10000.0)), sparse_kernel=4,
    sparse_stride=2, sparse_block=8, sparse_topk=2, sparse_local=8,
    sparse_dense_len=16, lightning_heads=4, lightning_head_dim=8,
    qk_norm=True, emb_scale=12.0, residual_scale=0.25, logit_scale=0.5)
# a state-space hybrid: selective-scan layers (a state and a conv tail a
# slot, reset at admission) beside attention over one K/V head, the
# LAST layer recurrent, a tied head
SSM = tfm.TransformerConfig(
    vocab=64, d_model=32, n_heads=4, head_dim=8, n_kv_heads=1, n_layers=3,
    d_ff=40, norm="rmsnorm", norm_eps=1e-6, mlp="swiglu", tied=True,
    layer_mixer=("mamba", "attn", "mamba"), mamba_d_inner=64,
    mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=4)
MODELS = {"sala": SALA, "ssm": SSM}
EXECUTE = "PjRtCpuExecutable::Execute"
# the K/V model over blocks of 8 rows (the chunk's width) and of 4 (a
# chunk, and every admission but the shortest, spans blocks)
BLOCKS = {"paged": 8, "block4": 4}
MODES = [*BLOCKS, "sala", "ssm"]
# what each request of a workload asks for beside its prompt
KINDS = {
    "greedy": [{}] * 5,
    "raw_key": [{"temperature": 0.8, "raw": s} for s in (3, 4, 5, 6, 7)],
    "typed_key": [{"temperature": 1.3, "typed": s} for s in (3, 4, 5, 6, 7)],
    "eos": [{"eos_id": 7}] * 5,
    "mixed": [{}, {"temperature": 0.8, "raw": 3}, {"eos_id": 7},
              {"temperature": 1.3, "typed": 4}, {}, {"max_new": 1},
              {"temperature": 0.5, "raw": 9, "eos_id": 11}],
}
PLENS = [3, 21, 9, 12, 17, 5, 14]      # inline and chunked admissions


@pytest.fixture(scope="module")
def params():
    return {"sala": tfm.init_params(SALA, jax.random.PRNGKey(2)),
            "ssm": tfm.init_params(SSM, jax.random.PRNGKey(3)),
            None: tfm.init_params(CFG, jax.random.PRNGKey(1))}


def _server(params, mode):
    if mode in MODELS:
        return ContinuousServer(params[mode], MODELS[mode], slots=3, smax=64,
                                prefill_chunk=8, prefill_buckets="4,8")
    return ContinuousServer(params[None], CFG, slots=3, smax=64,
                            prefill_chunk=8, prefill_buckets="4,8",
                            block_size=BLOCKS[mode])


def _submit(srv, kind, seed=0):
    """Queue the workload (keys are made and brought to the host here,
    in submit(), outside every step)."""
    r = np.random.RandomState(seed)
    rids = []
    for plen, ask in zip(PLENS, KINDS[kind]):
        ask = dict(ask)
        if "raw" in ask:
            ask["key"] = jax.random.PRNGKey(ask.pop("raw"))
        if "typed" in ask:
            ask["key"] = jax.random.key(ask.pop("typed"))
        ask.setdefault("max_new", 6)
        rids.append(srv.submit(
            [int(t) for t in r.randint(1, CFG.vocab, plen)], **ask))
    return rids


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("mode", MODES)
def test_a_workload_compiles_its_named_programs_and_nothing_else(
        params, mode, kind):
    srv = _server(params, mode)
    rids = _submit(srv, kind)
    with count_compiles() as c:
        out = srv.run()
    assert sorted(out) == rids and not srv.failed
    assert srv._prog_hits + srv._prog_misses > 10
    assert int(c) == srv._prog_misses


def _matmuls(fn, *args):
    return str(jax.make_jaxpr(fn)(*args)).count("dot_general")


@pytest.mark.parametrize("mode", MODES)
def test_the_probe_holds_one_layers_matmuls(params, mode):
    """`jit_probe` is ONE layer deep (PR 44): its jaxpr holds the
    matmuls of the last layer on one row and the head's, whatever the
    model's depth, where a one-row window through the whole model holds
    every layer's; a last layer that is recurrent (the "sala" and the
    "ssm" toys') ran in the chunk, and the probe is ln and head alone."""
    srv = _server(params, mode)
    cfg, weights = srv.cfg, srv.params
    last = cfg.n_layers - 1
    scratch = [serving._scratch_entry(cfg, srv.smax, i)
               for i in range(cfg.n_layers)]
    row = jnp.zeros((1, 1, cfg.d_model), cfg.dtype)
    pos = np.int32(5)
    probe = _matmuls(
        srv._probe_prog()._prog, srv._tail_params, row, scratch[last], pos,
        srv._feedback(), *srv._lanes(), np.int32(0), np.float32(0.0),
        srv._no_key)
    head = _matmuls(lambda x: tfm._logits(weights, x, cfg), row)
    layer = _matmuls(
        lambda x, kv: tfm._block_decode(x, weights["layers"][last], kv, pos,
                                        cfg, li=last), row, scratch[last])
    whole = _matmuls(
        lambda kv: tfm._decode_window(weights, kv, jnp.zeros((1, 1), int),
                                      pos, cfg), scratch)
    assert head == 1 and layer >= 4 and whole > layer + head
    recurrent = cfg.mixer(last) in tfm.RECURRENT_KINDS
    assert recurrent == (mode in MODELS)
    assert probe == head + (0 if recurrent else layer)
    assert len(srv._tail_params["layers"]) == 1


def _step_programs(logdir):
    """[(n, programs handed to the device inside it)] of every
    `serving.step` span of the trace under `logdir`."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    steps, runs = [], []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == "serving.step":
                    steps.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  int(dict(ev.stats)["n"])))
                elif ev.name == EXECUTE:
                    runs.append(ev.start_ns)
    return [(n, sum(a <= t < b for t in runs))
            for a, b, n in sorted(steps)]


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("mode", MODES)
def test_a_step_enqueues_its_dispatches_and_nothing_else(
        params, mode, kind, tmp_path):
    srv = _server(params, mode)
    rids = _submit(srv, kind, seed=1)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = srv.run()
    finally:
        jax.profiler.stop_trace()
    assert sorted(out) == rids and not srv.failed
    recs = srv.step_accounts()
    seen = _step_programs(tmp_path)
    assert [n for n, _ in seen] == [r.n for r in recs]
    assert sum(k for _, k in seen) > len(recs)      # the events are there
    assert seen == [(r.n, r.dispatches) for r in recs]
    # an admission is its probe, its splice and a scratch or a gather,
    # a chunk one program, a decode step one
    assert sum(r.dispatches for r in recs) == (
        3 * len(rids) + srv._chunks + sum(r.eager_ns > 0 for r in recs))


def test_importing_the_package_leaves_the_selective_scan_unimported():
    """`ops/mamba.py` is imported where it is used (as `lightning.py`
    and `sparse_attention.py` are): neither `import hpx_tpu` nor what
    the stencil cell's driver imports pays for it."""
    import subprocess
    import sys
    code = ("import sys, hpx_tpu, hpx_tpu.models.serving, "
            "chipbench.drivers.hpx_dataflow; "
            "print(sorted(m for m in sys.modules if m.endswith("
            "('ops.mamba', 'ops.lightning', 'ops.sparse_attention'))))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env={**__import__("os").environ,
                                         "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "[]"
