"""hpxlint tier-3 (dataflow) tests: the def-use core, the four rules
HPX019–HPX022 (positive + negative fixture per rule), the CLI fast
paths (``--changed``, ``--only``), the decorated-function suppression
reach, baseline ordering, the per-rule JSON counts, and the CI gate
script — including its perf budget (one parse per file, <15s for the
full three-tier sweep).
"""

import ast
import json
import os
import subprocess
import sys
import time

from hpx_tpu.analysis import all_rules, lint_sources, lint_paths
from hpx_tpu.analysis.cli import main as cli_main
from hpx_tpu.analysis.dataflow import (
    DataflowIndex,
    DefUse,
    classify_origin,
    provably_host,
)
from hpx_tpu.analysis.engine import FileContext, parse_count
from hpx_tpu.analysis.project import ProjectIndex

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint(sources, select):
    return lint_sources(sources, rules=all_rules(select)).findings


def rules_of(fs):
    return [f.rule for f in fs]


def _du(src):
    """DefUse over the first function in `src`."""
    fn = ast.parse(src).body[0]
    return DefUse(fn)


def _uses_of(du, name):
    return [u for u in du.uses if u.name == name]


# ---------------------------------------------------------------------------
# Def-use core: forks, loops, try/finally, augmented assignment
# ---------------------------------------------------------------------------

def test_defuse_if_fork_merges_both_arms():
    du = _du(
        "def f(c):\n"
        "    if c:\n"
        "        x = 1\n"
        "    else:\n"
        "        x = 2\n"
        "    return x\n")
    (use,) = _uses_of(du, "x")
    assert sorted(d.node.lineno for d in use.defs) == [3, 5]


def test_defuse_if_without_else_keeps_prior_def():
    du = _du(
        "def f(c):\n"
        "    x = 1\n"
        "    if c:\n"
        "        x = 2\n"
        "    return x\n")
    (use,) = _uses_of(du, "x")
    assert sorted(d.node.lineno for d in use.defs) == [2, 4]


def test_defuse_loop_back_edge_reaches_first_iteration():
    du = _du(
        "def f(xs):\n"
        "    y = 0\n"
        "    for v in xs:\n"
        "        z = y\n"
        "        y = 1\n"
        "    return y\n")
    # the in-loop read must see BOTH the pre-loop def and the
    # back-edge def from the previous iteration
    in_loop = [u for u in _uses_of(du, "y") if u.node.lineno == 4]
    assert in_loop
    lines = set()
    for u in in_loop:
        lines |= {d.node.lineno for d in u.defs}
    assert lines == {2, 5}
    # and the post-loop read sees the zero-iteration path too
    (after,) = [u for u in _uses_of(du, "y") if u.node.lineno == 6]
    assert {d.node.lineno for d in after.defs} == {2, 5}


def test_defuse_try_handler_sees_every_body_state():
    du = _du(
        "def f():\n"
        "    x = 1\n"
        "    try:\n"
        "        x = 2\n"
        "        risky()\n"
        "        x = 3\n"
        "    except ValueError:\n"
        "        h = x\n"
        "    return x\n")
    # the handler can run after any prefix of the body: all three
    # definitions reach the read at line 8
    (handler_use,) = [u for u in _uses_of(du, "x")
                      if u.node.lineno == 8]
    assert {d.node.lineno for d in handler_use.defs} == {2, 4, 6}


def test_defuse_finally_sees_normal_and_escaping_states():
    du = _du(
        "def f():\n"
        "    x = 1\n"
        "    try:\n"
        "        x = 2\n"
        "    finally:\n"
        "        g = x\n"
        "    return x\n")
    (fin_use,) = [u for u in _uses_of(du, "x") if u.node.lineno == 6]
    assert {d.node.lineno for d in fin_use.defs} == {2, 4}


def test_defuse_augmented_assignment_reads_then_rebinds():
    du = _du(
        "def f():\n"
        "    x = 1\n"
        "    x += 2\n"
        "    return x\n")
    aug_use, ret_use = _uses_of(du, "x")
    assert {d.node.lineno for d in aug_use.defs} == {2}
    (ret_def,) = ret_use.defs
    assert ret_def.kind == "aug" and ret_def.node.lineno == 3


def test_defuse_return_kills_fallthrough():
    du = _du(
        "def f(c):\n"
        "    if c:\n"
        "        x = 1\n"
        "        return x\n"
        "    x = 2\n"
        "    return x\n")
    last = [u for u in _uses_of(du, "x") if u.node.lineno == 6]
    (use,) = last
    # the early-returning arm cannot fall through to line 6
    assert {d.node.lineno for d in use.defs} == {5}


# ---------------------------------------------------------------------------
# HPX019 — unguarded shared state (inferred guarded-by)
# ---------------------------------------------------------------------------

HPX019_BAD = """\
from hpx_tpu.synchronization import Mutex

class Stats:
    def __init__(self):
        self._lock = Mutex()
        self.count = 0

    def bump(self):
        with self._lock:
            self.count += 1

    def bump2(self):
        with self._lock:
            self.count += 2

    def sloppy(self):
        self.count += 3
"""

HPX019_GOOD = HPX019_BAD.replace(
    "    def sloppy(self):\n        self.count += 3\n",
    "    def sloppy(self):\n"
    "        with self._lock:\n"
    "            self.count += 3\n")


def test_hpx019_bare_minority_write_fires():
    fs = _lint({"hpx_tpu/svc/fix19.py": HPX019_BAD}, ["HPX019"])
    assert rules_of(fs) == ["HPX019"]
    assert "self.count is mutated in Stats.sloppy()" in fs[0].message
    assert "2 of 3 mutation sites" in fs[0].message


def test_hpx019_silent_when_every_site_holds_the_lock():
    assert _lint({"hpx_tpu/svc/fix19.py": HPX019_GOOD},
                 ["HPX019"]) == []


def test_hpx019_no_majority_means_no_contract():
    # 1 held / 1 bare: no strict majority, nothing inferable
    src = HPX019_BAD.replace(
        "    def bump2(self):\n"
        "        with self._lock:\n"
        "            self.count += 2\n\n", "")
    assert _lint({"hpx_tpu/svc/fix19.py": src}, ["HPX019"]) == []


def test_hpx019_init_only_and_single_method_attrs_exempt():
    src = """\
from hpx_tpu.synchronization import Mutex

class Worker:
    def __init__(self):
        self._lock = Mutex()
        self.name = "w"          # __init__-only: exempt

    def step(self):
        self._scratch = 0        # single-method scratch: exempt
        with self._lock:
            self._scratch += 1
"""
    assert _lint({"hpx_tpu/svc/fix19.py": src}, ["HPX019"]) == []


def test_hpx019_scoped_to_shared_state_layers():
    # same race pattern outside svc/models/cache/dist: out of scope
    assert _lint({"hpx_tpu/algo/fix19.py": HPX019_BAD},
                 ["HPX019"]) == []


def test_hpx019_caller_held_lock_counts_via_call_graph():
    # the bare-looking helper is only ever called with the lock held:
    # its effective held-set comes from the one-level caller summary
    src = """\
from hpx_tpu.synchronization import Mutex

class Stats:
    def __init__(self):
        self._lock = Mutex()
        self.count = 0

    def bump(self):
        with self._lock:
            self._bump_locked()

    def bump2(self):
        with self._lock:
            self._bump_locked()

    def _bump_locked(self):
        self.count += 1

    def other(self):
        with self._lock:
            self.count += 5
"""
    assert _lint({"hpx_tpu/svc/fix19.py": src}, ["HPX019"]) == []


# ---------------------------------------------------------------------------
# HPX020 — donation use-after-donate
# ---------------------------------------------------------------------------

HPX020_BAD = """\
import jax

def step(fn, pool, tok):
    prog = jax.jit(fn, donate_argnums=(0,))
    out = prog(pool, tok)
    return pool + out
"""

HPX020_GOOD = """\
import jax

def step(fn, pool, tok):
    prog = jax.jit(fn, donate_argnums=(0,))
    pool = prog(pool, tok)
    return pool
"""


def test_hpx020_use_after_donate_fires():
    fs = _lint({"hpx_tpu/models/fix20.py": HPX020_BAD}, ["HPX020"])
    assert rules_of(fs) == ["HPX020"]
    assert "`pool` is used after being donated" in fs[0].message
    assert fs[0].line == 6


def test_hpx020_rebinding_the_result_is_silent():
    assert _lint({"hpx_tpu/models/fix20.py": HPX020_GOOD},
                 ["HPX020"]) == []


def test_hpx020_direct_jit_call_and_loop_rebind():
    bad = """\
import jax

def run(fn, state, xs):
    out = jax.jit(fn, donate_argnums=(0,))(state, xs)
    state.block_until_ready()
    return out
"""
    fs = _lint({"hpx_tpu/models/fix20.py": bad}, ["HPX020"])
    assert rules_of(fs) == ["HPX020"]
    good = """\
import jax

def run(fn, state, xs):
    prog = jax.jit(fn, donate_argnums=(0,))
    for x in xs:
        state = prog(state, x)
    return state
"""
    assert _lint({"hpx_tpu/models/fix20.py": good}, ["HPX020"]) == []


def test_hpx020_non_donated_positions_are_silent():
    src = """\
import jax

def step(fn, pool, tok):
    prog = jax.jit(fn, donate_argnums=(0,))
    out = prog(pool, tok)
    return tok + out
"""
    assert _lint({"hpx_tpu/models/fix20.py": src}, ["HPX020"]) == []


# ---------------------------------------------------------------------------
# HPX021 — mesh-axis consistency inside shard_map bodies
# ---------------------------------------------------------------------------

HPX021_BAD = """\
import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

def build(devs):
    mesh = Mesh(devs, ("dp", "sp"))

    def body(x):
        return jax.lax.psum(x, "tp")

    return shard_map(body, mesh=mesh,
                     in_specs=P("dp"), out_specs=P("dp"))
"""

HPX021_GOOD = HPX021_BAD.replace('jax.lax.psum(x, "tp")',
                                 'jax.lax.psum(x, "dp")')


def test_hpx021_undeclared_axis_fires():
    fs = _lint({"hpx_tpu/models/fix21.py": HPX021_BAD}, ["HPX021"])
    assert rules_of(fs) == ["HPX021"]
    assert "psum() over axis 'tp'" in fs[0].message
    assert "(dp, sp)" in fs[0].message


def test_hpx021_declared_axis_is_silent():
    assert _lint({"hpx_tpu/models/fix21.py": HPX021_GOOD},
                 ["HPX021"]) == []


def test_hpx021_specs_fallback_when_mesh_is_opaque():
    src = """\
import jax
from jax import shard_map
from jax.sharding import PartitionSpec as P

def build(mesh):
    def body(x):
        return jax.lax.psum(x, "tp")
    return shard_map(body, mesh=mesh,
                     in_specs=P("dp"), out_specs=P("dp"))
"""
    fs = _lint({"hpx_tpu/models/fix21.py": src}, ["HPX021"])
    assert rules_of(fs) == ["HPX021"]
    assert "(dp)" in fs[0].message


def test_hpx021_opaque_mesh_and_specs_skip_not_guess():
    # mesh is a parameter and one spec fragment is a variable: the
    # declared set cannot be resolved, so the site is skipped even
    # though "tp" looks suspicious
    src = """\
import jax
from jax import shard_map

def build(mesh, pspecs):
    def body(x):
        return jax.lax.psum(x, "tp")
    return shard_map(body, mesh=mesh,
                     in_specs=pspecs, out_specs=pspecs)
"""
    assert _lint({"hpx_tpu/models/fix21.py": src}, ["HPX021"]) == []


def test_hpx021_partition_spec_fragment_in_body():
    src = """\
import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

def build(devs):
    mesh = Mesh(devs, ("dp",))

    def body(x):
        s = P("tp")
        return jax.lax.psum(x, "dp"), s

    return shard_map(body, mesh=mesh,
                     in_specs=P("dp"), out_specs=P("dp"))
"""
    fs = _lint({"hpx_tpu/models/fix21.py": src}, ["HPX021"])
    assert rules_of(fs) == ["HPX021"]
    assert "PartitionSpec axis 'tp'" in fs[0].message


# The expert-parallel decode shape (models/moe.moe_ffn_decode): a
# same-file helper carrying axis_index / tiled all_to_all / psum over
# the expert axis, called from the shard_map body.  The helper-chasing
# path must CHECK these collectives, not skip them.
HPX021_EP = """\
import jax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

def _moe(x):
    i = jax.lax.axis_index("ep")
    x = jax.lax.all_to_all(x, "ep", split_axis=0, concat_axis=2,
                           tiled=True)
    return jax.lax.psum(x, "ep") + i

def build(devs):
    mesh = Mesh(devs, ("dp", "ep"))

    def body(x):
        return _moe(x)

    return shard_map(body, mesh=mesh,
                     in_specs=P("dp"), out_specs=P("dp"))
"""


def test_hpx021_ep_axis_declared_is_silent():
    assert _lint({"hpx_tpu/models/fix21.py": HPX021_EP},
                 ["HPX021"]) == []


def test_hpx021_ep_axis_undeclared_fires_in_chased_helper():
    # the same body on a mesh WITHOUT "ep" (the dp/tp serving default
    # before an ep axis is declared): every "ep" collective in the
    # chased helper flags, including the tiled all_to_all exchange
    src = HPX021_EP.replace('("dp", "ep")', '("dp", "tp")')
    fs = _lint({"hpx_tpu/models/fix21.py": src}, ["HPX021"])
    assert rules_of(fs) == ["HPX021"] * 3
    msgs = "\n".join(f.message for f in fs)
    assert "axis_index() over axis 'ep'" in msgs
    assert "all_to_all() over axis 'ep'" in msgs
    assert "psum() over axis 'ep'" in msgs
    assert "(dp, tp)" in msgs


def test_hpx021_registry_covers_moe_decode_collectives():
    # pin: every collective moe_ffn / moe_ffn_decode use inside
    # shard_map bodies stays in the axis-arg registry with the right
    # position, so their axis literals are checked rather than skipped
    from hpx_tpu.analysis.dataflow import _COLLECTIVE_AXIS_ARG
    assert _COLLECTIVE_AXIS_ARG["all_to_all"] == 1
    assert _COLLECTIVE_AXIS_ARG["axis_index"] == 0
    assert _COLLECTIVE_AXIS_ARG["psum"] == 1
    assert _COLLECTIVE_AXIS_ARG["pmean"] == 1


# ---------------------------------------------------------------------------
# HPX022 — flow-sensitive host sync
# ---------------------------------------------------------------------------

HPX022_BAD = """\
import jax.numpy as jnp

def mean_loss(x):
    s = jnp.sum(x)
    return float(s)
"""

HPX022_GOOD = """\
import numpy as np

def host_mean(x):
    n = len(x)
    m = np.mean(x)
    return float(n) + float(m)
"""


def test_hpx022_device_origin_sync_fires():
    fs = _lint({"hpx_tpu/exec/fix22.py": HPX022_BAD}, ["HPX022"])
    assert rules_of(fs) == ["HPX022"]
    assert "float(s)" in fs[0].message


def test_hpx022_host_origin_is_silent():
    assert _lint({"hpx_tpu/exec/fix22.py": HPX022_GOOD},
                 ["HPX022"]) == []


def test_hpx022_outside_hot_subpaths_is_silent():
    assert _lint({"hpx_tpu/svc/fix22.py": HPX022_BAD},
                 ["HPX022"]) == []


def test_hpx022_disagreeing_branches_stay_silent():
    # one branch host, one device: the reaching definitions disagree,
    # so the may-analysis refuses to speak (no false positive on the
    # host-only execution)
    src = """\
import jax.numpy as jnp

def maybe(x, flag):
    if flag:
        s = jnp.sum(x)
    else:
        s = 0.0
    return float(s)
"""
    assert _lint({"hpx_tpu/exec/fix22.py": src}, ["HPX022"]) == []


def test_hpx022_arithmetic_promotion_flags():
    # device + host scalar arithmetic yields a jax.Array — the BinOp
    # join promotes to device and the sink is flagged
    src = """\
import jax.numpy as jnp

def norm(x):
    s = jnp.sum(x) + 1.0
    return float(s)
"""
    fs = _lint({"hpx_tpu/exec/fix22.py": src}, ["HPX022"])
    assert rules_of(fs) == ["HPX022"]


def test_hpx022_unknown_origin_stays_silent():
    # a def-use chain that bottoms out in an unknown call must NOT be
    # guessed device — may-analysis only speaks with proof
    src = """\
import jax.numpy as jnp

def route(handle):
    s = handle.pull()
    return float(s)
"""
    assert _lint({"hpx_tpu/exec/fix22.py": src}, ["HPX022"]) == []


def test_hpx002_prover_drops_host_subscript_false_positive():
    # the historical HPX002 token-match false positive: int() over a
    # numpy (host) subscript — provably host, no finding, no
    # suppression comment needed anymore
    src = """\
import numpy as np

def pick(xs):
    idx = np.flatnonzero(xs)
    return int(idx[0])
"""
    assert _lint({"hpx_tpu/algo/fix02.py": src}, ["HPX002"]) == []


def test_hpx002_keeps_unproven_subscript_sync():
    src = """\
def pick(dev):
    out = dev.compute()
    return int(out[0])
"""
    fs = _lint({"hpx_tpu/algo/fix02.py": src}, ["HPX002"])
    assert rules_of(fs) == ["HPX002"]


def test_classify_origin_api():
    src = ("import jax.numpy as jnp\n"
           "import numpy as np\n"
           "def f(x):\n"
           "    a = jnp.dot(x, x)\n"
           "    b = np.arange(4)\n"
           "    c = x.shape[0]\n"
           "    return a, b, c\n")
    ctx = FileContext(src, "hpx_tpu/exec/fix.py")
    fn = ctx.tree.body[2]
    du = DefUse(fn)
    ret = fn.body[-1].value
    a, b, c = ret.elts
    assert classify_origin(a, du, ctx) == "device"
    assert classify_origin(b, du, ctx) == "host"
    assert provably_host(c, ctx)


# ---------------------------------------------------------------------------
# Suppression reach for decorated functions
# ---------------------------------------------------------------------------

HPX017_DECORATED = """\
import jax

@jax.jit  # hpxlint: disable=HPX017 — fixture: decorator-line directive
def tiny_kernel(x):
    return x + 1
"""


def test_suppression_on_decorator_line_reaches_def_finding():
    res = lint_sources({"hpx_tpu/models/fixsup.py": HPX017_DECORATED},
                       rules=all_rules(["HPX017"]))
    assert res.findings == []
    assert res.suppressed == 1
    assert res.suppressed_by_rule == {"HPX017": 1}


def test_decorated_finding_fires_without_directive():
    src = HPX017_DECORATED.replace(
        "  # hpxlint: disable=HPX017 — fixture: decorator-line "
        "directive", "")
    res = lint_sources({"hpx_tpu/models/fixsup.py": src},
                       rules=all_rules(["HPX017"]))
    assert rules_of(res.findings) == ["HPX017"]


def test_directive_on_decorator_does_not_blanket_body():
    src = """\
import jax

@jax.jit  # hpxlint: disable=HPX017 — fixture
def tiny_kernel(x):
    y = jax.jit(lambda v: v)(x)
    return y
"""
    res = lint_sources({"hpx_tpu/models/fixsup.py": src},
                       rules=all_rules(["HPX017"]))
    # the def-line finding is suppressed; the body one is not
    assert len(res.findings) == 1
    assert res.findings[0].line == 5


# ---------------------------------------------------------------------------
# Real tree: the shared-state contract of the serving plane
# ---------------------------------------------------------------------------

def _real_ctx(rel):
    path = os.path.join(REPO, *rel.split("/"))
    with open(path, encoding="utf-8") as fh:
        return FileContext(fh.read(), rel)


def test_real_tree_fleet_shared_state_guarded():
    """FleetRouter shared state is lock-guarded (verified by HPX019's
    inference over the real file)."""
    rel = "hpx_tpu/svc/fleet.py"
    with open(os.path.join(REPO, *rel.split("/")),
              encoding="utf-8") as fh:
        srcs = {rel: fh.read()}
    res = lint_sources(srcs, rules=all_rules(["HPX019"]))
    assert res.findings == [], \
        "\n".join(f.format() for f in res.findings)


def test_real_tree_fleet_router_counters_consistent():
    # FleetRouter: every _fl_lock-guarded counter is guarded at ALL
    # its mutation sites — HPX019 stays silent because the contract
    # is consistent, not because the index missed the class
    ctx = _real_ctx("hpx_tpu/svc/fleet.py")
    index = ProjectIndex([ctx])
    per_attr = {}
    for q, info in index.functions.items():
        if info.cls != "FleetRouter" or info.node.name == "__init__":
            continue
        for kind, attr, _node, held in info.attr_ops:
            if kind == "write":
                per_attr.setdefault(attr, []).append(bool(held))
    assert "prefill_tokens_saved" in per_attr
    for attr, held_flags in per_attr.items():
        assert len(set(held_flags)) == 1, \
            f"FleetRouter.{attr} mixes locked and bare mutation"


# ---------------------------------------------------------------------------
# CLI fast paths, per-rule counts, baseline ordering
# ---------------------------------------------------------------------------

BAD_MIXED = """\
import jax

def build(fs):
    for f in fs:
        g = jax.jit(f)
    try:
        return g
    except:
        pass
"""


def test_cli_only_filters_to_requested_rule(tmp_path, capsys):
    bad = tmp_path / "mod.py"
    bad.write_text(BAD_MIXED)
    assert cli_main([str(bad), "--no-baseline"]) == 1
    full = capsys.readouterr().out
    assert "HPX006" in full and "HPX005" in full
    assert cli_main([str(bad), "--no-baseline", "--only",
                     "HPX006"]) == 1
    only = capsys.readouterr().out
    assert "HPX006" in only and "HPX005" not in only


def test_cli_only_skips_stale_check_for_rule_subset(tmp_path, capsys):
    # a baseline carrying other rules' entries must not read as stale
    # under a partial --only scan
    bad = tmp_path / "mod.py"
    bad.write_text(BAD_MIXED)
    base = tmp_path / "base.json"
    assert cli_main([str(bad), "--baseline", str(base),
                     "--write-baseline"]) == 0
    capsys.readouterr()
    assert cli_main([str(bad), "--baseline", str(base),
                     "--only", "HPX006"]) == 0
    assert "stale baseline entry (" not in capsys.readouterr().out


def test_cli_changed_lints_only_git_dirty_files(tmp_path):
    subprocess.run(["git", "init", "-q", str(tmp_path)], check=True)
    clean = tmp_path / "clean.py"
    clean.write_text("x = 1\n")
    env = dict(os.environ,
               GIT_AUTHOR_NAME="t", GIT_AUTHOR_EMAIL="t@t",
               GIT_COMMITTER_NAME="t", GIT_COMMITTER_EMAIL="t@t")
    subprocess.run(["git", "add", "-A"], cwd=tmp_path, check=True)
    subprocess.run(["git", "commit", "-qm", "seed"], cwd=tmp_path,
                   env=env, check=True)
    run = [sys.executable, "-m", "hpx_tpu.analysis", "--changed",
           "--no-baseline"]
    pristine = subprocess.run(run, cwd=tmp_path, capture_output=True,
                              text=True, env=dict(env, PYTHONPATH=REPO))
    assert pristine.returncode == 0
    assert "no changed Python files" in pristine.stdout
    (tmp_path / "dirty.py").write_text(
        "def f():\n    try:\n        pass\n    except:\n        pass\n")
    dirty = subprocess.run(run, cwd=tmp_path, capture_output=True,
                           text=True, env=dict(env, PYTHONPATH=REPO))
    assert dirty.returncode == 1
    assert "HPX006" in dirty.stdout
    assert "clean.py" not in dirty.stdout


def test_json_report_has_per_rule_counts(tmp_path, capsys):
    bad = tmp_path / "mod.py"
    bad.write_text(BAD_MIXED + "\n# hpxlint: disable-file=HPX005\n")
    base = tmp_path / "base.json"
    assert cli_main([str(bad), "--baseline", str(base),
                     "--write-baseline"]) == 0
    capsys.readouterr()
    assert cli_main([str(bad), "--baseline", str(base),
                     "--format=json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["findings"] == []
    assert rep["suppressed_by_rule"] == {"HPX005": 1}
    assert rep["baselined_by_rule"] == {"HPX006": 1}


def test_update_baseline_entries_sorted_by_path_rule_key(tmp_path):
    bad_a = tmp_path / "a_mod.py"
    bad_b = tmp_path / "b_mod.py"
    bad_b.write_text(BAD_MIXED)
    bad_a.write_text(BAD_MIXED)
    base = tmp_path / "base.json"
    # feed paths b-first: the emitted entries must still come out in
    # (path, rule, message) order so baseline diffs are reviewable
    assert cli_main([str(bad_b), str(bad_a), "--baseline", str(base),
                     "--update-baseline"]) == 0
    entries = json.loads(base.read_text())["entries"]
    keys = [(e["path"], e["rule"], e["message"]) for e in entries]
    assert keys == sorted(keys)
    assert len({e["path"] for e in entries}) == 2


# ---------------------------------------------------------------------------
# The CI gate script + its perf budget
# ---------------------------------------------------------------------------

def test_lint_gate_script_passes_on_real_tree():
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint.py")],
        cwd=os.path.dirname(REPO) or "/", capture_output=True,
        text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    # github format on a clean tree: no annotations at all
    assert proc.stdout.strip() == ""


def test_three_tier_run_one_parse_per_file_under_budget():
    before = parse_count()
    t0 = time.monotonic()
    res = lint_paths([os.path.join(REPO, "hpx_tpu")],
                     rules=all_rules())
    elapsed = time.monotonic() - t0
    assert parse_count() - before == res.checked_files
    assert elapsed < 15.0, f"three-tier run took {elapsed:.1f}s"


def test_dataflow_index_shares_parsed_trees():
    srcs = {"hpx_tpu/svc/fix.py": HPX019_BAD,
            "hpx_tpu/models/fix.py": HPX020_BAD}
    ctxs = [FileContext(s, p) for p, s in srcs.items()]
    before = parse_count()
    dfx = DataflowIndex(ProjectIndex(ctxs))
    for p in srcs:
        dfx.file_dataflow(p)
    assert parse_count() == before  # def-use built on the shared ASTs
