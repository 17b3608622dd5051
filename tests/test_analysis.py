"""hpxlint static-analysis framework tests.

Each rule gets a minimal fixture that fires exactly once, plus the
corrected form of the same code that stays silent — the pair pins both
the detection AND the fix the rule's message recommends. The suite also
covers the suppression directives, the baseline mechanism, and (as the
lint gate) runs the real CLI over the real tree: a new finding anywhere
in hpx_tpu/ fails this file.
"""

import json
import os
import subprocess
import sys

import pytest

from hpx_tpu.analysis import (
    Finding,
    all_rules,
    apply_baseline,
    lint_paths,
    lint_source,
    lint_sources,
)
from hpx_tpu.analysis.cli import main as cli_main
from hpx_tpu.analysis.engine import Suppressions, load_baseline, parse_count

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def findings(source, path="hpx_tpu/exec/fixture.py", select=None):
    res = lint_source(source, path, rules=all_rules(select))
    return res.findings


def rules_of(fs):
    return [f.rule for f in fs]


# ---------------------------------------------------------------------------
# HPX001 — future wait under a registered lock
# ---------------------------------------------------------------------------

HPX001_BAD = """\
from hpx_tpu.synchronization import Mutex

_lock = Mutex()

def drain(f):
    with _lock:
        return f.get()
"""

HPX001_GOOD = """\
from hpx_tpu.synchronization import Mutex

_lock = Mutex()

def drain(f):
    with _lock:
        pending = f
    return pending.get()
"""


def test_hpx001_fires_once():
    fs = findings(HPX001_BAD)
    assert rules_of(fs) == ["HPX001"]
    assert "_lock" in fs[0].message


def test_hpx001_silent_after_fix():
    assert findings(HPX001_GOOD) == []


def test_hpx001_self_attribute_lock():
    src = (
        "from hpx_tpu.synchronization import Spinlock\n"
        "class Q:\n"
        "    def __init__(self):\n"
        "        self._mu = Spinlock()\n"
        "    def pop(self, f):\n"
        "        with self._mu:\n"
        "            f.wait()\n"
    )
    assert rules_of(findings(src)) == ["HPX001"]


def test_hpx001_ignores_unregistered_lock():
    # only Mutex/Spinlock/SharedMutex register with VERIFY_LOCKS; a
    # plain object with a context manager is out of scope (HPX004's job)
    src = "with open('x') as fh:\n    f.get()\n"
    assert findings(src) == []


# ---------------------------------------------------------------------------
# HPX002 — host-device sync in hot-path modules
# ---------------------------------------------------------------------------

HPX002_BAD = """\
import numpy as np

def gather(device_arr):
    return np.asarray(device_arr)
"""

HPX002_GOOD = """\
import jax.numpy as jnp

def gather(device_arr):
    return jnp.asarray(device_arr)
"""


def test_hpx002_fires_once():
    fs = findings(HPX002_BAD, path="hpx_tpu/algo/fixture.py")
    assert rules_of(fs) == ["HPX002"]


def test_hpx002_jnp_asarray_is_not_numpy():
    # alias resolution must distinguish np->numpy from jnp->jax.numpy
    assert findings(HPX002_GOOD, path="hpx_tpu/algo/fixture.py") == []


def test_hpx002_only_in_hot_subpaths():
    assert findings(HPX002_BAD, path="hpx_tpu/svc/fixture.py") == []


def test_hpx002_block_until_ready_and_item():
    src = "def f(x):\n    x.block_until_ready()\n    return x.item()\n"
    fs = findings(src, path="hpx_tpu/futures/fixture.py")
    assert rules_of(fs) == ["HPX002", "HPX002"]


# ---------------------------------------------------------------------------
# HPX003 — dropped future
# ---------------------------------------------------------------------------

HPX003_BAD = """\
from hpx_tpu.futures.async_ import async_

def kick(fn):
    async_(fn)
"""

HPX003_GOOD = """\
from hpx_tpu.futures.async_ import async_

def kick(fn):
    return async_(fn)
"""


def test_hpx003_fires_once():
    assert rules_of(findings(HPX003_BAD)) == ["HPX003"]


def test_hpx003_silent_when_kept():
    assert findings(HPX003_GOOD) == []


def test_hpx003_dropped_then():
    src = "def chain(f):\n    f.then(print)\n"
    assert rules_of(findings(src)) == ["HPX003"]


def test_hpx003_post_is_fire_and_forget():
    # post() returns None by design — not a dropped future
    src = ("from hpx_tpu.futures.async_ import post\n"
           "def kick(fn):\n"
           "    post(fn)\n")
    assert findings(src) == []


# ---------------------------------------------------------------------------
# HPX004 — raw primitives where registered ones are required
# ---------------------------------------------------------------------------

HPX004_BAD = """\
import threading

_lock = threading.Lock()
"""

HPX004_GOOD = """\
from hpx_tpu.synchronization import Mutex

_lock = Mutex()
"""


def test_hpx004_fires_once():
    fs = findings(HPX004_BAD, path="hpx_tpu/svc/fixture.py")
    assert rules_of(fs) == ["HPX004"]
    assert "Mutex" in fs[0].message


def test_hpx004_silent_after_fix():
    assert findings(HPX004_GOOD, path="hpx_tpu/svc/fixture.py") == []


def test_hpx004_exempt_below_synchronization():
    # futures/runtime/core sit BELOW synchronization in the import graph
    # and must keep raw primitives
    assert findings(HPX004_BAD, path="hpx_tpu/futures/fixture.py") == []
    assert findings(HPX004_BAD, path="hpx_tpu/runtime/fixture.py") == []


def test_hpx004_time_sleep():
    src = "import time\n\ndef nap():\n    time.sleep(1)\n"
    fs = findings(src, path="hpx_tpu/dist/fixture.py")
    assert rules_of(fs) == ["HPX004"]


# ---------------------------------------------------------------------------
# HPX005 — jit in a loop
# ---------------------------------------------------------------------------

HPX005_BAD = """\
import jax

def run(xs):
    for x in xs:
        y = jax.jit(lambda v: v + 1)(x)
    return y
"""

HPX005_GOOD = """\
import jax

def run(xs):
    step = jax.jit(lambda v: v + 1)
    for x in xs:
        y = step(x)
    return y
"""


def test_hpx005_fires_once():
    fs = findings(HPX005_BAD)
    assert rules_of(fs) == ["HPX005"]
    assert fs[0].severity == "warning"


def test_hpx005_silent_when_hoisted():
    assert findings(HPX005_GOOD) == []


# ---------------------------------------------------------------------------
# HPX006 — bare except
# ---------------------------------------------------------------------------

HPX006_BAD = "try:\n    x = 1\nexcept:\n    pass\n"
HPX006_GOOD = "try:\n    x = 1\nexcept Exception:\n    pass\n"


def test_hpx006_fires_once():
    assert rules_of(findings(HPX006_BAD)) == ["HPX006"]


def test_hpx006_silent_with_type():
    assert findings(HPX006_GOOD) == []


# ---------------------------------------------------------------------------
# HPX007 — span context manager discarded
# ---------------------------------------------------------------------------

HPX007_BAD = """\
from hpx_tpu.svc import tracing

def phase():
    tracing.span("phase", "serving", step=1)
    work()
"""

HPX007_GOOD = """\
from hpx_tpu.svc import tracing

def phase():
    with tracing.span("phase", "serving", step=1):
        work()
    tracing.instant("phase.done", "serving")
"""


def test_hpx007_fires_once():
    assert rules_of(findings(HPX007_BAD)) == ["HPX007"]


def test_hpx007_silent_with_with():
    assert findings(HPX007_GOOD) == []


def test_hpx007_annotate_statement():
    src = ("from hpx_tpu.svc.profiling import annotate\n"
           "def f():\n"
           "    annotate('region')\n")
    assert rules_of(findings(src)) == ["HPX007"]


def test_hpx007_kept_result_is_silent():
    # binding the manager (entered later / passed on) is fine
    src = ("def f(tracer):\n"
           "    s = tracer.span('x')\n"
           "    return s\n")
    assert findings(src) == []


# ---------------------------------------------------------------------------
# HPX008 — program cache keyed on a raw dynamic length
# ---------------------------------------------------------------------------

HPX008_BAD = """\
from hpx_tpu.models.transformer import _cached_program
def prefill(params, prompt, cfg):
    plen = len(prompt)
    ck = ("prefill", cfg, plen)
    return _cached_program(ck, lambda: None)
"""

HPX008_GOOD = """\
from hpx_tpu.models.transformer import _cached_program
def prefill(params, prompt, cfg, buckets):
    width = next(w for w in buckets if w >= len(prompt))
    ck = ("prefill", cfg, width)
    return _cached_program(ck, lambda: None)
"""


def test_hpx008_len_keyed_cache_fires():
    fs = findings(HPX008_BAD)
    assert rules_of(fs) == ["HPX008"]
    assert "'plen'" in fs[0].message


def test_hpx008_bucketed_key_is_silent():
    assert findings(HPX008_GOOD) == []


def test_hpx008_shape_unpack_and_inline_tuple():
    # `b, n = x.shape` taints both names; the key tuple may also be
    # passed inline and carry a bare `.shape` read
    src = ("from hpx_tpu.core.programs import cached_program\n"
           "P = {}\n"
           "def run(x, cfg):\n"
           "    b, n = x.shape\n"
           "    return cached_program(P, (cfg, n, x.shape),\n"
           "                          lambda: None)\n")
    fs = findings(src)
    assert rules_of(fs) == ["HPX008", "HPX008"]


def test_hpx008_two_call_sites_report_once():
    # one key construction feeding mesh/no-mesh branches is ONE finding
    src = ("from hpx_tpu.models.transformer import _cached_program\n"
           "def gen(params, prompt, cfg, mesh):\n"
           "    plen = len(prompt)\n"
           "    ck = ('gen', cfg, plen)\n"
           "    if mesh is None:\n"
           "        return _cached_program(ck, lambda: None)\n"
           "    return _cached_program(ck, lambda: None)\n")
    assert rules_of(findings(src)) == ["HPX008"]


def test_hpx008_static_key_is_silent():
    src = ("from hpx_tpu.core.programs import cached_program\n"
           "P = {}\n"
           "def run(v, mesh, axis):\n"
           "    return cached_program(P, ('sort', mesh, axis),\n"
           "                          lambda: None)\n")
    assert findings(src) == []


# ---------------------------------------------------------------------------
# HPX009 — host sync on draft/verify intermediates in the serving hot loop
# ---------------------------------------------------------------------------

SERVING_PATH = "hpx_tpu/models/serving.py"

HPX009_BAD = """\
import numpy as np
class ContinuousServer:
    def _spec_step(self, live):
        packed = self._paged_verify_prog(4)(None)
        vals = np.asarray(packed)
        return vals
"""

HPX009_GOOD = """\
import numpy as np
class ContinuousServer:
    def _finish_prefill(self, slot, req):
        # outside the hot set: prefill boundary syncs are expected
        first = np.asarray(req.first_logits)
        return first
"""


def test_hpx009_asarray_in_hot_loop_fires():
    fs = findings(HPX009_BAD, path=SERVING_PATH)
    assert rules_of(fs) == ["HPX009"]
    assert "_spec_step()" in fs[0].message


def test_hpx009_item_and_device_get_fire():
    src = ("import jax\n"
           "class ContinuousServer:\n"
           "    def step(self):\n"
           "        acc = self._acc_dev.item()\n"
           "        tgt = jax.device_get(self._tgt_dev)\n"
           "        return acc, tgt\n")
    fs = findings(src, path=SERVING_PATH)
    assert rules_of(fs) == ["HPX009", "HPX009"]


def test_hpx009_non_hot_function_is_silent():
    assert findings(HPX009_GOOD, path=SERVING_PATH) == []


def test_hpx009_outside_serving_path_is_silent():
    assert findings(HPX009_BAD, path="hpx_tpu/models/other.py") == []


def test_hpx009_nested_def_not_attributed_to_hot_parent():
    # a helper DEFINED inside a hot function is not the hot loop
    # itself (it runs wherever it is called; builders run at compile)
    src = ("import numpy as np\n"
           "class ContinuousServer:\n"
           "    def _spec_step(self, live):\n"
           "        def build():\n"
           "            return np.asarray([1, 2])\n"
           "        return build\n")
    assert findings(src, path=SERVING_PATH) == []


# ---------------------------------------------------------------------------
# HPX010 — full-pool gather outside the paged-attention oracle module
# ---------------------------------------------------------------------------

HPX010_BAD = """\
def decode_rows(x, k_pool, v_pool, table):
    k = k_pool[table]
    v = v_pool[table]
    return x, k, v
"""

HPX010_GOOD = """\
from hpx_tpu.ops.paged_attention import paged_decode_attention

def decode_rows(x, k_pool, v_pool, table, pos):
    return paged_decode_attention(x, k_pool, v_pool, table, pos,
                                  fused=True)
"""


def test_hpx010_fires_per_gather():
    fs = findings(HPX010_BAD, path=SERVING_PATH)
    assert rules_of(fs) == ["HPX010", "HPX010"]
    assert "'k_pool[table]'" in fs[0].message


def test_hpx010_fused_route_is_silent():
    assert findings(HPX010_GOOD, path=SERVING_PATH) == []


def test_hpx010_bounded_reads_are_silent():
    # plural `pools` is the host per-layer list; constant subscripts
    # read O(1) blocks; `.at[...]` chains are scatters, not gathers
    src = ("def f(pools, pool, bidx, vals):\n"
           "    kp, vp = pools[0]\n"
           "    head = pool[0]\n"
           "    return kp, vp, head, pool.at[bidx].set(vals)\n")
    assert findings(src, path=SERVING_PATH) == []


def test_hpx010_outside_paged_hot_paths_is_silent():
    assert findings(HPX010_BAD, path="hpx_tpu/svc/fixture.py") == []


def test_hpx010_oracle_sites_are_baselined():
    # the oracle module's two gathers (reference gather + quantized
    # frontier RMW) fire and are absorbed — with justification — by
    # the shipped baseline; a third would fail the gate
    res = lint_paths(
        [os.path.join(REPO, "hpx_tpu", "ops", "paged_attention.py")],
        rules=all_rules(["HPX010"]))
    assert len(res.findings) == 2
    new, matched = apply_baseline(res.findings, load_baseline())
    assert new == [] and matched == 2


# ---------------------------------------------------------------------------
# HPX011 — naked retry loops / broad-except swallowing in models+dist
# ---------------------------------------------------------------------------

HPX011_RETRY_BAD = """\
def fetch(conn):
    for attempt in range(5):
        try:
            return conn.read()
        except IOError:
            continue
"""

HPX011_RETRY_GOOD = """\
from hpx_tpu.exec.execution_base import suspend

def fetch(conn):
    for attempt in range(5):
        try:
            return conn.read()
        except IOError:
            suspend(0.01 * attempt)
            continue
"""

HPX011_SWALLOW_BAD = """\
def close(srv):
    try:
        srv.stop()
    except Exception:
        pass
"""


def test_hpx011_retry_without_backoff_fires():
    fs = findings(HPX011_RETRY_BAD, path="hpx_tpu/models/fixture.py")
    assert rules_of(fs) == ["HPX011"]
    assert "fetch()" in fs[0].message and "backoff" in fs[0].message


def test_hpx011_backoff_between_attempts_is_silent():
    assert findings(HPX011_RETRY_GOOD,
                    path="hpx_tpu/models/fixture.py") == []


def test_hpx011_sync_replay_route_is_silent():
    src = ("from hpx_tpu.svc.resiliency import sync_replay\n"
           "def fetch(conn):\n"
           "    return sync_replay(5, conn.read, backoff_s=0.01)\n")
    assert findings(src, path="hpx_tpu/models/fixture.py") == []


def test_hpx011_while_retry_fires():
    src = ("def poke(res):\n"
           "    while True:\n"
           "        try:\n"
           "            return res.acquire_()\n"
           "        except KeyError:\n"
           "            continue\n")
    fs = findings(src, path="hpx_tpu/dist/fixture.py")
    assert rules_of(fs) == ["HPX011"]


def test_hpx011_data_loop_error_isolation_is_silent():
    # a for over a DATA collection with per-item try is isolation,
    # not a retry of the same operation (dist.runtime's counter dump)
    src = ("def dump(patterns):\n"
           "    for p in patterns:\n"
           "        try:\n"
           "            print(p)\n"
           "        except ValueError:\n"
           "            continue\n")
    assert findings(src, path="hpx_tpu/dist/fixture.py") == []


def test_hpx011_broad_swallow_fires():
    fs = findings(HPX011_SWALLOW_BAD, path="hpx_tpu/models/fixture.py")
    assert rules_of(fs) == ["HPX011"]
    assert "close()" in fs[0].message


def test_hpx011_typed_or_handled_except_is_silent():
    # a typed except, and a broad one that actually DOES something,
    # are both fine — only pass-only Exception swallows fire
    src = ("def close(srv, log):\n"
           "    try:\n"
           "        srv.stop()\n"
           "    except ValueError:\n"
           "        pass\n"
           "    try:\n"
           "        srv.join()\n"
           "    except Exception as e:\n"
           "        log.warn(e)\n")
    assert findings(src, path="hpx_tpu/models/fixture.py",
                    select=["HPX011"]) == []


def test_hpx011_outside_resiliency_layers_is_silent():
    assert findings(HPX011_RETRY_BAD,
                    path="hpx_tpu/svc/fixture.py") == []
    assert findings(HPX011_SWALLOW_BAD,
                    path="hpx_tpu/algo/fixture.py",
                    select=["HPX011"]) == []


# ---------------------------------------------------------------------------
# engine: suppressions, syntax errors, baseline
# ---------------------------------------------------------------------------

def test_suppress_same_line():
    src = "try:\n    x = 1\nexcept:  # hpxlint: disable=HPX006 — why\n    pass\n"
    assert findings(src) == []


def test_suppress_next_line():
    src = ("try:\n    x = 1\n"
           "# hpxlint: disable-next=HPX006 — reason\n"
           "except:\n    pass\n")
    assert findings(src) == []


def test_suppress_next_skips_continuation_comments():
    # a multi-line justification must not swallow the directive
    src = ("try:\n    x = 1\n"
           "# hpxlint: disable-next=HPX006 — a justification that\n"
           "# spans several comment lines before the code\n"
           "except:\n    pass\n")
    assert findings(src) == []


def test_suppress_whole_file():
    src = "# hpxlint: disable-file=HPX006\ntry:\n    x=1\nexcept:\n    pass\n"
    assert findings(src) == []


def test_suppress_by_rule_name_and_all():
    by_name = "try:\n    x=1\nexcept:  # hpxlint: disable=bare-except\n    pass\n"
    assert findings(by_name) == []
    by_all = "try:\n    x=1\nexcept:  # hpxlint: disable=all\n    pass\n"
    assert findings(by_all) == []


def test_suppress_wrong_rule_does_not_apply():
    src = "try:\n    x=1\nexcept:  # hpxlint: disable=HPX004\n    pass\n"
    assert rules_of(findings(src)) == ["HPX006"]


def test_suppressions_counted():
    src = "try:\n    x=1\nexcept:  # hpxlint: disable=HPX006\n    pass\n"
    res = lint_source(src, "hpx_tpu/fixture.py", rules=all_rules())
    assert res.suppressed == 1


def test_syntax_error_is_a_finding():
    fs = findings("def broken(:\n")
    assert rules_of(fs) == ["HPX000"]


def test_baseline_roundtrip(tmp_path):
    fs = findings(HPX006_BAD)
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"entries": [{
        "path": "hpx_tpu/exec/fixture.py",
        "rule": "HPX006",
        "message": fs[0].message,
        "count": 1,
        "justification": "fixture",
    }]}))
    new, matched = apply_baseline(fs, load_baseline(str(path)))
    assert new == [] and matched == 1
    # a second identical finding exceeds the baselined count -> new
    new2, matched2 = apply_baseline(fs + fs, load_baseline(str(path)))
    assert len(new2) == 1 and matched2 == 1


def test_baseline_does_not_match_other_files(tmp_path):
    fs = findings(HPX006_BAD, path="hpx_tpu/other.py")
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"entries": [{
        "path": "hpx_tpu/exec/fixture.py", "rule": "HPX006",
        "message": fs[0].message, "count": 1,
        "justification": "fixture"}]}))
    new, matched = apply_baseline(fs, load_baseline(str(path)))
    assert len(new) == 1 and matched == 0


def test_select_rules():
    src = HPX006_BAD + "\nimport threading\n_l = threading.Lock()\n"
    only6 = findings(src, path="hpx_tpu/svc/fixture.py", select=["HPX006"])
    assert rules_of(only6) == ["HPX006"]


def test_finding_format():
    f = Finding(rule="HPX006", severity="error", path="a/b.py",
                line=3, col=0, message="m")
    assert f.format() == "a/b.py:3:0: HPX006 [error] m"


# ---------------------------------------------------------------------------
# HPX012 — unbounded get() on a remote action future
# ---------------------------------------------------------------------------

HPX012_BAD_CHAINED = """\
from hpx_tpu.dist.actions import async_action

def fetch(loc):
    return async_action("act", loc, 1).get()
"""

HPX012_BAD_VIA_NAME = """\
from hpx_tpu.dist.actions import async_action

def fetch(loc):
    f = async_action("act", loc, 1)
    prep()
    return f.get()
"""

HPX012_GOOD = """\
from hpx_tpu.dist.actions import async_action, resilient_action

def fetch(loc):
    a = async_action("act", loc, 1).get(5.0)       # bounded
    b = resilient_action("act", loc, 1,
                         timeout_s=5.0).get()      # policy owns it
    f = make_future()
    return a, b, f.get()                           # not a remote send
"""


def test_hpx012_flags_chained_unbounded_get():
    fs = findings(HPX012_BAD_CHAINED, path="hpx_tpu/svc/fixture.py")
    assert rules_of(fs) == ["HPX012"]
    assert "resilient_action" in fs[0].message


def test_hpx012_flags_named_future_get():
    fs = findings(HPX012_BAD_VIA_NAME, path="hpx_tpu/svc/fixture.py")
    assert rules_of(fs) == ["HPX012"]


def test_hpx012_clean_shapes():
    assert findings(HPX012_GOOD, path="hpx_tpu/svc/fixture.py") == []


def test_hpx012_skips_tests():
    assert findings(HPX012_BAD_CHAINED,
                    path="tests/test_fixture.py") == []


# ---------------------------------------------------------------------------
# HPX016 — counter-name grammar + dropped histogram timers
# ---------------------------------------------------------------------------

HPX016_BAD_NAME = """\
from hpx_tpu.svc.performance_counters import query_counter

def scrape():
    return query_counter("/serving/locality#0/ttft-p99")
"""

HPX016_BAD_FRAGMENT = """\
from hpx_tpu.svc.performance_counters import counter_name

def name():
    return counter_name("serving", "latency/{oops}")
"""

HPX016_BAD_DROPPED = """\
def observe(h):
    h.record()
    return h
"""

HPX016_GOOD = """\
from hpx_tpu.svc.performance_counters import counter_name, query_counter

def scrape():
    return query_counter("/serving{locality#0/total}/latency/ttft-s/p99")

def name():
    return counter_name("serving", "latency/ttft-s")

def observe(h):
    h.record(0.25)
    with h.record():
        pass
    return h
"""


def test_hpx016_malformed_full_name():
    fs = findings(HPX016_BAD_NAME, path="hpx_tpu/svc/fixture.py")
    assert rules_of(fs) == ["HPX016"]
    assert "grammar" in fs[0].message or "counter name" in fs[0].message


def test_hpx016_malformed_fragments():
    fs = findings(HPX016_BAD_FRAGMENT, path="hpx_tpu/svc/fixture.py")
    assert rules_of(fs) == ["HPX016"]


def test_hpx016_dropped_timer():
    fs = findings(HPX016_BAD_DROPPED, path="hpx_tpu/svc/fixture.py")
    assert rules_of(fs) == ["HPX016"]
    assert "record" in fs[0].message


def test_hpx016_silent_after_fix():
    assert findings(HPX016_GOOD, path="hpx_tpu/svc/fixture.py") == []


def test_hpx016_skips_tests():
    assert findings(HPX016_BAD_DROPPED,
                    path="tests/test_fixture.py") == []


# ---------------------------------------------------------------------------
# HPX017 — raw jit outside the profiled program-cache funnel
# ---------------------------------------------------------------------------

HPX017_BAD = """\
import jax

def decode_step(params, tok):
    prog = jax.jit(lambda p, t: p @ t)
    return prog(params, tok)
"""

HPX017_BAD_DECORATOR = """\
import jax

@jax.jit
def decode_step(params, tok):
    return params @ tok
"""

HPX017_GOOD = """\
import jax
from hpx_tpu.core.programs import cached_program

_PROGRAMS = {}

def _cached_program(key, build):
    return cached_program(_PROGRAMS, key, build)

def decode_step_lambda(params, tok):
    prog = _cached_program(("step", 128),
                           lambda: jax.jit(lambda p, t: p @ t))
    return prog(params, tok)

def decode_step_named(params, tok):
    def build():
        def step(p, t):
            return p @ t
        return jax.jit(step, donate_argnums=(0,))
    prog = cached_program(_PROGRAMS, ("step2",), build)
    return prog(params, tok)
"""


def test_hpx017_raw_jit_call():
    fs = findings(HPX017_BAD, path="hpx_tpu/models/fixture.py")
    assert rules_of(fs) == ["HPX017"]
    assert "decode_step" in fs[0].message


def test_hpx017_raw_jit_decorator():
    fs = findings(HPX017_BAD_DECORATOR,
                  path="hpx_tpu/models/fixture.py")
    assert rules_of(fs) == ["HPX017"]


def test_hpx017_silent_through_cache_funnel():
    assert findings(HPX017_GOOD,
                    path="hpx_tpu/models/fixture.py") == []


def test_hpx017_scoped_to_models_and_ops():
    # same source outside models//ops/ is silent — the funnel is a
    # serving-hot-path discipline, not a repo-wide jit ban
    assert findings(HPX017_BAD, path="hpx_tpu/svc/fixture.py") == []
    fs = findings(HPX017_BAD, path="hpx_tpu/ops/fixture.py")
    assert rules_of(fs) == ["HPX017"]


# ---------------------------------------------------------------------------
# HPX018 — reloadable knob mutated outside the config actuation path
# ---------------------------------------------------------------------------

HPX018_BAD = """\
class Server:
    def __init__(self):
        self.prefill_chunk = 64

    def go_faster(self):
        self.prefill_chunk = 512
        self._spec_k += 1
"""

HPX018_GOOD = """\
class Server:
    def __init__(self):
        self.prefill_chunk = 64
        self._spec_k = 4

    def _reload_knobs(self):
        self.prefill_chunk = 512
        self._spec_k = 5

    def go_faster(self, rc):
        rc.set("hpx.serving.prefill_chunk", "512")
"""


def test_hpx018_fires_on_unsanctioned_write():
    fs = findings(HPX018_BAD, path="hpx_tpu/models/fixture.py")
    assert rules_of(fs) == ["HPX018", "HPX018"]
    assert "prefill_chunk" in fs[0].message
    assert "hpx.serving.prefill_chunk" in fs[0].message
    assert "go_faster" in fs[0].message
    assert "_spec_k" in fs[1].message


def test_hpx018_silent_on_actuation_path():
    assert findings(HPX018_GOOD,
                    path="hpx_tpu/models/fixture.py") == []
    assert findings(HPX018_GOOD, path="hpx_tpu/svc/fixture.py") == []


def test_hpx018_scope():
    # svc/ is in scope, no file of it exempt; layers outside
    # models//svc/ (e.g. cache/radix's budget_blocks __init__) are
    # out of scope
    fs = findings(HPX018_BAD, path="hpx_tpu/svc/fixture.py")
    assert rules_of(fs) == ["HPX018", "HPX018"]
    assert findings(HPX018_BAD, path="hpx_tpu/cache/fixture.py") == []


def test_hpx018_real_tree_is_clean():
    # ground truth for the rule shipping with an empty baseline: the
    # only in-tree writes to the reloadable knobs' attrs are
    # construction and _reload_knobs
    res = lint_paths([os.path.join(REPO, "hpx_tpu")],
                     rules=all_rules(["HPX018"]))
    assert [f.rule for f in res.findings] == []


def test_hpx017_github_gate_on_real_tree(capsys):
    # the tier-1 gate invocation CI uses: the shipped tree must be
    # clean under the baseline with --format=github (annotations would
    # otherwise land on the PR)
    assert cli_main([os.path.join(REPO, "hpx_tpu"),
                     "--format=github"]) == 0
    assert capsys.readouterr().out == ""


HPX024_BAD = """\
def make_worker(params, cfg, block_size=16):
    return Worker(params, cfg, block_size)

def boot(params, cfg):
    return Server(params, cfg, spec_k=8,
                  prefill_buckets=[8, 16, 32, 64, 128])
"""

HPX024_GOOD = """\
def make_worker(params, cfg, block_size=None):
    if block_size is None:
        block_size = resolve_paged_block(cfg.head_dim)
    return Worker(params, cfg, block_size)

def boot(params, cfg, rc, chunk):
    k = rc.get_int("hpx.serving.spec.k", 4)
    return Server(params, cfg, spec_k=k,
                  prefill_buckets=_resolve_buckets("auto", chunk))
"""


def test_hpx024_fires_on_baked_shape_literals():
    fs = findings(HPX024_BAD, path="hpx_tpu/models/fixture.py")
    assert rules_of(fs) == ["HPX024", "HPX024", "HPX024"]
    assert "block_size" in fs[0].message
    assert "make_worker" in fs[0].message
    assert "resolve_paged_block" in fs[0].message
    assert "spec_k" in fs[1].message
    assert "prefill_buckets" in fs[2].message


def test_hpx024_silent_on_resolver_chain():
    assert findings(HPX024_GOOD,
                    path="hpx_tpu/models/fixture.py") == []


def test_hpx024_scope():
    # models/, svc/ and ops/ carry the serving geometry; layers
    # outside them (exec/, algo/) may bake shapes freely
    assert rules_of(findings(
        HPX024_BAD, path="hpx_tpu/svc/fixture.py")) == ["HPX024"] * 3
    assert rules_of(findings(
        HPX024_BAD, path="hpx_tpu/ops/fixture.py")) == ["HPX024"] * 3
    assert findings(HPX024_BAD) == []  # default exec/ path


def test_hpx024_real_tree_is_clean():
    # ground truth: the shipped models//svc//ops layers resolve every
    # shape knob through its config key (PrefillWorker's block_size
    # routes through resolve_paged_block)
    res = lint_paths([os.path.join(REPO, "hpx_tpu")],
                     rules=all_rules(["HPX024"]))
    assert [f.rule for f in res.findings] == []


def test_all_rules_registry():
    ids = sorted(r.id for r in all_rules())
    assert ids == ["HPX001", "HPX002", "HPX003", "HPX004",
                   "HPX005", "HPX006", "HPX007", "HPX008",
                   "HPX009", "HPX010", "HPX011", "HPX012",
                   "HPX013", "HPX014", "HPX015", "HPX016",
                   "HPX017", "HPX018", "HPX019", "HPX020",
                   "HPX021", "HPX022", "HPX023", "HPX024"]


def test_rule_registry_completeness(capsys):
    """Every rule must document itself consistently in all four places
    a reader finds it: the class docstring, the README lint table,
    --list-rules output, and the project/file tier split."""
    readme = open(os.path.join(REPO, "README.md"), encoding="utf-8").read()
    assert cli_main(["--list-rules"]) == 0
    listed = capsys.readouterr().out
    for rule in all_rules():
        doc = (type(rule).__doc__ or "")
        assert doc.strip().startswith(f"{rule.id}: "), rule.id
        assert f"| {rule.id} | {rule.name} |" in readme, \
            f"{rule.id} missing from the README lint table"
        assert rule.id in listed
    project_ids = {r.id for r in all_rules() if r.scope == "project"}
    assert project_ids == {"HPX013", "HPX014", "HPX015", "HPX023"}
    dataflow_ids = {r.id for r in all_rules() if r.scope == "dataflow"}
    assert dataflow_ids == {"HPX019", "HPX020", "HPX021", "HPX022"}


# ---------------------------------------------------------------------------
# HPX013 — cross-module lock-order inversion (whole-program tier)
# ---------------------------------------------------------------------------

HPX013_A = """\
from hpx_tpu.synchronization import Mutex
from hpx_tpu.svc import b

_a = Mutex()

def outer():
    with _a:
        b.grab()

def touch():
    with _a:
        pass
"""

HPX013_B_CYCLE = """\
from hpx_tpu.synchronization import Mutex
from hpx_tpu.svc import a

_b = Mutex()

def grab():
    with _b:
        pass

def reverse():
    with _b:
        a.touch()
"""

HPX013_B_ORDERED = """\
from hpx_tpu.synchronization import Mutex

_b = Mutex()

def grab():
    with _b:
        pass
"""


def test_hpx013_two_file_cycle_fires_with_both_witnesses():
    res = lint_sources({"hpx_tpu/svc/a.py": HPX013_A,
                        "hpx_tpu/svc/b.py": HPX013_B_CYCLE},
                       rules=all_rules(["HPX013"]))
    assert rules_of(res.findings) == ["HPX013"]
    msg = res.findings[0].message
    # both witness call chains, each naming the functions on the path
    assert "hpx_tpu.svc.a:outer -> hpx_tpu.svc.b:grab" in msg
    assert "hpx_tpu.svc.b:reverse -> hpx_tpu.svc.a:touch" in msg


def test_hpx013_consistent_order_is_silent():
    res = lint_sources({"hpx_tpu/svc/a.py": HPX013_A,
                        "hpx_tpu/svc/b.py": HPX013_B_ORDERED},
                       rules=all_rules(["HPX013"]))
    assert res.findings == []


def test_hpx013_single_file_nested_inversion_fires():
    src = """\
from hpx_tpu.synchronization import Mutex

_x = Mutex()
_y = Mutex()

def forward():
    with _x:
        with _y:
            pass

def backward():
    with _y:
        with _x:
            pass
"""
    res = lint_sources({"hpx_tpu/svc/m.py": src},
                       rules=all_rules(["HPX013"]))
    assert rules_of(res.findings) == ["HPX013"]


# ---------------------------------------------------------------------------
# HPX012/HPX013 coverage over the fleet module's shapes
# ---------------------------------------------------------------------------

HPX012_FLEET_BAD = """\
from hpx_tpu.dist.actions import async_action

class Router:
    def _digest(self, loc):
        # the placement-loop digest pull: a hung worker must not
        # wedge the router, so a bare get() is exactly the bug
        return async_action("prefix_digest", loc, 64).get()
"""

HPX012_FLEET_GOOD = """\
from hpx_tpu.dist.actions import async_action

class Router:
    def _digest(self, loc):
        return async_action("prefix_digest", loc, 64).get(0.25)
"""


def test_hpx012_flags_fleet_style_digest_pull():
    fs = findings(HPX012_FLEET_BAD, path="hpx_tpu/svc/fleet_fx.py")
    assert rules_of(fs) == ["HPX012"]
    assert findings(HPX012_FLEET_GOOD,
                    path="hpx_tpu/svc/fleet_fx.py") == []


def test_hpx013_fleet_instance_lock_inversion_fires():
    # fleet-shaped: the router's bookkeeping lock (an instance-attr
    # Mutex, like FleetRouter._fl_lock) inverted against a worker
    # module's lock must still be a whole-tree lock identity
    src = """\
from hpx_tpu.synchronization import Mutex

class Router:
    def __init__(self):
        self._fl_lock = Mutex()
        self._pool_lock = Mutex()

    def place(self):
        with self._fl_lock:
            with self._pool_lock:
                pass

    def retire(self):
        with self._pool_lock:
            with self._fl_lock:
                pass
"""
    res = lint_sources({"hpx_tpu/svc/fleet_fx.py": src},
                       rules=all_rules(["HPX013"]))
    assert rules_of(res.findings) == ["HPX013"]


def test_project_index_has_fleet_router_lock():
    # the real tree: HPX013's index must see svc/fleet's bookkeeping
    # lock, so fleet code is inside the lock-order contract
    from hpx_tpu.analysis.engine import FileContext
    from hpx_tpu.analysis.project import ProjectIndex
    path = os.path.join(REPO, "hpx_tpu", "svc", "fleet.py")
    with open(path) as fh:
        ctx = FileContext(fh.read(), "hpx_tpu/svc/fleet.py")
    index = ProjectIndex([ctx])
    assert "hpx_tpu.svc.fleet.FleetRouter._fl_lock" in index.locks


# ---------------------------------------------------------------------------
# HPX014 — config keys must be declared in core/config_schema.py
# ---------------------------------------------------------------------------

HPX014_SCHEMA = """\
def declare(key, type, default=None, doc="", reserved=False):
    pass

declare("hpx.fix.workers", "int", "4", "worker count")
declare("hpx.fix.trace", "bool", "0", "tracing toggle")
declare("hpx.fix.dead", "str", "x", "never read anywhere")
declare("hpx.fix.parity", "str", None, "HPX parity", reserved=True)
"""

HPX014_READER = """\
def setup(cfg):
    n = cfg.get_int("hpx.fix.workers")
    t = cfg.get_int("hpx.fix.trace")
    z = cfg.get("hpx.fix.typo_key")
    return n, t, z
"""


def _hpx014(sources):
    res = lint_sources(sources, rules=all_rules(["HPX014"]))
    return res.findings


def test_hpx014_undeclared_read_type_mismatch_and_dead_key():
    fs = _hpx014({"hpx_tpu/core/config_schema.py": HPX014_SCHEMA,
                  "hpx_tpu/svc/reader.py": HPX014_READER})
    msgs = sorted(f.message for f in fs)
    assert len(fs) == 3
    assert any("'hpx.fix.typo_key' read via get() is not declared"
               in m for m in msgs)
    assert any("'hpx.fix.trace' is declared 'bool' but read via "
               "get_int()" in m for m in msgs)
    assert any("'hpx.fix.dead' is declared but never read" in m
               for m in msgs)


def test_hpx014_declared_and_reserved_keys_are_silent():
    clean = """\
def setup(cfg):
    n = cfg.get_int("hpx.fix.workers")
    t = cfg.get_bool("hpx.fix.trace")
    d = cfg.get("hpx.fix.dead")
    return n, t, d
"""
    assert _hpx014({"hpx_tpu/core/config_schema.py": HPX014_SCHEMA,
                    "hpx_tpu/svc/reader.py": clean}) == []


def test_hpx014_real_tree_schema_is_exhaustive():
    # the shipped registry declares every key the tree reads, exactly:
    # no undeclared reads, no dead keys (modulo reserved= parity keys)
    res = lint_paths([os.path.join(REPO, "hpx_tpu")],
                     rules=all_rules(["HPX014"]))
    assert res.findings == [], "\n".join(f.format() for f in res.findings)


# ---------------------------------------------------------------------------
# HPX015 — incref/pin balance on every exit path (cache/ + models/)
# ---------------------------------------------------------------------------

def _hpx015(source):
    res = lint_sources({"hpx_tpu/cache/fixture.py": source},
                       rules=all_rules(["HPX015"]))
    return res.findings


def test_hpx015_early_return_leak_fires():
    fs = _hpx015("""\
class Pool:
    def take(self, alloc, bid):
        alloc.incref(bid)
        if bid < 0:
            return None
        v = self.read(bid)
        alloc.decref(bid)
        return v
""")
    assert rules_of(fs) == ["HPX015"]
    assert "incref(bid) in Pool.take" in fs[0].message


def test_hpx015_try_finally_balance_is_silent():
    assert _hpx015("""\
class Pool:
    def take(self, alloc, bid):
        alloc.incref(bid)
        try:
            return self.read(bid)
        finally:
            alloc.decref(bid)
""") == []


def test_hpx015_leak_inside_try_still_fires():
    # the finally here does NOT release; the early return leaks
    fs = _hpx015("""\
class Pool:
    def take(self, alloc, bid):
        alloc.incref(bid)
        try:
            if bid < 0:
                return None
            v = self.read(bid)
        finally:
            self.log(bid)
        alloc.decref(bid)
        return v
""")
    assert rules_of(fs) == ["HPX015"]


def test_hpx015_pure_ownership_transfer_is_silent():
    # acquire-only functions hand the references to an owner that
    # retires them elsewhere (the _capture_slot / _restore_slot shape)
    assert _hpx015("""\
class Pool:
    def capture(self, alloc, pins):
        for bid in pins:
            alloc.incref(bid)
        return list(pins)
""") == []


def test_hpx015_loop_acquire_release_pairs_by_iterable():
    # pinning loop + releasing loop over DIFFERENT iterables: the keys
    # ("new.pins" vs "old.pins") keep the transfer exemption intact
    assert _hpx015("""\
class Pool:
    def swap(self, alloc, new, old):
        for bid in new.pins:
            alloc.incref(bid)
        for bid in old.pins:
            alloc.decref(bid)
""") == []


def test_hpx015_outside_scoped_layers_is_silent():
    res = lint_sources({"hpx_tpu/svc/fixture.py": """\
class Pool:
    def take(self, alloc, bid):
        alloc.incref(bid)
        return bid
"""}, rules=all_rules(["HPX015"]))
    assert res.findings == []


# the host-tier checkout family (cache/tier.py): checkout() acquires an
# entry, checkin() retires it, putback() is the abort-path release —
# same balance discipline, one tier down from incref/decref

def _hpx015_tier(source):
    res = lint_sources({"hpx_tpu/cache/tier.py": source},
                       rules=all_rules(["HPX015"]))
    return res.findings


def test_hpx015_tier_checkout_leak_fires():
    fs = _hpx015_tier("""\
class Promoter:
    def restore(self, tier, h, bad):
        tier.checkout(h)
        if bad:
            return 0
        tier.checkin(h)
        return 1
""")
    assert rules_of(fs) == ["HPX015"]
    assert "checkout(h) in Promoter.restore" in fs[0].message
    assert "checkin()" in fs[0].message


def test_hpx015_tier_putback_on_abort_is_silent():
    # putback balances the checkout on the abort path exactly like
    # checkin does on the success path
    assert _hpx015_tier("""\
class Promoter:
    def restore(self, tier, h, bad):
        tier.checkout(h)
        if bad:
            tier.putback(h)
            return 0
        tier.checkin(h)
        return 1
""") == []


def test_hpx015_tier_checkout_transfer_is_silent():
    # the real promotion shape: checkout(hash) returns an ENTRY that
    # is checked in under its own name — the differing operand keys
    # keep the ownership-transfer exemption intact
    assert _hpx015_tier("""\
class Promoter:
    def promote(self, tier, h, bad):
        e = tier.checkout(h)
        if e is None:
            return None
        if bad:
            tier.putback(e)
            return None
        tier.checkin(e)
        return e
""") == []


def test_hpx016_tier_counter_namespace_is_stable():
    """The /cache{...}/tier/* namespace is an observability contract:
    every leaf cache/counters.py registers for a tiered server must
    (a) still be registered under exactly that name and (b) parse
    under the HPX016 counter grammar — base names and the derived pNN
    quantile counters alike."""
    from hpx_tpu.analysis.rules import _COUNTER_NAME_RE
    from hpx_tpu.svc.metrics import configured_quantiles, quantile_label
    from hpx_tpu.svc.performance_counters import counter_name

    leaves = ["tier/bytes-held", "tier/entries",
              "tier/count/demoted", "tier/count/promoted",
              "tier/count/dropped", "tier/count/declined",
              "tier/hit-depth-blocks"]
    src = open(os.path.join(REPO, "hpx_tpu", "cache", "counters.py"),
               encoding="utf-8").read()
    for leaf in leaves + ["tier/promote-latency-s"]:
        assert f'"{leaf}"' in src, \
            f"{leaf!r} gone from cache/counters.py — the tier " \
            "counter namespace is pinned; rename both sides or don't"
    hist = ["tier/promote-latency-s"] + [
        f"tier/promote-latency-s/{quantile_label(q)}"
        for q in configured_quantiles()]
    for leaf in leaves + hist:
        name = counter_name("cache", leaf, "server#0", locality=0)
        assert _COUNTER_NAME_RE.match(name), name
    # and the literal form stays HPX016-clean at a query site
    assert findings(
        "from hpx_tpu.svc.performance_counters import query_counter\n"
        "def scrape():\n"
        "    return query_counter(\n"
        '        "/cache{locality#0/server#0}/tier/count/promoted")\n',
        path="hpx_tpu/svc/fixture.py") == []


def test_hpx016_moe_counter_namespace_is_stable():
    """The /serving{...}/moe/* namespace is an observability contract:
    the MoE decode counters cache/counters.py registers for an
    expert-routed server must (a) still be registered under exactly
    those names and (b) parse under the HPX016 counter grammar,
    including the per-expert `expert#e` instance fragment."""
    from hpx_tpu.analysis.rules import _COUNTER_NAME_RE
    from hpx_tpu.svc.performance_counters import counter_name

    src = open(os.path.join(REPO, "hpx_tpu", "cache", "counters.py"),
               encoding="utf-8").read()
    for lit in ('"moe/tokens-routed"', '"moe/tokens-dropped"',
                'f"moe/expert#{e}/occupancy"'):
        assert lit in src, \
            f"{lit} gone from cache/counters.py — the MoE counter " \
            "namespace is pinned; rename both sides or don't"
    leaves = ["moe/tokens-routed", "moe/tokens-dropped",
              "moe/expert#0/occupancy", "moe/expert#7/occupancy"]
    for leaf in leaves:
        name = counter_name("serving", leaf, "server#0", locality=0)
        assert _COUNTER_NAME_RE.match(name), name
    # and the literal form stays HPX016-clean at a query site
    assert findings(
        "from hpx_tpu.svc.performance_counters import query_counter\n"
        "def scrape():\n"
        "    return query_counter(\n"
        '        "/serving{locality#0/server#0}/moe/tokens-dropped")\n',
        path="hpx_tpu/svc/fixture.py") == []


# ---------------------------------------------------------------------------
# HPX023 — quantile scans reachable from the serving hot path
# ---------------------------------------------------------------------------

def test_hpx023_quantile_reachable_from_step_fires():
    res = lint_sources({"hpx_tpu/svc/srv.py": """\
class Server:
    def step(self):
        self._tick()

    def _tick(self):
        return self.hist.quantile(0.99)
"""}, rules=all_rules(["HPX023"]))
    assert rules_of(res.findings) == ["HPX023"]
    assert "quantile()" in res.findings[0].message
    assert "Server._tick" in res.findings[0].message


def test_hpx023_detached_snapshot_is_silent():
    # the sanctioned shape: scan a detached from_snapshot() copy, not
    # the live histogram — the call-result base is off the hot path's
    # shared structure so it carries no per-step lock cost
    res = lint_sources({"hpx_tpu/svc/srv.py": """\
from hpx_tpu.svc.metrics import HistogramCounter

class Server:
    def step(self):
        self._tick()

    def _tick(self):
        snap = self.hist.delta(self.prev)
        return HistogramCounter.from_snapshot(snap).quantile(0.99)
"""}, rules=all_rules(["HPX023"]))
    assert res.findings == []


def test_hpx023_cold_path_quantile_is_silent():
    # same scan in a debug/stats method nothing on the hot path
    # reaches — reporting endpoints may walk buckets freely
    res = lint_sources({"hpx_tpu/svc/srv.py": """\
class Server:
    def step(self):
        self.tokens += 1

    def stats(self):
        return self.hist.quantile(0.99)
"""}, rules=all_rules(["HPX023"]))
    assert res.findings == []


def test_hpx023_cross_module_merged_hist_fires():
    # reachability crosses modules through import aliases: the router
    # pump calls a helper whose module-level merged_hist() scan is the
    # violation
    res = lint_sources({
        "hpx_tpu/svc/a.py": """\
from hpx_tpu.svc.b import summarize

class Router:
    def _pump_decodes(self):
        return summarize(self.hists)
""",
        "hpx_tpu/svc/b.py": """\
from hpx_tpu.svc.metrics import merged_hist

def summarize(hists):
    return merged_hist(hists)
"""}, rules=all_rules(["HPX023"]))
    assert rules_of(res.findings) == ["HPX023"]
    assert "merged_hist()" in res.findings[0].message
    assert res.findings[0].path == "hpx_tpu/svc/b.py"


# ---------------------------------------------------------------------------
# suppression on a multi-line statement's header line
# ---------------------------------------------------------------------------

def test_suppress_on_header_reaches_continuation_lines():
    src = """\
import numpy as np

def f(x):
    y = compute(  # hpxlint: disable=HPX002 — pinned fixture
        np.asarray(x))
    return y
"""
    res = lint_source(src, "hpx_tpu/exec/fixture.py",
                      rules=all_rules(["HPX002"]))
    assert res.findings == [] and res.suppressed == 1
    # same code without the directive fires on the continuation line
    bare = src.replace("  # hpxlint: disable=HPX002 — pinned fixture", "")
    res2 = lint_source(bare, "hpx_tpu/exec/fixture.py",
                       rules=all_rules(["HPX002"]))
    assert [(f.line, f.rule) for f in res2.findings] == [(5, "HPX002")]


def test_suppress_on_with_header_does_not_blanket_body():
    # directive on the `with` header suppresses findings on the
    # header's continuation lines only — the block body still fires
    header_only = """\
import threading

def setup():
    with wrap(  # hpxlint: disable=HPX004 — bootstrap substrate
            threading.Lock()):
        pass
"""
    res = lint_source(header_only, "hpx_tpu/svc/fixture.py",
                      rules=all_rules(["HPX004"]))
    assert res.findings == [] and res.suppressed == 1
    body = """\
import threading

def setup():
    with wrap(  # hpxlint: disable=HPX004 — bootstrap substrate
            make()):
        lock = threading.Lock()
"""
    res2 = lint_source(body, "hpx_tpu/svc/fixture.py",
                       rules=all_rules(["HPX004"]))
    assert [(f.line, f.rule) for f in res2.findings] == [(6, "HPX004")]


# ---------------------------------------------------------------------------
# --update-baseline / stale-entry gate / --format=github
# ---------------------------------------------------------------------------

def test_update_baseline_keeps_justifications_prunes_stale(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text(HPX006_BAD)
    bl = str(tmp_path / "baseline.json")
    assert cli_main([str(bad), "--baseline", bl, "--write-baseline"]) == 0
    rec = json.loads(open(bl).read())
    rec["entries"][0]["justification"] = "hand-written why"
    rec["entries"].append({"path": "gone.py", "rule": "HPX006",
                           "message": "m", "count": 1,
                           "justification": "stale"})
    with open(bl, "w") as f:
        json.dump(rec, f)
    # the gate fails while a stale entry lingers...
    assert cli_main([str(bad), "--baseline", bl]) == 1
    # ...--update-baseline prunes it and keeps the edited justification
    assert cli_main([str(bad), "--baseline", bl, "--update-baseline"]) == 0
    rec2 = json.loads(open(bl).read())
    assert [e["justification"] for e in rec2["entries"]] \
        == ["hand-written why"]
    assert cli_main([str(bad), "--baseline", bl]) == 0


def test_format_github_annotations(tmp_path, capsys):
    bad = tmp_path / "mod.py"
    bad.write_text(HPX006_BAD)
    assert cli_main([str(bad), "--no-baseline", "--format=github"]) == 1
    out = capsys.readouterr().out
    assert out.startswith("::error file=")
    assert "title=HPX006::" in out


def test_format_json(tmp_path, capsys):
    bad = tmp_path / "mod.py"
    bad.write_text(HPX006_BAD)
    assert cli_main([str(bad), "--no-baseline", "--format=json"]) == 1
    rec = json.loads(capsys.readouterr().out)
    assert rec["checked_files"] == 1
    assert [f["rule"] for f in rec["findings"]] == ["HPX006"]
    assert rec["stale_baseline_entries"] == []


# ---------------------------------------------------------------------------
# the lint gate: the real tree must be clean under the shipped baseline
# ---------------------------------------------------------------------------

def test_cli_gate_on_real_tree():
    res = lint_paths([os.path.join(REPO, "hpx_tpu")], rules=all_rules())
    # display paths are repo-relative, so the shipped baseline applies
    assert all(f.path.startswith("hpx_tpu") for f in res.findings)
    new, _ = apply_baseline(res.findings, load_baseline())
    assert new == [], "\n".join(f.format() for f in new)


def test_full_run_parses_once_and_stays_fast():
    # the project and dataflow tiers share the per-file tier's parsed
    # trees: a full three-tier run over N files costs exactly N
    # ast.parse calls, and the whole pass (all 22 rules, cross-module
    # index and def-use chains included) must stay inside the tier-1
    # perf budget
    import time
    before = parse_count()
    t0 = time.monotonic()
    res = lint_paths([os.path.join(REPO, "hpx_tpu")], rules=all_rules())
    elapsed = time.monotonic() - t0
    assert parse_count() - before == res.checked_files
    assert elapsed < 10.0, f"full hpxlint run took {elapsed:.1f}s"


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text(HPX006_BAD)
    assert cli_main([str(bad), "--no-baseline"]) == 1
    bad.write_text(HPX006_GOOD)
    assert cli_main([str(bad), "--no-baseline"]) == 0


def test_cli_list_rules(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    assert "HPX001" in out and "HPX006" in out


def test_module_smoke():
    # the documented invocation, end to end, from the repo root
    proc = subprocess.run(
        [sys.executable, "-m", "hpx_tpu.analysis", "hpx_tpu/"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
