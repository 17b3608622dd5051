"""hpxlint — AST-based static analysis for the hpx_tpu runtime.

The dynamic VERIFY_LOCKS analog (`hpx_tpu.synchronization`) only fires
on the paths a test happens to execute; this package is its static
complement.  A small stdlib-`ast` framework (rule registry, per-rule
severity, file/line findings, inline ``# hpxlint: disable=RULE``
suppressions, committed baseline) runs three tiers of rules:

Per-file tier (rules.py) — each rule sees one parsed file:

* HPX001 lock-held-wait      — future/latch/CV waits lexically inside a
  ``with Mutex():`` region (the classic AMT deadlock, SURVEY.md §5.2).
* HPX002 host-sync-hot-path  — ``np.asarray`` / ``.item()`` /
  ``block_until_ready`` / ``jax.device_get`` in executor/continuation
  code under ``hpx_tpu/{futures,exec,algo,ops}`` (the "task granularity
  chasm": a hidden device sync stalls the whole dispatch pipeline).
* HPX003 dropped-future      — ``async_()/async_many()/dataflow()`` or
  ``.then()`` results discarded as expression statements (the captured
  exception is silently lost; ``post()`` is the fire-and-forget API and
  is deliberately not flagged — it returns ``None`` by design).
* HPX004 raw-sync-primitive  — raw ``threading.Lock``/``time.sleep``/
  ``queue.Queue`` in runtime layers above ``hpx_tpu.synchronization``
  (which futures/, runtime/ and core/ sit *below* — they stay on the raw
  substrate and are exempt).
* HPX005 jit-in-loop         — ``jax.jit`` constructed inside a loop
  body (a fresh jitted callable per iteration defeats the trace cache).
* HPX006 bare-except         — ``except:`` swallows future exceptions
  (and KeyboardInterrupt/SystemExit) on the completion path.
* HPX007–HPX012              — see the README lint table.
* HPX016 counter-name-discipline — counter names that fail the
  ``/object{locality#N/instance}/counter`` registry grammar, and bare
  ``h.record()`` statements that drop the histogram timing context
  manager unrecorded.
* HPX018 knob-mutation — direct writes to the attributes behind
  ``models/serving._RELOADABLE_KNOBS`` outside ``__init__`` /
  ``_reload_knobs`` (they can land mid-step).

Whole-program tier (project.py) — every file is parsed once into a
shared :class:`~.project.ProjectIndex` (symbol table, class-level lock
identities, intra-package call graph) and cross-module rules run over
it:

* HPX013 lock-order-inversion — Mutex/Spinlock pairs acquired in both
  orders on different call paths, with both witness chains.
* HPX014 config-key-schema   — every ``cfg.get*("hpx....")`` read must
  be declared in ``core/config_schema.py``; flags undeclared reads,
  dead keys, and getter/type mismatches.
* HPX015 refcount-balance    — incref/pin without a matching
  decref/unpin on every exit path (static twin of
  ``BlockAllocator.leaked_blocks()``), in ``cache/`` and ``models/``.

Dataflow tier (dataflow.py) — per-function reaching-definitions /
def-use chains over the same parsed trees, plus one-level
interprocedural summaries from the call graph:

* HPX019 unguarded-shared-state  — a ``self.attr`` mutated bare while
  a strict majority of its mutation sites hold the same lock (the
  inferred guarded-by contract), in svc/, models/, cache/, dist/.
* HPX020 donation-use-after-donate — a binding passed at a
  ``donate_argnums`` position of a jitted call and used again after.
* HPX021 mesh-axis-consistency  — collective axis names and
  PartitionSpec fragments inside ``shard_map`` bodies that the
  enclosing mesh/specs never declare.
* HPX022 flow-sensitive-host-sync — a device-origin value (on every
  reaching definition) flowing into ``float()/int()/bool()/np.array``
  in hot-path code; the def-use re-founding of HPX002.

Run it: ``python -m hpx_tpu.analysis [paths...]`` or the installed
``hpxlint`` script (defaults to ``hpx_tpu/``; run from the repo root so
baseline paths line up).  ``--changed`` lints only git-dirty files and
``--only HPX0NN`` restricts the rule set — the ~1s pre-commit path;
``tools/lint.py`` is the full three-tier CI gate.
"""

from .engine import (
    Finding,
    LintResult,
    ProjectRule,
    Rule,
    all_rules,
    apply_baseline,
    lint_paths,
    lint_source,
    lint_sources,
    load_baseline,
    register,
    stale_entries,
    update_baseline_file,
    write_baseline,
)

__all__ = [
    "Finding",
    "LintResult",
    "ProjectRule",
    "Rule",
    "all_rules",
    "apply_baseline",
    "lint_paths",
    "lint_source",
    "lint_sources",
    "load_baseline",
    "register",
    "stale_entries",
    "update_baseline_file",
    "write_baseline",
]
