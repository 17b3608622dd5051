"""Imports point down: kernels know nothing of the cache manager, the
services or the models; the cache manager knows nothing of the models.

Read off each module's AST, function-level lazy imports included, so a
`from ..svc import x` tucked inside a function counts like one at the
top of the file."""

import ast
import glob
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# what the cache manager reads of the services today: spans, counters,
# histograms, the program profiler's timings and the fault injector
_SVC_UNDER_CACHE = {"tracing", "metrics", "performance_counters",
                    "progprof", "faultinject"}

# (importing module, imported module): known, each a named ROADMAP debt
_KNOWN = {
    # C18: the pool dequantization lives with the weight quantizer
    ("hpx_tpu/ops/paged_attention.py", "hpx_tpu.models.quant"),
}

# layer -> {forbidden package: the modules of it that are allowed}
_RULES = {
    "ops": {"svc": set(), "cache": set(), "models": set()},
    "cache": {"models": set(), "svc": _SVC_UNDER_CACHE},
    "models": {"svc": set()},
}

_MODULES = sorted(
    os.path.relpath(p, REPO) for p in
    glob.glob(os.path.join(REPO, "hpx_tpu", "ops", "*.py"))
    + glob.glob(os.path.join(REPO, "hpx_tpu", "cache", "*.py"))
    + [os.path.join(REPO, "hpx_tpu", "models", m + ".py")
       for m in ("transformer", "moe", "quant")])


def _imports(rel):
    """Every hpx_tpu module `rel` imports, as absolute dotted names
    (`from ..svc import tracing` -> hpx_tpu.svc.tracing)."""
    here = rel[:-3].split(os.sep)
    pkg = here[:-1]                 # __init__.py: its own package
    with open(os.path.join(REPO, rel), encoding="utf-8") as f:
        tree = ast.parse(f.read())
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            out.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = pkg[:len(pkg) - (node.level - 1)]
                mod = base + (node.module.split(".")
                              if node.module else [])
            else:
                mod = node.module.split(".")
            if len(mod) < 3:        # `from ..svc import tracing`
                out.update(".".join(mod + [a.name])
                           for a in node.names)
            else:
                out.add(".".join(mod))
    return {m for m in out if m.startswith("hpx_tpu.")}


def test_the_scan_sees_lazy_and_relative_imports():
    got = _imports(os.path.join("hpx_tpu", "cache", "tier.py"))
    assert "hpx_tpu.svc.progprof" in got         # inside a method
    assert "hpx_tpu.synchronization.Mutex" in got  # at the top


@pytest.mark.parametrize("module", _MODULES)
def test_imports_point_down(module):
    layer = module.split(os.sep)[1]
    bad = []
    for imp in sorted(_imports(module)):
        parts = imp.split(".")
        allowed = _RULES[layer].get(parts[1])
        if allowed is None or (module.replace(os.sep, "/"), imp) in _KNOWN:
            continue
        if len(parts) < 3 or parts[2] not in allowed:
            bad.append(imp)
    assert bad == [], f"{module} imports upward: {bad}"


def test_the_known_exceptions_still_exist():
    """A fixed debt leaves the list."""
    for module, imp in _KNOWN:
        assert imp in _imports(module.replace("/", os.sep)), (module, imp)
