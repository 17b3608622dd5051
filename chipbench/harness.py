"""What every cell's run shares: finding the cell's files by name,
refusing to measure without the chip, the compile cache, the traced
sub-window, the per-layer readers and the one result line.

The harness holds no list of cells, configurations, mixes or metrics:
`--workload` names an entry of BENCHMARK.json's `workloads`, whose
`config` and `traffic` name chipbench/configs/<config>.json and
chipbench/traffic/<mix>.json; those name their driver and generator;
each per-layer metric of BENCHMARK.json is read by
chipbench/layers/<metric-name>.py.
"""

from __future__ import annotations

import contextlib
import glob
import importlib.util
import json
import os
import shutil
import time
from typing import Any, Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(ROOT, ".chipbench_out")


class Refused(Exception):
    """The run may not be made: no result line, exit code 2."""


def load_by_path(relpath: str, root: str = ROOT):
    """Import a file of the checkout by its path (a metric's name may
    hold dots, so module names will not do)."""
    path = os.path.join(root, relpath)
    name = "chipbench_file_" + "".join(
        c if c.isalnum() else "_" for c in relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"no such file: {relpath}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and \
            isinstance(out.get(k), dict) else v
    return out


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def seed_key(seed: int):
    """A jax PRNG key from any whole number (the driver's seeds pass
    2**31, more than the 32 signed bits PRNGKey takes)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


class Context:
    """One run of one cell."""

    def __init__(self, workload: str, seed: int, seconds: float,
                 trace: bool, t_start: float, root: str = ROOT,
                 rehearse: Optional[str] = None):
        self.root, self.seed, self.seconds = root, int(seed), float(seconds)
        self.trace, self.t_start = bool(trace), t_start
        self.bench = _read_json(os.path.join(root, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise Refused(f"workload {workload!r} is not in BENCHMARK.json "
                          f"(known: {sorted(cells)})")
        self.cell = cells[workload]
        conf = {c["name"]: c for c in self.bench["configs"]}[
            self.cell["config"]]
        self.config = _read_json(os.path.join(root, conf["file"]))
        self.traffic = _read_json(os.path.join(
            root, "chipbench", "traffic", self.cell["traffic"] + ".json"))
        self.rehearsal = rehearse is not None
        if rehearse:
            over = _read_json(rehearse)
            self.config = _merge(self.config, over.get("config", {}))
            self.traffic = _merge(self.traffic, over.get("traffic", {}))
        self.trace_data: Optional[dict] = None
        self._tracing = False
        self.devices_ready_s: Optional[float] = None

    # -- the chip, or nothing ------------------------------------------
    def require_device(self):
        import jax
        devs = jax.devices()
        plat = devs[0].platform
        if self.rehearsal:
            if plat == "tpu":
                raise Refused("--rehearse overrides sizes: refused on a TPU")
        elif plat != "tpu":
            raise Refused(f"no TPU: jax found platform {plat!r}; the "
                          "benchmark has no CPU fallback")
        if len(devs) < int(self.cell["chips"]) and not self.rehearsal:
            raise Refused(f"cell needs {self.cell['chips']} chips, jax "
                          f"found {len(devs)}")
        if plat == "tpu":
            from . import opcount
            self.peaks = opcount.load_peaks(devs[0].device_kind)
            self._enable_cache(jax)
        else:
            self.peaks = None
        self.devices = devs
        self.devices_ready_s = self.clock() - self.t_start
        return devs

    def _enable_cache(self, jax):
        # where JAX_COMPILATION_CACHE_DIR is set jax reads it itself;
        # else a fixed directory inside the checkout (part of the key)
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              os.path.join(self.root, ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    # -- files found by name -------------------------------------------
    def driver(self):
        return load_by_path(self.config["driver"], self.root)

    def reference(self):
        return load_by_path(self.config["reference"], self.root)

    def generator(self, **sizes):
        return load_by_path(self.traffic["generator"], self.root).make(
            self.traffic, self.seed, **sizes)

    # -- earlier lines, spans, clock -----------------------------------
    @staticmethod
    def clock() -> float:
        return time.perf_counter()

    def say(self, **line) -> None:
        print(json.dumps(line), flush=True)

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    @staticmethod
    def stalls(ends, t_open: float, block: int = 1) -> dict:
        """Where a window lost time: `ends` are the host-clock times at
        which its iterations (steps, DAGs) ended, taken `block` at a
        time (a server that syncs every few steps has no steady single
        step). A block that took over 1.1 times the median block is a
        stall, and its excess over the median is time the window lost.
        For an earlier line of every run: a far-off run then says
        whether it froze once or ran slow throughout."""
        marks = [t_open] + list(ends)[block - 1::block]
        dts = [b - a for a, b in zip(marks, marks[1:])]
        if not dts:
            return {}
        mid = sorted(dts)[len(dts) // 2]
        worst = sorted(((dt, t) for dt, t in zip(dts, marks[1:])
                        if dt > 1.1 * mid), reverse=True)
        return {"block_iters": block, "block_ms_p50": 1e3 * mid,
                "block_ms_max": 1e3 * max(dts), "stalls": len(worst),
                "stall_s": sum(dt - mid for dt, _ in worst),
                "worst_at_s_ms": [[round(t - dt - t_open, 3),
                                   round(1e3 * dt, 1)]
                                  for dt, t in worst[:3]]}

    def setup_seconds(self, t_open: float) -> float:
        return t_open - self.t_start

    # -- the traced sub-window -----------------------------------------
    def trace_start(self):
        import jax
        self._trace_dir = os.path.join(OUT_DIR, "trace-" + self.cell["name"])
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        os.makedirs(self._trace_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._tracing = True
        self._win = self.span("bench.trace_window")
        self._win.__enter__()

    def trace_stop(self):
        import jax
        self._win.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._tracing = False

    def _load_trace(self):
        """Read the trace once the window has closed: parsing a few
        seconds of a server's trace takes seconds itself."""
        from . import trace_reduce
        files = glob.glob(os.path.join(self._trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        self.trace_data = trace_reduce.load_xplane(files[0])
        dump = os.environ.get("CHIPBENCH_DUMP_TRACE")
        if dump:        # a recorded trace for the tests, never in a check
            with open(dump, "w") as f:
                json.dump(self.trace_data, f)

    def trace_abort(self):
        if self._tracing:
            import jax
            with contextlib.suppress(Exception):
                self._win.__exit__(None, None, None)
                jax.profiler.stop_trace()
            self._tracing = False

    # -- the device's memory -------------------------------------------
    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices[:int(self.cell["chips"])]]
        return int(max(peaks))

    # -- per-layer metrics and the result line -------------------------
    def _reports(self, metric: dict) -> bool:
        cells = metric.get("workloads")
        return cells is None or self.cell["name"] in cells

    def per_layer(self, counters: dict) -> Dict[str, dict]:
        out = {}
        for m in self.bench["per_layer"]:
            if not self._reports(m):
                continue
            reader = load_by_path(
                os.path.join("chipbench", "layers", m["name"] + ".py"),
                self.root)
            value = reader.read(self.trace_data, counters, self)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def end_to_end(self, values: dict) -> Dict[str, dict]:
        out = {}
        for m in self.bench["end_to_end"]:
            if self._reports(m) and m["name"] in values:
                out[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
        return out

    def result(self, outcome: dict) -> dict:
        """outcome (from the driver): end_to_end values, counters,
        checks [(name, value, limit)], attempted, failed,
        memory_peak_bytes."""
        from . import trace_reduce
        checks = {name: {"value": float(v), "limit": float(lim)}
                  for name, v, lim in outcome["checks"]}
        correct = all(c["value"] <= c["limit"] for c in checks.values()) \
            and bool(checks)
        dev = self.devices[0]
        device: Dict[str, Any] = {
            "platform": dev.platform, "kind": dev.device_kind,
            "count": len(self.devices),
            "memory_peak_bytes": int(outcome["memory_peak_bytes"])}
        line: Dict[str, Any] = {
            "correct": bool(correct),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"])}
        if self.trace:
            self._load_trace()
            if self.trace_data["devices"]:
                busy_s, window_s = trace_reduce.busy(self.trace_data)
                device["busy_s"], device["window_s"] = busy_s, window_s
                line["breakdown"] = trace_reduce.breakdown(self.trace_data)
            elif not self.rehearsal:
                raise RuntimeError("the trace holds no device plane")
            else:       # a CPU rehearsal: no device plane to read
                self.trace_data = None
            line["metrics"] = self.per_layer(outcome["counters"])
            line["device"] = device
        else:
            line["metrics"] = self.end_to_end(outcome["end_to_end"])
            line["device"] = device
        if self.rehearsal:
            line["rehearsal"] = True
        line["checks"] = checks
        return line
