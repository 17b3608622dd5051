"""Both cells end to end at a tiny size, through the same command and
the same files as a run on the chip."""

import json
import os
import shutil

import pytest

from chipbench.tests import helpers as h


@pytest.mark.parametrize("workload", sorted(h.REHEARSE))
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_and_prints_the_contracts_line(workload, trace):
    p, lines = h.run_cell(workload, trace=trace, seed=2**31 + 12345,
                          rehearse=h.REHEARSE[workload])
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(lines[-1])
    keys = set(line) - {"rehearsal", "checks", "breakdown"}
    assert keys == h.RESULT_KEYS
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu" and line["rehearsal"] is True
    cells = h.bench()
    kind = "per_layer" if trace else "end_to_end"
    mine = {m["name"] for m in cells[kind]
            if workload in m.get("workloads", [workload])}
    assert set(line["metrics"]) <= mine
    if not trace:
        assert set(line["metrics"]) == mine
        assert all(v["value"] > 0 for v in line["metrics"].values())
    # the numbers compared, each beside its limit, end standard error
    tail = p.stderr.strip().splitlines()[-(len(line["checks"]) + 1):]
    assert tail[-1] == "correct: True"
    assert all(t.startswith("check ") and " limit " in t for t in tail[:-1])


def test_no_measured_window_without_a_tpu():
    p, lines = h.run_cell("sc2-3b.gen-closed")
    assert p.returncode != 0
    assert not lines, "a run without a TPU may print no result"
    assert "no TPU" in p.stderr


def test_unknown_workload_is_refused():
    p, lines = h.run_cell("no-such-cell")
    assert p.returncode != 0 and not lines


def test_same_seed_same_inputs():
    from chipbench import harness
    gen = harness.load_by_path("chipbench/traffic_gen/requests.py")
    traffic = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-32.json")))
    a, b, c = (gen.make(traffic, s, vocab=49152) for s in (7, 7, 2**31 + 8))
    assert a.request(3) == b.request(3)
    assert a.request(3)["prompt"] != c.request(3)["prompt"]
    # every seed does the same work: lengths do not depend on the seed
    assert [a.lengths(k) for k in range(64)] == \
        [c.lengths(k) for k in range(64)]
    plens = [a.lengths(k)[0] for k in range(512)]
    outs = [a.lengths(k)[1] for k in range(512)]
    assert 128 <= min(plens) and max(plens) <= 1024
    assert 64 <= min(outs) and max(outs) <= 256


def test_closed_loop_staggers_callers_and_refuses_another_kind():
    import pytest
    from chipbench import harness
    gen = harness.load_by_path("chipbench/traffic_gen/requests.py")
    traffic = json.load(open(os.path.join(
        h.ROOT, "chipbench/traffic/closed-32.json")))
    g = gen.make(traffic, 1, vocab=1000)
    assert [r["k"] for r in g.poll(0, 0.0)] == [0]
    assert [r["k"] for r in g.poll(10, 0.7)] == [1, 2]
    g.finished()
    assert [r["k"] for r in g.poll(10, 0.8)] == [3]
    assert len(g.poll(10 ** 6, 0.9)) == 32 - 3
    assert g.ramp_done(200, 0.0) and not g.ramp_done(199, 99.0)
    with pytest.raises(ValueError):
        gen.make(dict(traffic, arrival={"kind": "poisson"}), 1, vocab=1000)


def test_a_later_pr_adds_a_cell_with_new_files_only(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a cell,
    each as new files plus new entries: no file that is there is
    edited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(h.ROOT, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(h.ROOT, "hpx_tpu"), os.path.join(root, "hpx_tpu"))
    before = {}
    for d, _, files in os.walk(os.path.join(root, "chipbench")):
        for f in files:
            before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
    conf = json.load(open(os.path.join(
        root, "chipbench/configs/hpx-1d-stencil.json")))
    conf.update(name="hpx-1d-stencil-k025", k=0.25)
    json.dump(conf, open(os.path.join(
        root, "chipbench/configs/hpx-1d-stencil-k025.json"), "w"))
    mix = json.load(open(h.REHEARSE["hpx-stencil.dataflow-coarse"]))
    base = json.load(open(os.path.join(
        root, "chipbench/traffic/dag-coarse.json")))
    base.update(mix["traffic"], name="dag-tiny")
    json.dump(base, open(os.path.join(
        root, "chipbench/traffic/dag-tiny.json"), "w"))
    with open(os.path.join(root, "chipbench/layers/dags_traced.new.py"),
              "w") as f:
        f.write("def read(trace, counters, ctx):\n"
                "    return counters.get('traced_dags')\n")
    bench = h.bench()
    bench["configs"].append({
        "name": "hpx-1d-stencil-k025", "source": conf["source"] + " k 0.25",
        "file": "chipbench/configs/hpx-1d-stencil-k025.json",
        "reduced": [], "why": "a later PR's"})
    bench["workloads"].append({
        "name": "hpx-k025.tiny", "config": "hpx-1d-stencil-k025",
        "traffic": "dag-tiny", "chips": 1, "why": "a later PR's"})
    for m in bench["end_to_end"]:
        if m["name"] == "mcells_s":
            m["workloads"].append("hpx-k025.tiny")
    bench["per_layer"].append({
        "name": "dags_traced.new", "unit": "dags", "better": "higher",
        "source": "program_counter", "layer": "HPX model",
        "moves": "mcells_s", "workloads": ["hpx-k025.tiny"]})
    json.dump(bench, open(os.path.join(root, "BENCHMARK.json"), "w"))
    empty = os.path.join(root, "none.json")
    json.dump({}, open(empty, "w"))
    for trace, want in ((0, {"mcells_s", "setup_s"}),
                        (1, {"dags_traced.new"})):
        p, lines = h.run_cell("hpx-k025.tiny", trace=trace, rehearse=empty,
                              root=root)
        assert p.returncode == 0, p.stderr[-2000:]
        line = json.loads(lines[-1])
        assert line["correct"] is True and set(line["metrics"]) == want
    for path, data in before.items():
        assert open(path, "rb").read() == data, f"{path} was edited"
