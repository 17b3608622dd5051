"""Every file BENCHMARK.json names is found by name, and the file keeps
to the limits of the contract that can be checked without a run."""

import json
import os
import re

from chipbench.tests import helpers as h

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|"
                   r"head_size|expansion|experts_per_tok")


def test_keys_and_names():
    b = h.bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["chipbench"]
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    assert os.path.getsize(os.path.join(h.ROOT, "BENCHMARK.json")) < 65536
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(names)) == len(names)
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in b[group]:
            assert NAME.match(e["name"]), e["name"]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1 and UNIT.match(m["unit"])
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
    cells = {w["name"]: w for w in b["workloads"]}
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and UNIT.match(m["unit"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        moved = e2e[m["moves"]].get("workloads", list(cells))
        assert set(m["workloads"]) <= set(moved), m["name"]
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        reports = [m for m in b["end_to_end"] if m["name"] != "setup_s"
                   and w["name"] in m.get("workloads", [w["name"]])]
        layers = [m for m in b["per_layer"]
                  if w["name"] in m.get("workloads", [w["name"]])]
        assert reports and layers, w["name"]


def test_every_named_file_is_there():
    b = h.bench()
    configs = {c["name"]: c for c in b["configs"]}
    used = {w["config"] for w in b["workloads"]}
    assert used == set(configs)
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("chipbench/") and len(c["source"]) <= 200
        conf = json.load(open(os.path.join(h.ROOT, c["file"])))
        assert conf["reduced"] == c["reduced"]
        assert not any(WIDTH.search(k) for k in c["reduced"])
        for key in ("source", "assumed", "reduced", "driver", "reference"):
            assert key in conf, (c["name"], key)
        assert os.path.exists(os.path.join(h.ROOT, conf["driver"]))
        assert os.path.exists(os.path.join(h.ROOT, conf["reference"]))
    assert "guarantees" in json.load(open(os.path.join(
        h.ROOT, "chipbench/configs/hpx-1d-stencil.json")))
    for w in b["workloads"]:
        mix = json.load(open(os.path.join(
            h.ROOT, "chipbench/traffic", w["traffic"] + ".json")))
        assert mix["why"] and mix["who"]
        assert os.path.exists(os.path.join(h.ROOT, mix["generator"]))
    for m in b["per_layer"]:
        path = os.path.join(h.ROOT, "chipbench/layers", m["name"] + ".py")
        assert os.path.exists(path), path
        assert "def read(trace, counters, ctx)" in open(path).read()


def test_command_names_nothing_outside_paths():
    b = h.bench()
    assert len(b["command"]) <= 32
    for word in b["command"]:
        assert not word.startswith("/") and ".." not in word
