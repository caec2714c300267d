"""BENCHMARK.json and the files it names: every cell's configuration,
mix and per-layer reader exists under its name, and each reader reads
nothing from an empty run and a number from a made-up one."""
import json
import os
import re

import pytest

import family
import run
import traffic
from devtrace import Trace

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return run.load_benchmark()


def test_names_and_files(bench):
    assert bench["paths"] == ["chipbench"]
    for c in bench["configs"]:
        assert NAME.match(c["name"])
        assert os.path.isfile(os.path.join(run.ROOT, c["file"]))
        sp = family.load_config(c["name"])
        with open(os.path.join(run.ROOT, c["file"])) as f:
            raw = json.load(f)
        # the file BENCHMARK.json names is the one its family reads
        assert family.load(sp.family).spec(raw, c["name"]) == sp
        assert sorted(c["reduced"]) == sorted(raw["reduced"])
        assert sp.layers < raw["published"]["num_hidden_layers"]
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        traffic.load(w["traffic"])
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    assert "setup_s" in names


def test_every_cell_reports_enough(bench):
    for w in bench["workloads"]:
        e2e = [m["name"] for m in bench["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = [m for m in bench["per_layer"]
                 if w["name"] in m["workloads"]]
        assert layer
        for m in layer:
            assert m["moves"] in e2e


def _made_up_run(spec):
    data = run.RunData(spec=spec, mix={}, peaks={
        "bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    data.window_s = 2.0
    data.steps = [[4000, 5000], [4001, 5001]]
    data.hot0 = {"step_s": 1.0, "r_wait_s": 0.5}
    data.hot1 = {"step_s": 1.2, "r_wait_s": 0.6}
    d = "/device:TPU:0"
    data.trace = Trace(
        ops={d: [("%paged_decode_attention.1 = custom-call(), custom_call_target=\"tpu_custom_call\"", 0.0, 1e8, "jit_x"),
                 ("fusion", 2e8, 1e8, "jit_y")]},
        marks=[("bench.window", 0.0, 2e9)])
    return data


def test_readers(bench):
    sp = family.load_config("qwen3-8b")
    for m in bench["per_layer"]:
        read = run.load_reader(m["name"]).read
        empty = run.RunData(spec=sp, mix={}, peaks={})
        assert read(empty) is None, m["name"]
        v = read(_made_up_run(sp))
        assert v is not None and v > 0, m["name"]
        if m["unit"] == "%":
            assert v <= 100.0, m["name"]
