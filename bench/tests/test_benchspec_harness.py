"""The harness's data-driven lookup, the contract's naming rules, the
closed-loop schedule and the refusal of a CPU backend."""
import json
import os
import re
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

from bench import spec
from bench.traffic import make_schedule

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    return spec.load_benchmark()


def test_top_level_keys(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["command"][:2] == ["python3", "bench/run.py"]
    assert bm["paths"] == ["bench"]
    assert 1 <= bm["run_seconds"] <= 51


def test_names_and_units(bm):
    for c in bm["configs"]:
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
    for w in bm["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in bm["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    assert len(names) == len(set(names))


def test_every_cell_resolves_by_name(bm):
    for w in bm["workloads"]:
        cell = spec.resolve(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["kind"] == "closed_loop"
        assert cell.limits
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.metric_module(m["name"]).read)


def test_moves_names_a_metric_each_cell_reports(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    for m in bm["per_layer"]:
        assert m["moves"] in e2e, m
        for w in m.get("workloads", [x["name"] for x in bm["workloads"]]):
            assert spec.applies(e2e[m["moves"]], w), (m["name"], w)
    for w in bm["workloads"]:
        cell = spec.resolve(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer


def test_same_seed_same_schedule():
    mix = spec.resolve("yi6b.chat").traffic
    a, b = (make_schedule(mix, 2**33 + 5, 64000, 1024) for _ in range(2))
    c = make_schedule(mix, 7, 64000, 1024)
    for i in (0, 1, 500):
        assert np.array_equal(a.prompt(i), b.prompt(i))
        assert a.request(i)[1] == b.request(i)[1]
        # every seed serves the same sizes in the same order
        assert len(c.prompt(i)) == len(a.prompt(i))
    assert not np.array_equal(a.prompt(0), c.prompt(0))
    assert (a.prompt_len + a.max_new <= 1023).all()
    assert a.prompt_len.min() >= 32 and a.prompt_len.max() <= 768
    assert (a.prompt_len % mix["prompt_granule"] == 0).all()


def test_a_new_config_mix_and_metric_need_only_new_files(tmp_path, bm):
    """One throwaway entry of each kind, in a copy of the benchmark."""
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cfg = json.loads((spec.BENCH / "configs" / "yi-6b.json").read_text())
    cfg["name"] = "yi-6b-d2"
    cfg["num_hidden_layers"] = 2
    (tmp_path / "bench/configs/yi-6b-d2.json").write_text(json.dumps(cfg))
    mix = dict(spec.resolve("yi6b.chat").traffic, clients=4)
    (tmp_path / "bench/traffic/chat4.json").write_text(json.dumps(mix))
    (tmp_path / "bench/limits/yi6b-d2.chat4.json").write_text(
        json.dumps({"limits": {"logit_gap": 1.0}}))
    (tmp_path / "bench/metrics/steps_in_window.py").write_text(
        "def read(run):\n    return len(run.steps)\n")
    new = json.loads(json.dumps(bm))
    new["configs"].append({"name": "yi-6b-d2", "source": "x",
                           "file": "bench/configs/yi-6b-d2.json",
                           "reduced": ["num_hidden_layers"], "why": "x"})
    new["workloads"].append({"name": "yi6b-d2.chat4", "config": "yi-6b-d2",
                             "traffic": "chat4", "chips": 1, "why": "x"})
    new["per_layer"].append({"name": "steps_in_window", "unit": "steps",
                             "better": "higher", "source": "program_counter",
                             "layer": "scheduler", "moves": "setup_s",
                             "workloads": ["yi6b-d2.chat4"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    cell = spec.resolve("yi6b-d2.chat4", root=tmp_path)
    assert cell.config["num_hidden_layers"] == 2 and cell.traffic["clients"] == 4
    assert "steps_in_window" in [m["name"] for m in cell.per_layer]
    read = spec.metric_module("steps_in_window", root=tmp_path).read
    assert read(types.SimpleNamespace(steps=[1, 2, 3])) == 3


def test_run_py_refuses_the_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(spec.BENCH / "run.py"),
                        "--workload", "yi6b.chat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
