"""``BENCHMARK.json`` against the driver's contract, as far as a test can
read it, and against the files it names."""

import importlib.util
import json
import os
import re

import pytest

from benchmark import run as bench_run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def module(kind, name):
    path = os.path.join(ROOT, "benchmark", kind, name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_top_level():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16 and all(PATH.match(p) for p in BENCH["paths"])
    assert len(BENCH["command"]) <= 32 and all(line_ok(w) for w in BENCH["command"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    # the full check has to fit with all 24 cells a benchmark may have
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert line_ok(c["source"]) and line_ok(c["why"])
        assert PATH.match(c["file"]) and c["file"].startswith(BENCH["paths"][0] + "/")
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"])
        for key in c["reduced"]:  # a width is never reduced
            assert not re.search(r"(_dim|_rank|_size|hidden|intermediate|head_dim)$", key)
        with open(os.path.join(ROOT, c["file"])) as f:
            data = json.load(f)
        for key in c["reduced"]:
            assert key in data and key in data["published"]
            assert data[key] != data["published"][key]


def test_workloads():
    cells = BENCH["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    configs = {c["name"] for c in BENCH["configs"]}
    for c in cells:
        assert set(c) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(c["name"]) and NAME.match(c["traffic"])
        assert c["config"] in configs and c["chips"] in (1, 4) and line_ok(c["why"])
        assert os.path.exists(
            os.path.join(ROOT, "benchmark", "traffic", c["traffic"] + ".json")
        )
    assert sum(1 for c in cells if c["chips"] == 4) == 1


@pytest.mark.parametrize("kind", ["end_to_end", "per_layer"])
def test_metrics_match_their_readers(kind):
    cells = {c["name"] for c in BENCH["workloads"]}
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"bound"} if kind == "end_to_end" else {"layer", "moves"}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for m in BENCH[kind]:
        assert set(m) <= allowed and set(m) >= allowed - {"workloads"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        reader = module(bench_run.READERS[kind], m["name"])
        assert reader.NAME == m["name"] and reader.UNIT == m["unit"]
        assert reader.SOURCE == m["source"]
        assert not hasattr(reader, "CELLS")  # a reader names no cell
        assert set(m.get("workloads", [])) <= cells
        if kind == "end_to_end":
            assert m["source"] in ("host_clock", "device_trace")
            assert 0.01 <= m["bound"] <= 0.1
        else:
            assert reader.LAYER == m["layer"] and line_ok(m["layer"])
            assert reader.MOVES == m["moves"] and m["moves"] in e2e
            # the contract: reported only where the metric it moves is
            moved = set(e2e[m["moves"]].get("workloads", cells))
            assert set(m.get("workloads", cells)) <= moved
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    assert "setup_s" in e2e


def test_the_harness_names_no_model_cell_or_metric():
    words = {c["name"] for c in BENCH["configs"]}
    words |= {m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    words |= {"resnet", "mistral", "transformer"}
    for name in ("run.py", "reduce_trace.py", "harness.py"):
        with open(os.path.join(ROOT, "benchmark", name)) as f:
            text = f.read()
        for word in words:
            assert not re.search(r"\b%s\b" % re.escape(word), text), (name, word)


def test_every_file_under_paths_is_named_from_the_allowed_characters():
    for base, dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "out")]
        for name in files:
            rel = os.path.relpath(os.path.join(base, name), ROOT)
            assert PATH.match(rel), rel


def test_peaks_name_their_source():
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    assert all("source" in p for p in peaks.values())
