"""BENCHMARK.json keeps to the benchmark's contract, and every name in
it resolves to its files."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_./-]{1,200}")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= len(manifest["command"]) <= 32
    assert all(_line(w) for w in manifest["command"])
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.fullmatch(p) and not p.startswith("/") \
            and ".." not in p.split("/")
        assert os.path.isdir(os.path.join(ROOT, p))
    script = manifest["command"][1]
    assert any(script.startswith(p + "/") for p in manifest["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in manifest["workloads"]}
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/")
                   for p in manifest["paths"])
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["name"] == c["name"]
        assert body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        assert all(NAME.fullmatch(k) for k in c["reduced"])
        for kind in ("generators", "refs"):
            key = "generator" if kind == "generators" else "reference"
            assert os.path.isfile(os.path.join(ROOT, "bench", kind,
                                               body[key] + ".py"))


def test_workloads(manifest):
    names = [w["name"] for w in manifest["workloads"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(1, len(names) // 2)
    configs = {c["name"] for c in manifest["configs"]}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and NAME.fullmatch(w["traffic"])
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        mix = os.path.join(ROOT, "bench", "traffic", w["traffic"] + ".json")
        with open(mix) as f:
            loop = json.load(f)["loop"]
        assert os.path.isfile(os.path.join(ROOT, "bench", "loops",
                                           loop + ".py"))


def test_metrics(manifest):
    e2e, per_layer = manifest["end_to_end"], manifest["per_layer"]
    names = [m["name"] for m in e2e + per_layer]
    assert len(set(names)) == len(names)
    assert 1 <= len(e2e) <= 16 and 1 <= len(per_layer) <= 128
    workloads = {w["name"] for w in manifest["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        bound_max = 0.25
        assert 0.01 <= m["bound"] <= bound_max
    assert any(m["name"] == "setup_s" for m in e2e)
    layers = {}
    for m in per_layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in {x["name"] for x in e2e}
        assert _line(m["layer"])
        layers.setdefault(m["layer"], []).append(m["name"])
        assert os.path.isfile(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    for m in e2e + per_layer:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", workloads)) <= workloads
    for w in workloads:
        reported = [m for m in e2e if w in m.get("workloads", workloads)]
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert any(w in m.get("workloads", workloads) for m in per_layer)


def test_peaks_name_their_source():
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        peaks = json.load(f)
    assert "cloud.google.com/tpu" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12
