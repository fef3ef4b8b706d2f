"""`BENCHMARK.json` against the format rules of the benchmark's contract, as
far as they can be checked without the driver."""

import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|proj|head"
                   r"|n_embd|n_inner|expan|experts_per")


@pytest.fixture(scope="module")
def bench():
    text = (ROOT / "BENCHMARK.json").read_text()
    assert len(text.encode()) <= 64 * 1024
    return json.loads(text)


def _line(s, limit=200):
    return 1 <= len(s) <= limit and "\n" not in s and "\t" not in s


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert bench["paths"] == ["perfbench"]
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_configs(bench):
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("perfbench/") and (ROOT / c["file"]).is_file()
        assert c["file"] not in files
        files.add(c["file"])
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key), key
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_workloads(bench):
    seen, four = set(), 0
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        four += w["chips"] == 4
    assert 1 <= len(bench["workloads"]) <= 24
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert len({w["name"] for w in bench["workloads"]}) == len(seen)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and 1 <= len(e2e) <= 16
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                         "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                         "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
        layers.add(m["layer"])
        assert (ROOT / "perfbench/layer_metrics" / f"{m['name']}.py").is_file()
        where = set(m.get("workloads", cells))
        assert where <= cells
        assert where <= set(e2e[m["moves"]].get("workloads", cells))
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for cell in cells:
        here = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(here) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])
    perf_md = (ROOT / "PERF.md").read_text()
    assert all(layer in perf_md for layer in layers)


def test_files_under_paths_are_named_from_a_names_characters():
    tracked = [p for p in (ROOT / "perfbench").rglob("*")
               if p.is_file() and "__pycache__" not in p.parts]
    for p in tracked:
        rel = str(p.relative_to(ROOT))
        assert re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", rel), rel
