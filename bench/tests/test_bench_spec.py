"""BENCHMARK.json against the benchmark's contract, and every name it gives
found as a file."""

import json
import os
import re
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import harness  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"][0] == "python3" and len(spec["command"]) <= 32
    assert 1 <= spec["run_seconds"] <= 51
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in spec["paths"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_and_units_use_allowed_characters(spec):
    names = ([c["name"] for c in spec["configs"]]
             + [w["name"] for w in spec["workloads"]]
             + [m["name"] for m in spec["end_to_end"] + spec["per_layer"]])
    names += [w["traffic"] for w in spec["workloads"]]
    names += [k for c in spec["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    assert len(set(names)) == len(names) - len(spec["workloads"]) + len(
        {w["traffic"] for w in spec["workloads"]})
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0 < m["bound"] <= 0.25
    for text in ([w["why"] for w in spec["workloads"]]
                 + [c["why"] for c in spec["configs"]]
                 + [c["source"] for c in spec["configs"]]
                 + [m["layer"] for m in spec["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_workload_resolves_to_its_files(spec):
    for w in spec["workloads"]:
        cell = harness.resolve(w["name"], ROOT)
        assert cell.config["name"] == w["config"]
        assert os.path.exists(cell.driver_path)
        assert cell.chips in (1, 4)
        assert cell.per_layer, w["name"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert len(cell.end_to_end) >= 2
        for name, path in cell.metric_paths.items():
            mod = harness.load_module(path, "m_" + name.replace(".", "_"))
            assert callable(mod.read)
            assert mod.read({}) is None  # nothing to read -> nothing


def test_each_moves_is_reported_by_every_listed_cell(spec):
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    cells = {w["name"] for w in spec["workloads"]}
    for m in spec["per_layer"]:
        target = e2e[m["moves"]]
        for w in m.get("workloads", sorted(cells)):
            assert w in cells
            assert "workloads" not in target or w in target["workloads"], (
                m["name"], w)


def test_rooflines_have_a_whole_step_share_beside_them(spec):
    for m in spec["per_layer"]:
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"
            beside = [o for o in spec["per_layer"] if "mfu" in o["name"]
                      and o["moves"] == m["moves"]
                      and set(m["workloads"]) <= set(o["workloads"])]
            assert beside, m["name"]


def test_four_chip_cells_are_at_most_half(spec):
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 2)


def test_a_new_config_and_traffic_file_are_found_by_name(tmp_path, spec):
    """A later change adds a configuration, a mix and a cell as files and
    entries only: the harness finds them without an edit."""
    bench = tmp_path / "bench"
    for sub in ("drivers", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub), bench / sub)
    (bench / "configs").mkdir()
    (bench / "traffic").mkdir()
    cfg = json.load(open(os.path.join(BENCH, "configs", "esam_mnist_if.json")))
    cfg["name"] = "esam_mnist_if_narrow"
    (bench / "configs" / "esam_mnist_if_narrow.json").write_text(
        json.dumps(cfg))
    (bench / "traffic" / "static_bursty.json").write_text(json.dumps(
        {"driver": "open_loop", "rate_hz": 1000, "p_event": 0.0}))
    new = dict(spec)
    new["configs"] = spec["configs"] + [{
        "name": "esam_mnist_if_narrow", "source": "https://example.org/x",
        "file": "bench/configs/esam_mnist_if_narrow.json", "reduced": [],
        "why": "test"}]
    new["workloads"] = spec["workloads"] + [{
        "name": "if_static_bursty", "config": "esam_mnist_if_narrow",
        "traffic": "static_bursty", "chips": 1, "why": "test"}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(new))
    cell = harness.resolve("if_static_bursty", str(tmp_path))
    assert cell.config["name"] == "esam_mnist_if_narrow"
    assert cell.traffic["rate_hz"] == 1000
    assert cell.driver_path.endswith("open_loop.py")
    # metrics without a workloads list would follow the cell's end-to-end
    # metrics; these all list theirs, so the new cell reads none of them
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}
    with pytest.raises(KeyError):
        harness.resolve("no_such_cell", str(tmp_path))
