"""BENCHMARK.json against the benchmark's contract, and every file a cell
is made of found by name."""
import json
import re

import pytest

from bench import run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\t\n\r]{1,200}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")

TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}


@pytest.fixture(scope="module")
def manifest():
    return run.load_manifest()


def test_keys_and_sizes(manifest):
    assert set(manifest) == TOP_KEYS
    assert (run.ROOT / "BENCHMARK.json").stat().st_size <= 64 * 1024
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= len(manifest["paths"]) <= 16
    for p in manifest["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
    cmd = manifest["command"]
    assert len(cmd) <= 32 and all(LINE.match(w) for w in cmd)
    for word in cmd[1:]:
        if "/" in word:
            assert any(word.startswith(p + "/") for p in manifest["paths"])
    for c in manifest["configs"]:
        assert set(c) == CONFIG_KEYS
    for c in manifest["workloads"]:
        assert set(c) == CELL_KEYS
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == E2E_KEYS
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == LAYER_KEYS


def test_names_units_and_lines(manifest):
    names = [c["name"] for c in manifest["configs"]]
    names += [c["name"] for c in manifest["workloads"]]
    names += [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    names += [c["traffic"] for c in manifest["workloads"]]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads"):
        entries = [c["name"] for c in manifest[group]]
        assert len(entries) == len(set(entries))
    metrics = [m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for c in manifest["configs"] + manifest["workloads"]:
        assert LINE.match(c["why"])
    for c in manifest["configs"]:
        assert LINE.match(c["source"])
        assert len(c["reduced"]) <= 16
    for m in manifest["per_layer"]:
        assert LINE.match(m["layer"])


def test_bounds_and_sources(manifest):
    e2e = manifest["end_to_end"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(manifest["per_layer"]) <= 128
    assert any(m["name"] == "setup_s" for m in e2e)
    for m in e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in manifest["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        if run.quantity(m["name"]).endswith("_roofline"):
            assert m["unit"] == "%"


def test_cells_and_chip_share(manifest):
    cells = manifest["workloads"]
    assert 1 <= len(cells) <= 24
    configs = {c["name"] for c in manifest["configs"]}
    used = {c["config"] for c in cells}
    assert used == configs
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs))
    four = sum(1 for c in cells if c["chips"] == 4)
    assert all(c["chips"] in (1, 4) for c in cells)
    assert four <= max(1, len(cells) // 2)


def test_every_file_found_by_name(manifest):
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        path = run.ROOT / c["file"]
        assert any(c["file"].startswith(p + "/") for p in manifest["paths"])
        config = json.loads(path.read_text())
        assert config["name"] == c["name"]
        assert set(c["reduced"]) <= set(config["graph"]) | set(config)
        assert run.data_file("graphs", config["graph"]["generator"],
                             ".py").exists()
    for cell in manifest["workloads"]:
        got = run.resolve_cell(manifest, cell["name"])
        traffic = got[2]
        for kind in ("refs", "work", "controls"):
            assert run.data_file(kind, traffic["algorithm"], ".py").exists()
    for m in manifest["per_layer"]:
        mod = run.load_module("metrics", run.quantity(m["name"]))
        assert callable(mod.read)


def test_moves_is_reported_where_the_metric_is(manifest):
    """Every cell that reports a per-layer metric reports the end-to-end
    metric it moves, and every cell reports setup_s, another end-to-end
    metric and a per-layer metric."""
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for cell in manifest["workloads"]:
        _, _, _, ends, layers = run.resolve_cell(manifest, cell["name"])
        reported = {m["name"] for m in ends}
        assert "setup_s" in reported and len(reported) >= 2
        assert layers
        for m in layers:
            assert m["moves"] in e2e
            assert m["moves"] in reported, (cell["name"], m["name"])

