"""The benchmark's files: every cell, configuration, traffic mix and
per-layer metric is found by the name ``BENCHMARK.json`` gives it."""

import importlib
import json
import re

import pytest

import run

BENCH = json.loads((run.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
LIMITS = {"route_mismatch", "jass_mismatch", "bmw_gap", "final_gap"}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_by_name(cell):
    c = run.load_cell(cell)
    assert c["cell"]["name"] == cell
    assert set(c["traffic"]["check"]["limits"]) == LIMITS
    assert c["traffic"]["arrivals"]["rate_qps"] > 0
    spec = run.build_spec(c["config"], "jnp")
    assert spec.routing.adapt_every == 0
    assert {m["name"] for m in c["end_to_end"]} >= {"setup_s"}
    assert c["per_layer"]


READERS = sorted({m["name"] for m in BENCH["per_layer"]}
                 | {f.stem for f in (run.HERE / "metrics").glob("*.py")
                    if f.stem != "__init__"})


@pytest.mark.parametrize("metric", READERS)
def test_metric_reader_by_name(metric):
    mod = importlib.import_module(f"metrics.{metric}")
    assert callable(mod.read)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(conf):
    cfg = json.loads((run.ROOT / conf["file"]).read_text())
    assert cfg["name"] == conf["name"]
    assert set(cfg["reduced"]) == set(conf["reduced"])
    for key in ("source", "deployment", "preset", "overrides", "corpus",
                "stage0", "stage2", "assumed"):
        assert key in cfg


def test_names_and_bounds():
    names = ([w["name"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    moves = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in moves for m in BENCH["per_layer"])
    peaks = json.loads((run.HERE / "peaks.json").read_text())
    assert all(p["hbm_bytes_per_s"] > 0 for p in peaks.values())
