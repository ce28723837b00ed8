"""BENCHMARK.json: every part of every cell is found by name, and the
file keeps to the shape the harness relies on."""

import json
import os
import re

import pytest

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
BENCH = harness.load_bench()
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_parts_are_found_by_name(cell):
    run = harness.Run(BENCH, cell, 0, 1.0, False)
    assert run.cfg["name"] == run.workload["config"]
    for fn in ("make_weights", "lower", "request_codes", "Reference", "layers"):
        assert callable(getattr(run.model, fn)), fn
    for fn in ("setup", "window", "check"):
        assert callable(getattr(run.driver, fn)), fn
    assert run.limits and all(v >= 0 for v in run.limits.values())
    e2e = [m["name"] for m in harness._metric_list(BENCH, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness._metric_list(BENCH, "per_layer", cell)


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader_that_reads_nothing_from_nothing(metric):
    path = os.path.join(harness.HERE, "metrics", metric + ".py")
    reader = harness.load_module(path, "m_" + metric.replace(".", "_"))
    run = harness.Run(BENCH, CELLS[0], 0, 1.0, True)
    assert reader.read(run) is None


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        harness.Run(BENCH, "no-such-cell", 0, 1.0, False)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
             + [w["traffic"] for w in BENCH["workloads"]])
    assert all(NAME.match(n) for n in names), names
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["layer"]
        for cell in m["workloads"]:
            moved = [e for e in BENCH["end_to_end"] if e["name"] == m["moves"]][0]
            assert cell in moved.get("workloads", CELLS)
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        with open(os.path.join(harness.ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= len(CELLS) // 2


def test_refuses_to_run_without_a_tpu(capsys):
    rc = harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                       "--trace", "0"])
    assert rc == 2
    assert capsys.readouterr().out == ""
