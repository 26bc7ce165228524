"""BENCHMARK.json keeps to its contract, and every configuration, traffic
mix and per-layer metric is found by its name in a file of its own."""
import json
import re

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "bench/run.py"]
    assert bench["paths"] == ["bench", "tests/bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_names_units_and_entries(bench):
    names = [x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[g]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in bench["end_to_end"]:
        assert 0 < m["bound"] <= 0.25 and m["source"] in ("host_clock",
                                                          "device_trace")
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_every_cell_reports_what_it_must(bench):
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        e2e = {m["name"] for m in spec.metrics_of(bench, w["name"],
                                                   "end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = spec.metrics_of(bench, w["name"], "per_layer")
        assert layer
        for m in layer:   # what a per-layer metric moves, the cell reports
            assert m["moves"] in e2e


def test_each_part_is_found_by_name(bench):
    for c in bench["configs"]:
        conf = spec.config(c["name"])
        assert conf["name"] == c["name"]
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert set(c["reduced"]) == set(conf["reduced"])
    for w in bench["workloads"]:
        assert spec.traffic(w["traffic"])["kind"] in ("closed", "open")
    for m in bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"]))


def test_a_mix_added_as_one_file_is_found(tmp_path):
    (tmp_path / "burst-x2.json").write_text(json.dumps(
        {"kind": "open", "rate_qps": 123}))
    dirs = spec.TRAFFIC_DIRS + (tmp_path,)
    assert spec.traffic("burst-x2", dirs)["rate_qps"] == 123
    with pytest.raises(spec.SpecError):
        spec.traffic("burst-x2")          # the tree itself is unchanged
    (tmp_path / "ops_per_call.py").write_text(
        "def read(ctx):\n    return ctx['n'] * 2\n")
    read = spec.metric_reader("ops_per_call", (tmp_path,))
    assert read({"n": 4}) == 8


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.peaks("TPU v99")


def test_full_check_fits_its_time(bench):
    """A full check with 24 cells: 2 + 14 per cell runs of run_seconds + 60
    s, 2 x 90 s of compile per cell and 1200 s spare in 43200 s."""
    rs = bench["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200
