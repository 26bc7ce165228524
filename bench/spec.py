"""Find what a cell is made of, by name.

``BENCHMARK.json`` names each cell's configuration and traffic mix; each
lives in a file of its own, found by that name:

    bench/configs/<config>.json    a deployment: corpus sizes, source, cuts
    bench/traffic/<traffic>.json   a traffic mix read by bench/traffic.py
    bench/metrics/<metric>.py      a per-layer metric's reader, read(ctx)
    bench/peaks.json               the device peaks, keyed by device_kind

Every lookup takes the directories to search, so a caller (a test, a later
cell) can put a file beside the tree's own without editing any of them.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CONFIG_DIRS = (BENCH / "configs",)
TRAFFIC_DIRS = (BENCH / "traffic",)
METRIC_DIRS = (BENCH / "metrics",)


class SpecError(LookupError):
    pass


def _find(name: str, dirs, suffix: str) -> Path:
    for d in dirs:
        p = Path(d) / f"{name}{suffix}"
        if p.is_file():
            return p
    raise SpecError(f"no {suffix} file named {name!r} in "
                    f"{[str(d) for d in dirs]}")


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise SpecError(f"no workload {name!r} in BENCHMARK.json; known: "
                    f"{[w['name'] for w in bench['workloads']]}")


def config(name: str, dirs=CONFIG_DIRS) -> dict:
    return json.loads(_find(name, dirs, ".json").read_text())


def traffic(name: str, dirs=TRAFFIC_DIRS) -> dict:
    return json.loads(_find(name, dirs, ".json").read_text())


def metric_reader(name: str, dirs=METRIC_DIRS):
    """The ``read(ctx)`` function of the per-layer metric ``name``."""
    path = _find(name, dirs, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def metrics_of(bench: dict, cell: str, group: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell`` reports:
    those with no ``workloads`` key, and those that list it."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; a kind that is
    not in the table is an error, never a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise SpecError(f"device_kind {device_kind!r} is not in the peaks "
                        f"table {sorted(table['devices'])}") from None
