import json
import os
import sys

import pytest

# the benchmark is imported as the package ``bench`` from the checkout root
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run, spec  # noqa: E402

# a configuration and two mixes a test run can hold on the CPU, handed to
# the harness's lookups from a temporary directory
TINY = {
    "name": "tiny-16-l2", "index_config": "tsdg-reduced",
    "metric": "l2", "n": 1024, "d": 16, "queries": 128, "k": 10,
    "reduced": {}, "assumed": [],
    "matmul_precision": "highest",
    "corpus": {"layout_seed": 16, "clusters": 8, "subspace": 4,
               "spread": 0.5, "noise": 0.05},
    "limits": {"dist_gap": 1e-4, "recall_at_10": 0.7},
}
SEED = 2**31 + 77


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """``tiny(kind, trace=, system=, seconds=, mix=, seed=)`` runs the
    harness on the CPU with the look for a chip skipped and JAX's cache
    settings untouched; ``mix`` replaces the cell's traffic by a mix
    written as a file of its own."""
    import jax

    d = tmp_path_factory.mktemp("tiny")
    (d / "tiny-16-l2.json").write_text(json.dumps(TINY))
    (d / "batch-tiny.json").write_text(json.dumps({"kind": "closed",
                                                   "batch": 128}))
    (d / "open-tiny.json").write_text(json.dumps({"kind": "open",
                                                  "rate_qps": 100}))
    bench = spec.load_benchmark()
    cells = {"closed": {"name": "tiny.batch", "config": "tiny-16-l2",
                        "traffic": "batch-tiny", "chips": 1},
             "open": {"name": "tiny.open", "config": "tiny-16-l2",
                      "traffic": "open-tiny", "chips": 1}}
    like = {"tiny.batch": "sift128.batch10k", "tiny.open": "sift128.open-b1"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [c for c, w in like.items()
                               if w in m["workloads"]]
    bench["workloads"] += list(cells.values())

    def go(kind, *, trace=False, system=None, seconds=1.0, mix=None,
           seed=SEED):
        cell = cells[kind]
        if mix is not None:   # a mix added as one file beside the others
            (d / "added.json").write_text(json.dumps(mix))
            cell = dict(cell, traffic="added")
        return run.run_cell(bench, cell, seed=seed, seconds=seconds,
                            trace=trace, config_dirs=(d,), traffic_dirs=(d,),
                            system=system, compile_cache=False,
                            devices_and_peaks=(jax.devices(),
                                               spec.peaks("TPU v5 lite")))
    return go


def _line_shape(res, traced):
    keys = ["correct", "attempted", "failed", "metrics", "device"]
    keys += ["breakdown"] if traced else []
    assert list(res) == keys + ["checks"]     # the checks come last
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    json.loads(json.dumps(res))


@pytest.fixture
def line_shape():
    """Asserts the result line's keys, in order, and that it is JSON."""
    return _line_shape
