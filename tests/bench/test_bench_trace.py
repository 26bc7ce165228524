"""The reduction from a profiler trace to busy time, idle gaps, the
breakdown and the roofline share: on intervals made by hand, and on a
small trace recorded on a TPU v5e by tests/bench/record_trace.py."""
import os

import pytest

from bench import spec
from bench import trace as T

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


MS = 1e6


def _data():
    spans = [("bench.window", 0, 100 * MS), ("bench.search", 10 * MS, 20 * MS),
             ("bench.sleep", 20 * MS, 80 * MS),
             ("bench.search", 80 * MS, 90 * MS)]
    ops = [("while.1", 12 * MS, 20 * MS), ("kernel.2", 13 * MS, 17 * MS),
           ("fusion.3", 82 * MS, 88 * MS),
           ("late", 95 * MS, 120 * MS)]                  # cut at 100 ms
    return {"spans": spans,
            "devices": {"/device:TPU:0": T.self_times(ops)}}


def test_union_gaps_and_overlap():
    busy = T.union([(12, 20), (18, 30), (35, 39), (61, 88), (95, 100),
                    (96, 97)])
    assert busy == [(12, 30), (35, 39), (61, 88), (95, 100)]
    assert T.gaps(busy, 0, 100) == [(0, 12), (30, 35), (39, 61), (88, 95)]
    assert T.overlap(busy, [(10, 40), (60, 90)]) == 18 + 4 + 27


def test_self_times_and_short_names():
    ops = T.self_times([("while.1", 0, 10), ("a", 1, 3), ("b", 4, 6),
                        ("c", 12, 13)])
    assert [(n, own) for n, _, _, own in ops] == [("while.1", 6), ("a", 2),
                                                  ("b", 2), ("c", 1)]
    assert T.short_name("%fusion.3 = bf16[8]{0} fusion(%x)") == "fusion.3"


def test_reduce_busy_idle_and_breakdown():
    s = T.reduce(_data())
    ms = 1e-3
    assert s["window_s"] == pytest.approx(100 * ms)
    assert s["busy_s"] == pytest.approx((8 + 6 + 5) * ms)
    # search spans are widened by the clock skew (5 ms), not further
    assert s["search_s"] == pytest.approx((8 + 6) * ms)
    assert s["device_ops"][0] == ["fusion.3", pytest.approx(6 * ms)]
    assert ["while.1", pytest.approx(4 * ms)] in s["device_ops"]
    assert ["late", pytest.approx(5 * ms)] in s["device_ops"]
    # the longest gap fell while the host slept; the first before any span
    assert s["idle_gaps"][0] == ["sleep", pytest.approx(62 * ms)]
    assert ["none", pytest.approx(12 * ms)] in s["idle_gaps"]


def test_reduce_refuses_a_trace_without_window_or_device_work():
    d = _data()
    with pytest.raises(ValueError):
        T.reduce({"spans": d["spans"][1:], "devices": d["devices"]})
    with pytest.raises(ValueError):
        T.reduce({"spans": d["spans"], "devices": {}})


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    if not os.path.exists(DATA):
        pytest.fail(f"the recorded trace {DATA} is missing")
    return T.collect(ProfileData.from_file(DATA))


def test_recorded_tpu_trace(recorded):
    assert list(recorded["devices"]) == ["/device:TPU:0"]
    names = [n for n, _, _ in recorded["spans"]]
    assert names.count("bench.search") == 3 and "bench.window" in names
    s = T.reduce(recorded)
    assert 0 < s["search_s"] <= s["busy_s"] < s["window_s"]
    assert len(s["device_ops"]) <= 10 and len(s["idle_gaps"]) <= 10
    # the host slept 10 ms between calls: the device idled meanwhile
    assert s["idle_gaps"][0][0] == "sleep"
    assert s["idle_gaps"][0][1] > 0.009


def test_roofline_reader_on_the_recorded_trace(recorded):
    s = T.reduce(recorded)
    read = spec.metric_reader("search_roofline")
    import dataclasses

    from bench.system import index_config

    cfg = dataclasses.replace(index_config("tsdg-paper"), large_hops=4,
                              max_degree=8, large_n_seeds=8)
    ctx = {"trace": s, "traced": {"queries": 1024}, "index": cfg,
           "config": {"d": 128}, "peaks": spec.peaks("TPU v5 lite")}
    v = read(ctx)
    assert 0 < v <= 100
    assert read(dict(ctx, trace=None)) is None    # nothing to read
    idle = spec.metric_reader("device_idle.batch")({"trace": s})
    assert idle == pytest.approx(100 * (1 - s["busy_s"] / s["window_s"]))
