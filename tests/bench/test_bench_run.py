"""The harness end to end on the CPU, at a size a test run can hold: the
look for a chip is skipped, everything else runs as on the chip.  A sound
run comes out correct; the control (the reference in bfloat16 in the
program's place) and a run with an answer altered where it is produced
come out not correct."""
from bench.system import Control


def test_sound_closed_run_is_correct(tiny, line_shape):
    res = tiny("closed")
    line_shape(res, traced=False)
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"qps", "recall_at_10", "build_s",
                                   "setup_s"}
    assert res["metrics"]["recall_at_10"]["value"] > 0.5


def test_traced_closed_run_reports_per_layer_metrics(tiny, line_shape):
    res = tiny("closed", trace=True)
    line_shape(res, traced=True)
    assert res["correct"]
    assert {"pad_share.batch", "search_roofline", "device_idle.batch",
            "build.knn_s", "build.diversify_s"} <= set(res["metrics"])
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10


def test_control_comes_out_not_correct(tiny):
    res = tiny("closed", system=Control)
    assert not res["correct"]
    chk = res["checks"]["dist_gap"]
    assert chk["value"] > chk["limit"]


def test_answer_altered_where_produced_is_not_correct(tiny, monkeypatch):
    from repro.serve.engine import ANNEngine

    query = ANNEngine.query

    def altered(self, Q, **kw):
        ids, dists = query(self, Q, **kw)
        ids = ids.copy()
        ids[len(ids) // 2, 0] = (ids[len(ids) // 2, 0] + 1) % 1024
        return ids, dists
    monkeypatch.setattr(ANNEngine, "query", altered)
    assert not tiny("closed")["correct"]
