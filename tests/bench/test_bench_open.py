"""The open-loop mix end to end on the CPU, and what a run does where it
cannot measure: no TPU, or a device kind the peaks table does not hold."""
import jax
import pytest

from bench import run


def test_sound_open_run_is_correct(tiny, line_shape):
    # a 3-s window: the trace covers its last 2 s, once the loop has settled
    res = tiny("open", trace=True, seconds=3.0)
    line_shape(res, traced=True)
    assert res["correct"] and res["attempted"] == 300
    assert 1.9 < res["device"]["window_s"] < 2.9
    assert {"coalesced.open", "pad_share.open",
            "device_idle.open"} <= set(res["metrics"])


def test_open_answers_sent_to_the_wrong_request_are_not_correct(
        tiny, monkeypatch):
    from repro.serve.engine import ANNEngine

    query = ANNEngine.query

    def swapped(self, Q, **kw):
        ids, dists = query(self, Q, **kw)
        return ids[::-1].copy(), dists[::-1].copy()
    monkeypatch.setattr(ANNEngine, "query", swapped)
    # single queries are coalesced, so reversed rows reach other requests
    res = tiny("open", seconds=2.0)
    assert not res["correct"]
    assert res["checks"]["dist_gap"]["value"] > \
        res["checks"]["dist_gap"]["limit"]


@pytest.mark.parametrize("devices", [
    None,                                     # the CPU this test runs on
    [type("Dev", (), {"platform": "tpu",
                      "device_kind": "TPU v99 (not in the table)"})()],
], ids=["no_tpu", "unknown_device_kind"])
def test_no_measurable_chip_exits_nonzero_and_prints_no_result(
        devices, monkeypatch, capsys):
    if devices is not None:
        monkeypatch.setattr(jax, "devices", lambda *a: devices)
    rc = run.main(["--workload", "sift128.batch10k", "--seed", "1",
                   "--seconds", "1"])
    assert rc == 2
    assert capsys.readouterr().out == ""
