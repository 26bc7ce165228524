"""New traffic shapes are data: a mix with bursts, or with requests of
many sizes, is one file that the one generator reads, and runs end to end
on the CPU with nothing in the tree edited."""
import numpy as np
import pytest

from bench import traffic as tr

BURST = {"kind": "open", "rate_qps": 100, "size": [1, 4],
         "profile": [[1.0, 2.0], [4.0, 0.75]]}


def test_burst_profile_keeps_the_mean_and_shapes_the_rate():
    seed = 2**31 + 5
    due, rows, size = tr.schedule(BURST, 10.0, 50, seed)
    assert len(due) == 1000 and np.all(np.diff(due) >= 0)
    assert 0.0 <= due.min() and due.max() < 10.0
    assert len(rows) == size.sum() and rows.max() < 50
    per_s = np.bincount(due.astype(int), minlength=10)
    burst, calm = per_s[[0, 5]].mean(), per_s[[1, 2, 3, 4, 6, 7, 8, 9]].mean()
    assert burst == pytest.approx(200, rel=0.2)      # 2x the mean of 100
    assert calm == pytest.approx(75, rel=0.2)
    again = tr.schedule(BURST, 10.0, 50, seed)
    assert all(np.array_equal(a, b) for a, b in zip(again, (due, rows, size)))


def test_ranged_sizes_are_one_set_in_each_seeds_order():
    a = tr.sizes([1, 2048], 64, 11, 4)
    b = tr.sizes([1, 2048], 64, 12, 4)
    assert sorted(a) == sorted(b) and not np.array_equal(a, b)
    assert a.min() == 1 and a.max() == 2048
    mix = {"kind": "closed", "batch": [1, 2048]}
    assert len(tr.batch_order(mix, 100, 11, 0)) == tr.batch_size(mix, 11, 0)
    assert tr.batch_size({"kind": "closed", "batch": 64}, 11, 5) == 64


@pytest.mark.parametrize("mix", [
    BURST,
    {"kind": "closed", "batch": [8, 128]},
], ids=["open_burst_sizes", "closed_ranged"])
def test_added_mix_runs_end_to_end(tiny, mix):
    res = tiny(mix["kind"], mix=mix)
    assert res["correct"], res["checks"]
    if mix["kind"] == "open":
        _, _, size = tr.schedule(mix, 1.0, 128, 2**31 + 77)
        assert res["attempted"] == size.sum()
