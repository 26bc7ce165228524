"""The benchmark's arithmetic: end-to-end numbers over the whole window,
the comparison that decides ``correct``, the nominal work and its bound,
and the seeded generators."""
import numpy as np
import pytest

from bench import measure
from bench import reference as ref
from bench import traffic as tr
from bench import work
from bench.traffic import ANSWER_WAIT_S, Window


def _win(due, done, ids=None, dists=None, qidx=None):
    due, done = np.asarray(due, float), np.asarray(done, float)
    R = len(due)
    ids = np.zeros((R, 2), np.int64) if ids is None else np.asarray(ids)
    dists = np.zeros((R, 2)) if dists is None else np.asarray(dists)
    qidx = np.arange(R) if qidx is None else np.asarray(qidx)
    return Window(qidx=qidx, due=due, done=done, ids=ids, dists=dists)


def test_qps_is_taken_over_the_whole_window():
    # three batches of 4 answered at 1, 2 and 4 s: 12 queries over 4 s,
    # not the mean of the per-batch rates
    w = _win([0] * 4 + [1] * 4 + [2] * 4, [1] * 4 + [2] * 4 + [4] * 4)
    assert measure.qps(w) == pytest.approx(3.0)


def test_p99_counts_from_due_time_and_counts_failures():
    R = 200
    due = np.linspace(0.0, 9.0, R)
    done = due + 0.010
    done[5] = due[5] + 0.500          # one slow answer
    w = _win(due, done)
    # nearest rank: the 198th of 200 sorted latencies is still 10 ms
    assert measure.percentile_ms(w, 10.0, 99) == pytest.approx(10.0)
    done[6] = done[7] = np.nan        # two failed requests wait out the
    w = _win(due, done)               # answer wait and take the tail
    assert measure.percentile_ms(w, 10.0, 99) == pytest.approx(500.0)
    lat = measure.latencies_s(w, 10.0)
    assert lat[6] == pytest.approx(10.0 + ANSWER_WAIT_S - due[6])


def test_recall_is_the_mean_over_answers_returned():
    gt = np.array([[1, 2, 3], [4, 5, 6], [7, 8, 9]])
    ids = np.array([[1, 2, 9], [6, 5, 4], [0, 0, 0]])
    w = _win([0, 0, 0], [1, 1, np.nan], ids=ids, qidx=[0, 1, 2])
    # the unanswered third request does not enter the mean
    assert measure.recall(w, gt, 3) == pytest.approx((2 + 3) / 6)


def test_distance_gaps_and_bad_rows():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(50, 8)).astype(np.float32)
    Q = rng.normal(size=(4, 8)).astype(np.float32)
    ids, dists = ref.top_k(X, Q, 5)
    gap = ref.distance_gaps(X, Q, np.arange(4), ids, dists)
    assert gap.max() < 1e-6
    wrong = dists.copy()
    wrong[2, 3] *= 1.01
    assert ref.distance_gaps(X, Q, np.arange(4), ids, wrong)[2, 3] > 1e-4
    bad = ids.copy()
    bad[0, 0] = 50                    # out of range: an infinite gap
    assert np.isinf(ref.distance_gaps(X, Q, np.arange(4), bad, dists)[0, 0])
    assert ref.bad_rows(ids, dists, 50) == 0
    dup = ids.copy()
    dup[1, 1] = dup[1, 0]
    assert ref.bad_rows(dup, dists, 50) == 1
    assert ref.bad_rows(ids, dists[:, ::-1], 50) == 4
    assert ref.bad_rows(bad, dists, 50) == 1


def test_checks_compare_each_number_with_its_limit():
    rng = np.random.default_rng(1)
    X = rng.normal(size=(64, 8)).astype(np.float32)
    Q = rng.normal(size=(6, 8)).astype(np.float32)
    ids, dists = ref.top_k(X, Q, 10)
    w = _win(np.zeros(6), np.ones(6), ids=ids, dists=dists)
    lim = {"dist_gap": 1e-5, "recall_at_10": 0.95}
    chk = measure.checks(w, X, Q, ids, lim)
    assert list(chk) == ["dist_gap", "recall_at_10", "unanswered",
                         "bad_rows"]
    assert measure.passed(chk)
    w.done[3] = np.nan
    chk = measure.checks(w, X, Q, ids, lim)
    assert chk["unanswered"]["value"] == 1 and not measure.passed(chk)
    # the recall has to reach its limit: the best answer dropped from each
    # row, ranks 2..10 and an eleventh in, reads 9/10 and fails, though its
    # ids and distances agree with each other
    i11, d11 = ref.top_k(X, Q, 11)
    w = _win(np.zeros(6), np.ones(6), ids=i11[:, 1:], dists=d11[:, 1:])
    chk = measure.checks(w, X, Q, ids, lim)
    assert chk["dist_gap"]["value"] < 1e-5 and chk["bad_rows"]["value"] == 0
    assert chk["recall_at_10"]["value"] == pytest.approx(0.9)
    assert not measure.passed(chk)


def test_bf16_reference_reads_a_wider_gap_than_f32():
    rng = np.random.default_rng(2)
    X = rng.normal(size=(512, 32)).astype(np.float32) + 3.0
    Q = rng.normal(size=(16, 32)).astype(np.float32) + 3.0
    i32, d32 = ref.top_k(X, Q, 10)
    i16, d16 = ref.top_k(X, Q, 10, dtype="bfloat16")
    g32 = ref.distance_gaps(X, Q, np.arange(16), i32, d32).max()
    g16 = ref.distance_gaps(X, Q, np.arange(16), i16, d16).max()
    assert g16 > 100 * g32
    # one query per call too: XLA may not fold the rounding away there
    i1, d1 = ref.top_k(X, Q[:1], 10, dtype="bfloat16")
    assert ref.distance_gaps(X, Q, np.arange(1), i1, d1).max() > 100 * g32


def test_large_search_work_and_its_bound():
    from bench.system import index_config

    cfg = index_config("tsdg-paper")       # 128 seeds, 128 hops, degree 32
    w = work.large_search(10, cfg, d=128)
    rows = 10 * (128 + 128 * 32)
    assert w["rows"] == rows
    assert w["bytes"] == rows * 512 + 10 * 128 * 32 * 8
    assert w["flops"] == rows * 256
    small = index_config("tsdg-reduced")   # 128 seeds, 32 hops, degree 8
    assert work.large_search(1, small, d=16)["rows"] == 128 + 32 * 8
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12}
    t, bound = work.least_time(w, peaks)
    assert bound == "hbm" and t == pytest.approx(w["bytes"] / 819e9)
    t, bound = work.least_time({"bytes": 1.0, "flops": 1e12}, peaks)
    assert bound == "compute" and t == pytest.approx(1e12 / 197e12)


def test_seeded_generators_are_deterministic():
    mix = {"kind": "open", "rate_qps": 300}
    seed = 2**31 + 12345
    a, b = tr.schedule(mix, 10.0, 100, seed), tr.schedule(mix, 10.0, 100,
                                                          seed)
    assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
    assert len(a[0]) == 3000 and np.all(np.diff(a[0]) >= 0)
    # every seed offers the same number of requests, in another order
    c = tr.schedule(mix, 10.0, 100, seed + 1)
    assert len(c[0]) == 3000 and not np.array_equal(a[1], c[1])
    mix = {"kind": "closed", "batch": 64}
    assert np.array_equal(tr.batch_order(mix, 64, seed, 3),
                          tr.batch_order(mix, 64, seed, 3))
    assert sorted(tr.batch_order(mix, 64, seed, 3)) == list(range(64))
    assert not np.array_equal(tr.batch_order(mix, 64, seed, 3),
                              tr.batch_order(mix, 64, seed, -1))
    kw = dict(layout_seed=3, clusters=4, subspace=2, spread=0.5, noise=0.05)
    X1, Q1 = ref.make_corpus(seed, 32, 4, 8, **kw)
    X2, Q2 = ref.make_corpus(seed, 32, 4, 8, **kw)
    X3, _ = ref.make_corpus(seed + 1, 32, 4, 8, **kw)
    assert np.array_equal(np.asarray(X1), np.asarray(X2))
    assert np.array_equal(np.asarray(Q1), np.asarray(Q2))
    assert not np.array_equal(np.asarray(X1), np.asarray(X3))
