"""End-to-end numbers and the comparison that decides ``correct``, from
what the window produced (a :class:`bench.traffic.Window`).

Every number is taken over the whole window: a rate over all its work and
all its time, a tail over all its requests.  A request that raised or was
never answered counts as failed; in a latency tail it counts as having
waited until the answer wait ran out.
"""
from __future__ import annotations

import math

import numpy as np

from bench import reference as ref
from bench.traffic import ANSWER_WAIT_S


def answered(win) -> np.ndarray:
    return np.isfinite(win.done)


def qps(win) -> float:
    """Useful queries answered over the time from the window's start to
    the last answer."""
    ok = answered(win)
    return float(ok.sum() / win.done[ok].max())


def latencies_s(win, seconds: float) -> np.ndarray:
    """Due time to answer, per request; a failed request waited until the
    answer wait ran out."""
    first = (np.arange(len(win.due)) if win.req is None
             else np.unique(win.req, return_index=True)[1])
    due, done = win.due[first], win.done[first]
    return np.where(np.isfinite(done), done - due,
                    seconds + ANSWER_WAIT_S - due)


def percentile_ms(win, seconds: float, q: float) -> float:
    """The ``q``-th percentile by nearest rank over every request due in
    the window, in ms."""
    lat = np.sort(latencies_s(win, seconds))
    rank = max(1, math.ceil(q / 100.0 * len(lat)))
    return float(lat[rank - 1] * 1e3)


def recall(win, gt, k: int) -> float:
    """Mean recall@k of every answer returned in the window."""
    ok = answered(win)
    hits = ref.recall_hits(win.ids[ok], gt[win.qidx[ok]], k)
    return float(hits.sum() / (ok.sum() * k))


def checks(win, X, pool_d, gt, limits: dict) -> dict:
    """The numbers compared for ``correct``, each with its limit:

    * ``dist_gap`` — the widest gap between a returned distance and the
      reference's distance of the same (query, id), over the size of the
      terms (see :func:`bench.reference.distance_gaps`), over every answer
      of the window;
    * ``recall_at_10`` — the mean recall@10 of every answer of the window
      against the exact top-10 ``gt``; it has to reach its limit
      (``at_least``), so a search that stops early, or a merge that loses
      the best candidates, fails even where its ids and distances agree;
    * ``unanswered`` — queries due in the window that never got an
      answer, or got an error (exact: 0);
    * ``bad_rows`` — answers with an id out of range, an id twice, or
      distances out of order (exact: 0).
    """
    ok = answered(win)
    gap = ref.distance_gaps(X, pool_d, win.qidx[ok], win.ids[ok],
                            win.dists[ok]) if ok.any() else np.array([np.inf])
    return {
        "dist_gap": {"value": float(gap.max()),
                     "limit": float(limits["dist_gap"])},
        "recall_at_10": {"value": recall(win, gt, 10) if ok.any() else 0.0,
                         "limit": float(limits["recall_at_10"]),
                         "at_least": True},
        "unanswered": {"value": int((~ok).sum()), "limit": 0},
        "bad_rows": {"value": ref.bad_rows(win.ids[ok], win.dists[ok],
                                           X.shape[0]),
                     "limit": 0},
    }


def passed(chk: dict) -> bool:
    return all(c["value"] >= c["limit"] if c.get("at_least")
               else c["value"] <= c["limit"] for c in chk.values())
