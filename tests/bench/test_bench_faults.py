"""Faults planted under the timed path whose ids and distances agree with
each other, so that only the recall floor of ``correct`` can catch them:
a search cut to an eighth of its hops, and a merge that loses each
answer's best candidate.  Both come out not correct through the same
``passed()`` that a run's result uses; a sound run at the same seed
reaches the floor (the tiny configuration reads about 0.73 against the
faults' 0.63 and 0.16)."""
import pytest

from bench.system import DropBest, ShortSearch


@pytest.mark.parametrize("system", [ShortSearch, DropBest],
                         ids=["short_search", "drop_best"])
def test_planted_search_fault_is_not_correct(tiny, system):
    res = tiny("closed", system=system)
    chk = res["checks"]
    assert not res["correct"]
    assert chk["recall_at_10"]["value"] < chk["recall_at_10"]["limit"]
    # ids and distances agree: only the recall shows the fault
    assert chk["dist_gap"]["value"] <= chk["dist_gap"]["limit"]
    assert chk["bad_rows"]["value"] == 0
