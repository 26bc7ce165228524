"""Share of the roofline reached by the search, in %: the least time the
chip could take for the nominal work of the queries searched in the traced
part of the window (bench/work.py, counted from the shapes of the index
configuration the cell names), over the device time inside the benchmark's
``bench.search`` spans.  Batch cells; moves qps."""

from bench import work


def read(ctx):
    t, traced = ctx["trace"], ctx["traced"]
    if not t or not traced or not traced["queries"] or t["search_s"] <= 0:
        return None
    w = work.large_search(traced["queries"], ctx["index"],
                          d=ctx["config"]["d"])
    least, _ = work.least_time(w, ctx["peaks"])
    return 100.0 * least / t["search_s"]
