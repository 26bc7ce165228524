"""Queries per dispatch of the micro-batcher (serve/queue.py), over the
whole window: the batcher's own counters, read through ``BatcherStats``.
Moves p99_ms: fewer, fuller dispatches wait longer to fill and pad less."""


def read(ctx):
    w = ctx["window"]
    if not w or not w.get("batcher_dispatches"):
        return None
    return w["batcher_queries"] / w["batcher_dispatches"]
