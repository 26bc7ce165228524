"""Padded rows per useful query in the engine (serve/engine.py) over the
whole window: ``ServeStats.padded_queries`` over the queries answered.
The bucket ladder's waste on whole batches; moves qps."""


def read(ctx):
    w = ctx["window"]
    if not w or not w["queries"]:
        return None
    return w["padded"] / w["queries"]
