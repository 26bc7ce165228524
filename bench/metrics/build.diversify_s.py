"""Seconds of the build's diversification stage (core/diversify.py), as
the build pipeline (ann/pipeline.py) logs it at INFO; read in the traced
run, where the pipeline waits for each stage.  Moves build_s."""


def read(ctx):
    return ctx["stages"].get("diversify")
