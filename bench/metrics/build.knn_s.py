"""Seconds of the build's k-NN stage (core/knn_build.py), as the build
pipeline (ann/pipeline.py) logs it at INFO; read in the traced run, where
the pipeline waits for each stage.  Moves build_s."""


def read(ctx):
    return ctx["stages"].get("knn")
