#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are found by name
(``BENCHMARK.json``, ``bench/configs/``, ``bench/traffic/``).  Set-up makes
the corpus and queries on the chip from ``--seed``, builds the index through
the program's ``Index.build`` and warms the shapes the mix sends; the window
then runs the mix for ``--seconds``.  Once it has closed, the program is
freed and every answer of the window is compared with the reference.

With ``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` a part of the window runs under the profiler and the result
carries the per-layer metrics read from it.  The last stdout line is one
JSON object; the numbers compared for ``correct`` are also the last lines
on stderr.  Without a TPU, with fewer chips than the cell asks for, or on a
``device_kind`` missing from the peaks table, it exits non-zero and prints
no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

_T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
if os.path.dirname(HERE) not in sys.path:
    sys.path.insert(0, os.path.dirname(HERE))

from bench import spec  # noqa: E402

# seconds of the window that run under the profiler in a traced run (a
# closed mix traces whole batches until this much has passed)
TRACED_S = 2.0


class NoChip(RuntimeError):
    pass


def say(*a):
    print(*a, file=sys.stderr, flush=True)


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names another; every program is
    kept, so that only a checkout's first run of a cell compiles."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def device_check(chips: int) -> tuple:
    """(devices, peaks of their kind); raises NoChip where the run cannot
    be measured."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r}); "
                     "this benchmark measures the chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    try:
        pk = spec.peaks(devs[0].device_kind)
    except spec.SpecError as e:
        raise NoChip(str(e)) from None
    return devs[:chips], pk


class Tracer:
    """Runs ``traced_s`` of the window under the profiler, inside a
    ``bench.window`` span, and keeps the counters at both ends: from the
    window's start, or (``begin(end_at=)``) the last ``traced_s`` before
    ``end_at``, so that an open loop is traced once it has settled and the
    profiler's stop, which blocks the host for seconds, comes after the
    last send."""

    def __init__(self, logdir, counters, traced_s):
        self.logdir, self.counters, self.traced_s = logdir, counters, traced_s
        self.active = self.done = False
        self.c0 = self.c1 = None

    def begin(self, end_at=None):
        self.start = 0.0 if end_at is None else end_at - self.traced_s
        self.tick(0.0)

    def tick(self, t):
        import jax

        if not (self.active or self.done) and t >= self.start:
            self.c0 = self.counters()
            jax.profiler.start_trace(self.logdir)
            self.span = jax.profiler.TraceAnnotation("bench.window")
            self.span.__enter__()
            self.t0 = time.perf_counter()
            self.active = True
        elif self.active and time.perf_counter() - self.t0 >= self.traced_s:
            self.end()

    def end(self):
        import jax

        if not self.active:
            return
        self.active, self.done = False, True
        self.c1 = self.counters()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()


def delta(c0: dict, c1: dict) -> dict:
    return {k: c1[k] - c0[k] for k in c0}


def count_files(path: str) -> int:
    return sum(len(f) for _, _, f in os.walk(path)) \
        if os.path.isdir(path) else 0


def end_to_end(name: str, ctx: dict) -> float:
    from bench import measure

    win, seconds = ctx["win"], ctx["seconds"]
    if name == "qps":
        return measure.qps(win)
    if name == "p99_ms":
        return measure.percentile_ms(win, seconds, 99)
    if name == "recall_at_10":
        return measure.recall(win, ctx["gt"], 10)
    if name == "build_s":
        return ctx["build_s"]
    if name == "setup_s":
        return ctx["setup_s"]
    raise spec.SpecError(f"no end-to-end metric named {name!r}")


def run_cell(bench: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, config_dirs=spec.CONFIG_DIRS,
             traffic_dirs=spec.TRAFFIC_DIRS, metric_dirs=spec.METRIC_DIRS,
             system=None, devices_and_peaks=None, t_start=None,
             compile_cache=True) -> dict:
    """One run of ``cell``; returns the result object.  ``system`` replaces
    the program (the control and the planted faults do);
    ``devices_and_peaks`` replaces the look for a chip and
    ``compile_cache=False`` leaves JAX's cache settings alone (tests on the
    CPU do both).

    The index is built at JAX's default matmul precision, as a user's
    build runs.  From then on, for the warm-up and the window, JAX's default
    is the configuration's ``matmul_precision``: the configuration states
    float32 distances, and the program's distance kernels take JAX's
    default for their dot.  The default is put back at the end."""
    import jax
    import numpy as np

    from bench import reference as ref
    from bench.system import Program

    t_start = _T_START if t_start is None else t_start
    seed = seed % 2**63   # any whole number; the generators take 63 bits
    devs, pk = devices_and_peaks or device_check(cell["chips"])
    conf = spec.config(cell["config"], config_dirs)
    mix = spec.traffic(cell["traffic"], traffic_dirs)
    k = conf["k"]
    cache = use_compile_cache(str(spec.ROOT)) if compile_cache else None
    cache_files = count_files(cache) if cache else 0
    say(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")

    X, Q = ref.make_corpus(seed, conf["n"], conf["queries"], conf["d"],
                           **conf["corpus"])
    Qh = np.asarray(Q)
    t_build = time.perf_counter()
    built = [(system or Program)(X, conf, k=k, stage_log=trace)]
    say(f"[build] n={conf['n']} d={conf['d']} "
        f"build_s={built[0].build_s:.3f} "
        f"before_build_s={t_build - t_start:.3f} stages={built[0].stages}")
    prev_precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision",
                      conf["matmul_precision"])
    try:
        return _measure(bench, cell, conf, mix, built, X, Q, Qh, seed=seed,
                        seconds=seconds, trace=trace, devs=devs, pk=pk,
                        cache=cache, cache_files=cache_files,
                        t_start=t_start, metric_dirs=metric_dirs)
    finally:
        jax.config.update("jax_default_matmul_precision", prev_precision)


def _measure(bench, cell, conf, mix, built, X, Q, Qh, *, seed, seconds,
             trace, devs, pk, cache, cache_files, t_start, metric_dirs):
    """Warm-up, window, the reference's comparison and the result.  The
    system is handed over in the list ``built``, so that freeing it here,
    before the reference runs, frees it."""
    import jax
    import numpy as np

    from bench import measure
    from bench import reference as ref
    from bench import traffic as tr
    from bench.system import index_config

    k = conf["k"]
    sysm = built.pop()
    server = tr.warm(sysm, Qh, mix, seed)

    def counters():
        c = sysm.counters()
        if server is not None:
            c.update({f"batcher_{a}": b for a, b in
                      sysm.batcher_counters(server).items()})
        return c

    # what set-up made stays: keep the collector's pauses out of the window
    gc.collect()
    gc.freeze()
    c_before = counters()
    setup_s = time.perf_counter() - t_start
    new_entries = count_files(cache) - cache_files if cache else 0
    say(f"[setup] setup_s={setup_s:.3f} compile cache {cache}: "
        f"{new_entries} new entries"
        + (" (this run compiled in set-up)" if new_entries else ""))

    logdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    tracer = Tracer(logdir, counters, TRACED_S) if trace else tr.NoTrace()
    try:
        win = tr.run(sysm, server, Qh, mix, seconds, seed, k=k,
                     tracer=tracer)
        c_after = counters()
        summary = None
        if trace:
            from bench import trace as trace_mod
            summary = trace_mod.read(logdir,
                                     cpu=devs[0].platform == "cpu")
    finally:
        if logdir:
            shutil.rmtree(logdir, ignore_errors=True)
    in_window = delta(c_before, c_after)
    say(f"[window] requests={len(win.due)} answered="
        f"{int(measure.answered(win).sum())} errors={win.errors} "
        f"compiles_in_window={in_window['compiles']} counters={in_window}")
    say(f"[paths] {sysm.paths()}")
    if win.late_s is not None:
        late = np.sort(win.late_s)
        say(f"[generator] late_ms p50={late[len(late) // 2] * 1e3:.3f} "
            f"p99={late[int(len(late) * 0.99)] * 1e3:.3f} "
            f"max={late[-1] * 1e3:.3f}")

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    build_s, stages = sysm.build_s, sysm.stages
    traced = delta(tracer.c0, tracer.c1) if trace else None
    del sysm, server
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()

    t_ref = time.perf_counter()
    gt, _ = ref.top_k(X, Q, k)
    chk = measure.checks(win, X, Q, gt, conf["limits"])
    say(f"[reference] seconds={time.perf_counter() - t_ref:.3f}")

    ctx = {"win": win, "seconds": seconds, "gt": gt, "build_s": build_s,
           "setup_s": setup_s, "config": conf, "traffic": mix,
           "index": index_config(conf["index_config"]),
           "peaks": pk, "stages": stages, "trace": summary,
           "window": in_window, "traced": traced}
    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_of(bench, cell["name"], group):
        v = (spec.metric_reader(m["name"], metric_dirs)(ctx) if trace
             else end_to_end(m["name"], ctx))
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    result = {"correct": measure.passed(chk),
              "attempted": int(len(win.due)),
              "failed": int(chk["unanswered"]["value"]),
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = chk
    for name, c in chk.items():
        say(f"check {name} = {c['value']!r} "
            f"{'at least' if c.get('at_least') else 'limit'} {c['limit']!r}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    cell = spec.workload(bench, args.workload)
    sys.path.insert(0, str(spec.ROOT / "src"))
    try:
        result = run_cell(bench, cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace))
    except NoChip as e:
        say(f"[device] {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
