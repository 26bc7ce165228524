#!/usr/bin/env python3
"""Record the small device trace that tests/bench/test_bench_trace.py reads.

    python3 tests/bench/record_trace.py <out_dir> [--dump]

On one chip it runs a jitted matmul chain a few times inside the
benchmark's own spans (``bench.window`` around ``bench.search`` calls, with
host sleeps between them), so the trace holds device operations, idle
gaps and host spans on one clock.  ``--dump`` prints every plane and line
of the trace and a few events of each, to read the trace's layout by hand.
"""
import glob
import os
import shutil
import sys
import tempfile
import time


def main() -> int:
    import jax
    import jax.numpy as jnp

    out = sys.argv[1]
    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2

    @jax.jit
    def step(a):
        for _ in range(4):
            a = jnp.tanh(a @ a) * 0.5
        return a

    a = jnp.ones((1024, 1024), jnp.float32) * 0.01
    step(a).block_until_ready()
    logdir = tempfile.mkdtemp()
    jax.profiler.start_trace(logdir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.search"):
                step(a).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.sleep"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                    recursive=True)[0]
    os.makedirs(out, exist_ok=True)
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    if "--dump" in sys.argv:
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(src)
        for plane in pd.planes:
            print("PLANE", plane.name)
            for line in plane.lines:
                evs = list(line.events)
                print("  LINE", repr(line.name), len(evs))
                for e in evs[:4]:
                    print("    ", repr(e.name[:100]), e.start_ns,
                          e.duration_ns, list(e.stats)[:6])
    return 0


if __name__ == "__main__":
    sys.exit(main())
