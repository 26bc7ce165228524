"""From a profiler trace to the numbers the per-layer metrics read.

The benchmark records its own host spans (``bench.<what>``) with
``jax.profiler.TraceAnnotation``, so they sit on the profiler's clock beside
the device's operations.  This module reads the ``.xplane.pb`` file with
JAX's own ``ProfileData`` and reduces it to:

* ``busy_s``    the union of the intervals in which an operation ran on the
                device, inside the traced window, averaged over the chips;
* ``window_s``  the length of the traced window (the ``bench.window`` span);
* ``search_s``  the device busy time that falls inside ``bench.search``
                spans (the search executable and the staging it needs),
                each span widened by ``SKEW_NS`` on either side: the
                device's clock in a TPU v5e trace runs about 1 to 2 ms
                apart from the host's;
* ``device_ops`` the operations that took the most device time, by self
                time (an operation that encloses others on the same line,
                such as a while loop, keeps only the time they leave);
* ``idle_gaps`` the longest idle gaps, each named by the innermost host
                span it fell in (what the host was doing meanwhile).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
SEARCH_SPAN = "bench.search"
# lines of a device plane that repeat what the op line holds, at a coarser
# grain; busy time is read from the op line when the plane has one
OP_LINE = "XLA Ops"
SKEW_NS = 5e6


def find_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns)


def short_name(name: str) -> str:
    """A TPU op event is named by its whole HLO instruction
    (``%fusion.3 = bf16[...] fusion(...)``); keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def self_times(evs):
    """[(name, start, end, self)] of one line's events, where ``self`` is
    the duration less that of the events nested directly inside."""
    out, stack = [], []
    for name, s, e in sorted(evs, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [name, s, e, e - s]
        if stack and e <= stack[-1][2]:
            stack[-1][3] -= e - s
        out.append(rec)
        stack.append(rec)
    return [tuple(r) for r in out]


def _cpu_ops(plane):
    """XLA's CPU backend runs its operations on host threads; they carry
    an ``hlo_module`` stat.  Read only for a run on the CPU, so that the
    reduction can be exercised without a chip."""
    return [ev for ln in plane.lines for ev in self_times(
        (e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
        for e in ln.events
        if e.duration_ns > 0 and any(k == "hlo_module" for k, _ in e.stats))]


def collect(pd, *, cpu: bool = False) -> dict:
    """Host spans and device op events from a ``ProfileData``:
    ``{"spans": [(name, start, end)],
       "devices": {plane: [(name, start, end, self)]}}``,
    times in ns on the profiler's clock.  ``cpu``: the run was on XLA's
    CPU backend, whose operations stand in for the device's."""
    spans, devices, cpu_evs = [], {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [ln for ln in lines if ln.name == OP_LINE] or lines
            evs = [ev for ln in ops for ev in self_times(
                (short_name(n), s, s + d) for n, s, d in _events(ln)
                if d > 0)]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans.extend((n, s, s + d) for n, s, d in _events(ln)
                             if n.startswith(SPAN_PREFIX))
            if cpu:
                cpu_evs.extend(_cpu_ops(plane))
    if cpu and not devices and cpu_evs:
        devices["/host:CPU"] = cpu_evs
    return {"spans": spans, "devices": devices}


def union(intervals):
    """Merge [(start, end)] into sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def overlap(a, b) -> float:
    """Total length of the intersection of two disjoint sorted lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def gaps(busy, lo, hi):
    """The idle intervals of ``busy`` (disjoint, sorted) inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(spans, t) -> str:
    """The innermost (shortest) host span covering time ``t``, without its
    prefix; ``"none"`` where no benchmark span covers it."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and name != WINDOW_SPAN and \
                (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0][len(SPAN_PREFIX):] if best else "none"


def reduce(data: dict, *, top: int = 10) -> dict:
    """The summary described in the module docstring.  Raises when the
    trace holds no window span or no device operation inside it."""
    windows = [(s, e) for n, s, e in data["spans"] if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = min(s for s, _ in windows), max(e for _, e in windows)
    search = union((s - SKEW_NS, e + SKEW_NS) for n, s, e in data["spans"]
                   if n == SEARCH_SPAN)
    host = [sp for sp in data["spans"] if sp[2] > lo and sp[1] < hi]
    busy_ns, search_ns, per_op = [], [], defaultdict(float)
    all_gaps = []
    for evs in data["devices"].values():
        inside = clip([(s, e) for _, s, e, _ in evs], lo, hi)
        busy = union(inside)
        busy_ns.append(sum(e - s for s, e in busy))
        search_ns.append(overlap(busy, search))
        for name, s, e, own in evs:
            if s >= lo and e <= hi:
                per_op[name] += own
            elif min(e, hi) > max(s, lo):   # cut by the window's edge
                per_op[name] += own * (min(e, hi) - max(s, lo)) / (e - s)
        all_gaps.extend(gaps(busy, lo, hi))
    if not busy_ns or max(busy_ns) <= 0:
        raise ValueError("no device operation ran inside the traced window")
    n_dev = len(busy_ns)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_ns) / n_dev * 1e-9,
        "search_s": sum(search_ns) / n_dev * 1e-9,
        "n_devices": n_dev,
        "device_ops": [[n, t / n_dev * 1e-9] for n, t in ops],
        "idle_gaps": [[label(host, (s + e) / 2), (e - s) * 1e-9]
                      for s, e in longest],
    }


def read(logdir: str, *, cpu: bool = False) -> dict:
    from jax.profiler import ProfileData

    return reduce(collect(ProfileData.from_file(find_xplane(logdir)),
                          cpu=cpu))
