"""Ahead-of-time compiles of the ANN path's Pallas kernels for a TPU v5e.

Interpret mode runs the kernel bodies as XLA ops, so it never checks what
Mosaic accepts: layouts, tiling, SMEM and VMEM limits.  These cases compile
each kernel with ``interpret=False`` for a described (not attached) v5e at
the search shapes of ``ANN_SHAPES`` (N = 2^20, d = 128, degree 32), and
check that the kernel really is in the program (``tpu_custom_call``) rather
than an interpret-mode emulation.  Nothing runs, so nothing here says
anything about results or speed.

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this file.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import l2dist as L2
from repro.kernels import topk as TK
from repro.kernels import visited as VF

N, D, M = 1 << 20, 128, 32       # ANN_SHAPES build_1m / search_* corpus
F32, I32, I8, BOOL = jnp.float32, jnp.int32, jnp.int8, jnp.bool_


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


def _block():
    def f(q, v, m, *s):
        return L2.block_distances_pallas(q, v, m, *s, interpret=False)
    return f


def _gather(self_q=False):
    def f(q, x, i, m, *s):
        return L2.gather_block_distances_pallas(q, x, i, m, *s,
                                                self_q=self_q,
                                                interpret=False)
    return f


def _merge(keep, masked):
    if masked:
        return lambda d, i, m: TK.rank_merge_pallas(d, i, m, keep=keep,
                                                    interpret=False)
    return lambda d, i: TK.rank_merge_pallas(d, i, keep=keep,
                                             interpret=False)


# name -> (function, [(shape, dtype), ...]); shapes are the serving and
# build shapes at 1M x 128: small regime S = 10 * t0 = 640 rows, large
# regime S = 10240, diversify tiles T = 2048 x (2 * degree), nn_descent
# tiles of 16384 rows x (k + k * sample) = 288 candidates
CASES = {
    "block_fp32": (_block(), [((640, 1, D), F32), ((640, M, D), F32),
                              ((640, M), BOOL)]),
    "block_int8": (_block(), [((640, 1, D), F32), ((640, M, D), I8),
                              ((640, M), BOOL), ((640, M), F32)]),
    "block_self_query": (_block(), [((2048, 64, D), F32),
                                    ((2048, 64, D), F32),
                                    ((2048, 64), BOOL)]),
    "delta_scan": (lambda q, v, m: L2.block_distances_pallas(
        q, v, m, bs=1, interpret=False),
        [((1, 32, D), F32), ((1, 256, D), F32), ((1, 256), BOOL)]),
    "gather_fp32_small_batch": (_gather(), [
        ((640, 1, D), F32), ((N, D), F32), ((640, M), I32),
        ((640, M), BOOL)]),
    "gather_fp32_large_batch": (_gather(), [
        ((10240, 1, D), F32), ((N, D), F32), ((10240, M), I32),
        ((10240, M), BOOL)]),
    "gather_int8": (_gather(), [
        ((640, 1, D), F32), ((N, D), I8), ((640, M), I32),
        ((640, M), BOOL), ((640, M), F32)]),
    "gather_self_query": (lambda x, i, m: L2.gather_block_distances_pallas(
        None, x, i, m, self_q=True, interpret=False),
        [((N, D), F32), ((2048, 64), I32), ((2048, 64), BOOL)]),
    "gather_nn_descent": (_gather(), [
        ((16384, 1, D), F32), ((N, D), F32), ((16384, 288), I32),
        ((16384, 288), BOOL)]),
    "visited_filter": (lambda t, i, v: VF.visited_filter_pallas(
        t, i, v, interpret=False),
        [((10240, 8, 2048), I32), ((10240, M), I32), ((10240, M), BOOL)]),
}
for _w in (32, 64, 512, 2048):
    _rows = 10240 if _w < 512 else 640
    CASES[f"rank_merge_{_w}"] = (_merge(min(_w, 32), False),
                                 [((_rows, _w), F32), ((_rows, _w), I32)])
    CASES[f"rank_merge_{_w}_masked"] = (
        _merge(min(_w, 32), True),
        [((_rows, _w), F32), ((_rows, _w), I32), ((_rows, _w), BOOL)])


# the name each case's kernel carries in the program, and so in a trace
KERNEL_NAMES = {
    "block_fp32": "block_distances", "block_int8": "block_distances_int8",
    "block_self_query": "block_distances", "delta_scan": "block_distances",
    "gather_fp32_small_batch": "gather_distances",
    "gather_fp32_large_batch": "gather_distances",
    "gather_int8": "gather_distances_int8",
    "gather_self_query": "gather_pair_distances",
    "gather_nn_descent": "gather_distances",
    "visited_filter": "visited_filter",
}
for _name in CASES:
    if _name.startswith("rank_merge"):
        KERNEL_NAMES[_name] = ("rank_merge_masked" if _name.endswith("masked")
                               else "rank_merge")


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(spec, name):
    fn, shapes = CASES[name]
    args = [spec(shape, dtype) for shape, dtype in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, name
    assert re.search(rf"%{KERNEL_NAMES[name]}\.\d+ = .*tpu_custom_call",
                     text), name
