"""Multi-device tests (subprocess: device count is locked at jax init)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(code: str, devices: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_distributed_build_and_search():
    out = _run("""
import numpy as np, jax, jax.numpy as jnp, dataclasses
from repro.data.synthetic import make_clustered, recall_at_k
from repro.core import distributed as D
from repro.configs import get_arch
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = jax.make_mesh((4, 2), ('data', 'model'))
ds = make_clustered(n=4096, d=16, n_queries=32, n_clusters=24, noise=0.6, seed=0)
cfg = dataclasses.replace(get_arch('tsdg-paper'), k_graph=12, max_degree=16,
                          lambda0=8, bridge_hubs=32, bridge_k=8,
                          large_ef=48, large_hops=64)
X = jax.device_put(jnp.asarray(ds.X), NamedSharding(mesh, P('data', None)))
nbrs, lams, degs, hubs = D.make_build_fn(mesh, cfg)(X)
search = D.make_search_fn(mesh, cfg, kind='large', k=10)
Q = jax.device_put(jnp.asarray(ds.Q), NamedSharding(mesh, P('model', None)))
ids, dist = search(X, nbrs, lams, degs, hubs, Q)
r = recall_at_k(np.asarray(ids), ds.gt, 10)
assert r > 0.7, r
print('RECALL', r)
""")
    assert "RECALL" in out


def test_compressed_psum_matches_exact():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.optim import compression as C
mesh = jax.make_mesh((4,), ('data',))
g = jnp.arange(64, dtype=jnp.float32).reshape(4, 16) / 7.0
err = jnp.zeros((4, 16))
def f(gs, es):
    out, new_e = C.compressed_psum({'g': gs[0]}, {'g': es[0]}, 'data')
    return out['g'], new_e['g']
fn = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P('data'), P('data')),
             out_specs=(P(), P('data')), check_vma=False))
out, new_err = fn(g[:, None], err[:, None])
exact = jnp.sum(g, axis=0)
rel = float(jnp.max(jnp.abs(out[0] - exact) / (jnp.abs(exact) + 1e-6)))
assert rel < 0.05, rel
# error feedback: second round with same grads corrects toward exact
out2, _ = fn(g[:, None], new_err)
print('OK', rel)
""")


def test_elastic_restore_different_mesh(tmp_path):
    d = str(tmp_path / "elastic")
    # save on a 4-device mesh
    _run(f"""
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as ckpt
mesh = jax.make_mesh((4,), ('data',))
x = jax.device_put(jnp.arange(32.0).reshape(8, 4),
                   NamedSharding(mesh, P('data', None)))
ckpt.save({{'x': x}}, 3, {d!r})
""", devices=4)
    # restore on a 2-device mesh with new shardings
    _run(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.train import checkpoint as ckpt
mesh = jax.make_mesh((2,), ('data',))
tmpl = {{'x': jnp.zeros((8, 4))}}
shard = {{'x': NamedSharding(mesh, P('data', None))}}
state, step = ckpt.restore({d!r}, tmpl, shardings=shard)
assert step == 3
np.testing.assert_array_equal(np.asarray(state['x']),
                              np.arange(32.0).reshape(8, 4))
print('ELASTIC OK')
""", devices=2)


@pytest.mark.slow
def test_reduced_bundle_lowering_multidevice():
    """Representative (arch x shape) bundles lower+compile on a real
    multi-device mesh (reduced configs; full configs live in dryrun.py)."""
    _run("""
import jax, dataclasses
from repro.configs import get_reduced
from repro.configs.base import ShapeSpec
from repro.launch import steps as S
mesh = jax.make_mesh((4, 2), ('data', 'model'))
cfg = get_reduced('olmoe-1b-7b')
shp = ShapeSpec('train', 'train', dict(seq_len=64, global_batch=8))
S.build_lm_bundle(cfg, shp, mesh).lower(mesh).compile()
shp = ShapeSpec('decode', 'decode', dict(seq_len=128, global_batch=8))
S.build_lm_bundle(cfg, shp, mesh).lower(mesh).compile()
cfg = get_reduced('gatedgcn')
shp = ShapeSpec('full_graph_sm', 'train', dict(n_nodes=256, n_edges=1024, d_feat=16))
S.build_gnn_bundle(cfg, shp, mesh).lower(mesh).compile()
cfg = get_reduced('wide-deep')
shp = ShapeSpec('train_batch', 'train', dict(batch=32))
S.build_recsys_bundle(cfg, shp, mesh).lower(mesh).compile()
print('BUNDLES OK')
""")
