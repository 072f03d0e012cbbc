"""Distributed PDASC + collectives (8 fake devices, subprocess-isolated)."""

from conftest import run_in_devices


def test_exact_merge_and_butterfly():
    out = run_in_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as dd
from repro.kernels.ref import knn_ref
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(0)
db = jnp.asarray(rng.normal(size=(1600, 16)).astype(np.float32))
Q = jnp.asarray(rng.normal(size=(8, 16)).astype(np.float32))
gd, gi = dd.exact_knn_sharded(db, Q, mesh, db_axes=("data",), distance="l2", k=10)
wd, wi = knn_ref(Q, db, 10, "l2")
assert float(jnp.max(jnp.abs(gd - wd))) < 1e-5
for i in range(8):
    assert set(np.asarray(gi[i]).tolist()) == set(np.asarray(wi[i]).tolist())
gd2, gi2 = dd.exact_knn_sharded(db, Q, mesh, db_axes=("data",), distance="l2",
                                k=10, merge="allgather")
assert bool(jnp.allclose(gd, gd2))
# multi-axis merge (data then model)
gd3, _ = dd.exact_knn_sharded(db, Q, mesh, db_axes=("data", "model"),
                              distance="l2", k=10)
assert bool(jnp.allclose(gd, gd3))
print("MERGE_OK")
""")
    assert "MERGE_OK" in out


def test_sharded_build_search_recall():
    out = run_in_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as dd, distances as dl, radius as rl
from repro.kernels.ref import knn_ref
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))
rng = np.random.default_rng(1)
db = jnp.asarray(rng.normal(size=(1600, 12)).astype(np.float32))
Q = jnp.asarray(rng.normal(size=(16, 12)).astype(np.float32))
dist = dl.get("euclidean")
sidx = dd.build_sharded(db, mesh, db_axes=("data",), gl=50,
                        distance="euclidean")
assert sidx.levels[0].points.shape[0] == 4  # one sub-index per data shard
r = rl.estimate_radius(db, dist, quantile=0.85)
res = dd.search_sharded(sidx, Q, mesh, db_axes=("data",), dist=dist, k=10,
                        r=float(r), mode="dense")
_, gt = knn_ref(Q, db, 10, "l2")
rec = np.mean([len(set(np.asarray(res.ids[i]).tolist())
                   & set(np.asarray(gt[i]).tolist())) / 10 for i in range(16)])
assert rec > 0.9, rec
# ids must be valid global rows
ids = np.asarray(res.ids)
assert ((ids >= -1) & (ids < 1600)).all()
print("SHARDED_OK", rec)
""")
    assert "SHARDED_OK" in out


def test_butterfly_is_permutation_invariant():
    """Global top-k must not depend on which shard holds which rows."""
    out = run_in_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed as dd
from repro.launch.mesh import make_mesh

mesh = make_mesh((8,), ("data",))
rng = np.random.default_rng(2)
db = rng.normal(size=(800, 8)).astype(np.float32)
Q = jnp.asarray(rng.normal(size=(4, 8)).astype(np.float32))
d1, i1 = dd.exact_knn_sharded(jnp.asarray(db), Q, mesh, db_axes=("data",), k=7)
perm = rng.permutation(800)
d2, i2 = dd.exact_knn_sharded(jnp.asarray(db[perm]), Q, mesh,
                              db_axes=("data",), k=7)
assert np.allclose(np.asarray(d1), np.asarray(d2), atol=1e-5)
# map permuted ids back
i2_orig = perm[np.asarray(i2)]
for q in range(4):
    assert set(np.asarray(i1[q]).tolist()) == set(i2_orig[q].tolist())
print("PERM_OK")
""")
    assert "PERM_OK" in out


def test_compressed_dp_step_runs_and_learns():
    out = run_in_devices("""
import numpy as np, jax, jax.numpy as jnp
from repro.launch.mesh import make_mesh
from repro.optim import AdamWConfig, adamw_init
from repro.train.dp_step import make_compressed_dp_step

mesh = make_mesh((2, 4), ("pod", "data"))
rng = np.random.default_rng(3)
W_true = rng.normal(size=(16, 1)).astype(np.float32)

def loss_fn(params, batch):
    pred = batch["x"] @ params["w"]
    return jnp.mean((pred - batch["y"]) ** 2), {}

params = {"w": jnp.zeros((16, 1), jnp.float32)}
opt = adamw_init(params)
step, init_cs = make_compressed_dp_step(
    loss_fn, mesh, AdamWConfig(lr=3e-2, weight_decay=0.0, total_steps=100,
                               warmup_steps=0, schedule="constant"),
    compress_ratio=0.25)
cs = init_cs(params)
losses = []
with jax.set_mesh(mesh):
    for s in range(60):
        x = rng.normal(size=(64, 16)).astype(np.float32)
        y = x @ W_true
        params, opt, cs, m = step(params, opt, cs,
                                  {"x": jnp.asarray(x), "y": jnp.asarray(y)})
        losses.append(float(m["loss"]))
assert losses[-1] < losses[0] * 0.2, (losses[0], losses[-1])
print("DP_OK", losses[0], losses[-1])
""")
    assert "DP_OK" in out
