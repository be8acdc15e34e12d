"""2D edge-partitioned GCN == reference GCN (8 devices, subprocess)."""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np

from repro.graphs.generators import erdos_renyi
from repro.models import gnn as G
from repro.models.gnn_dist import (Grid2D, abstract_inputs, bucket_edges,
                                   build_gcn2d_loss, layout_features,
                                   make_grid)


def main():
    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    rng = np.random.default_rng(0)
    n, d_in, dh, classes = 37, 12, 16, 5
    g = erdos_renyi(n, 0.15, seed=2)
    x = rng.normal(size=(n, d_in)).astype(np.float32)
    labels = rng.integers(0, classes, n).astype(np.int32)
    deg = np.bincount(g.dst, minlength=n).astype(np.float32)
    dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
    coef = (dinv[g.src] * dinv[g.dst]).astype(np.float32)

    # --- reference: plain segment-sum GCN (message part only, no self loop)
    params = {"w": [jnp.asarray(rng.normal(size=(d_in, dh)).astype(np.float32)
                                / np.sqrt(d_in)),
                    jnp.asarray(rng.normal(size=(dh, classes)).astype(np.float32)
                                / np.sqrt(dh))]}

    def ref_loss(params):
        h = jnp.asarray(x)
        for i, w in enumerate(params["w"]):
            hw = h @ w
            m = hw[jnp.asarray(g.src)] * jnp.asarray(coef)[:, None]
            h = jax.ops.segment_sum(m, jnp.asarray(g.dst), num_segments=n)
            if i == 0:
                h = jax.nn.relu(h)
        logz = jax.nn.logsumexp(h, axis=-1)
        gold = jnp.take_along_axis(h, jnp.asarray(labels)[:, None], 1)[:, 0]
        return jnp.mean(logz - gold)

    # --- 2D-partitioned version
    grid = make_grid(mesh, n, g.nnz)
    src_b, dst_b, coef_b = bucket_edges(grid, g.src, g.dst, coef)
    xp = layout_features(grid, x)
    lp = layout_features(grid, labels[:, None].astype(np.float32))[:, 0]
    mask = layout_features(grid, np.ones((n, 1), np.float32))[:, 0] > 0

    loss2d = build_gcn2d_loss(mesh, grid, n_layers=2)
    with jax.sharding.set_mesh(mesh):
        args = (params, jnp.asarray(xp), jnp.asarray(src_b),
                jnp.asarray(dst_b), jnp.asarray(coef_b),
                jnp.asarray(lp.astype(np.int32)), jnp.asarray(mask))
        l2d = jax.jit(loss2d)(*args)
        g2d = jax.jit(jax.grad(loss2d))(*args)

    lref = ref_loss(params)
    gref = jax.grad(ref_loss)(params)
    print("ref loss", float(lref), "2d loss", float(l2d))
    np.testing.assert_allclose(float(lref), float(l2d), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gref), jax.tree.leaves(g2d)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)
    print("2D-partitioned GCN == reference (loss + grads)")
    print("ALL-OK")


if __name__ == "__main__":
    main()
