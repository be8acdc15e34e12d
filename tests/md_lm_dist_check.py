"""Distributed LM training equivalence (8 CPU devices, subprocess).

The sharded train step (FSDP+TP via logical rules) must produce the same
loss trajectory as the single-device step — GSPMD partitioning is
numerics-preserving modulo reduction order.
"""
import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax
import jax.numpy as jnp
import numpy as np

from repro.data.pipeline import LMDataConfig, LMPipeline
from repro.models import transformer as T
from repro.optim import adamw
from repro.sharding.rules import NO_SHARDING, make_policy

CFG = T.TransformerConfig(name="d", n_layers=2, d_model=64, n_heads=4,
                          n_kv=2, d_ff=128, vocab=256, head_dim=16)
OPT = adamw.AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=20)


def run(policy, shard=False):
    params = T.init_params(CFG, jax.random.key(0))
    if shard:
        logical = T.param_logical_axes(CFG, policy.model_size)
        shardings = jax.tree.map(
            policy.named, logical, is_leaf=lambda x: isinstance(x, tuple))
        params = jax.tree.map(
            lambda a, s: jax.device_put(a, s) if s is not None else a,
            params, shardings, is_leaf=lambda x: hasattr(x, "shape"))
    opt = adamw.init_state(params)
    pipe = LMPipeline(LMDataConfig(vocab=256, batch=4, seq=32, seed=3))

    @jax.jit
    def step(params, opt, tokens, targets):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(CFG, p, tokens, targets, policy))(params)
        params, opt, _ = adamw.update(OPT, grads, opt, params)
        return params, opt, loss

    losses = []
    for s in range(5):
        b = pipe.batch(s)
        params, opt, loss = step(params, opt, jnp.asarray(b["tokens"]),
                                 jnp.asarray(b["targets"]))
        losses.append(float(loss))
    return losses


def main():
    assert len(jax.devices()) == 8
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
    base = run(NO_SHARDING, shard=False)
    with jax.sharding.set_mesh(mesh):
        sharded = run(make_policy(mesh), shard=True)
    print("single:", np.round(base, 5))
    print("sharded:", np.round(sharded, 5))
    np.testing.assert_allclose(base, sharded, rtol=2e-4)
    print("ALL-OK")


if __name__ == "__main__":
    main()
