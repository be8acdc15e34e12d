"""Atomic checkpoint save/restore and retention (``repro.launch.checkpoint``)."""
import os

import jax
import numpy as np

from repro.launch import checkpoint as ckpt


def _state():
    # The keys of a bc_run checkpoint (cumulative λ, batch index, batch
    # size), nested one level so tree paths round-trip too. Device dtypes:
    # a restore with ``like=`` puts every leaf back on the device.
    rng = np.random.default_rng(0)
    return {"lam": rng.random(37).astype(np.float32), "batch": np.int32(4),
            "nb": np.int32(16),
            "aux": {"sigma": rng.random((3, 5)).astype(np.float32),
                    "seen": np.arange(6, dtype=np.int32)}}


def test_checkpoint_roundtrip(tmp_path):
    state = _state()
    ckpt.save(str(tmp_path), 5, state)
    restored, step = ckpt.restore(str(tmp_path), like=state)
    assert step == 5
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_atomic_and_retention(tmp_path):
    state = _state()
    for s in range(6):
        ckpt.save(str(tmp_path), s, state, keep=3)
    assert sorted(ckpt.all_steps(str(tmp_path))) == [3, 4, 5]
    assert not any(n.endswith(".tmp") for n in os.listdir(tmp_path))
