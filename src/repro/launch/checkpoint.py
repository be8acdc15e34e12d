"""Fault-tolerant checkpointing with mesh-resharding restore.

Design (no orbax/tensorstore dependency — built from scratch):

* ``save(path, step, tree)`` — writes one ``.npz`` per host-visible shard
  set plus a JSON manifest, then **atomically renames** the staging
  directory (a crash mid-save never corrupts the latest checkpoint).
* ``restore(path, like=...)`` — loads into the *current* mesh/sharding: the
  arrays are stored unsharded (gathered) with their tree structure, and
  ``jax.device_put`` against the target sharding re-shards, so a checkpoint
  written on a ``(4, 2)`` mesh restores onto ``(2, 4)`` or ``(8,)`` —
  elastic scale up/down.
* ``latest_step(dir)`` / retention — the restart loop's entry point.

For BC runs (``launch/bc_run.py --ckpt-dir``) the checkpoint is tiny: the
cumulative λ accumulator, the batch index and the batch size
(``tests/test_checkpoint.py``).
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
from typing import Any, Dict, Optional

import jax
import numpy as np

_SEP = "/"


def _flatten(tree) -> Dict[str, Any]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _SEP.join(_path_str(p) for p in path)
        flat[key] = leaf
    return flat


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    return str(p)


def save(ckpt_dir: str, step: int, tree, *, keep: int = 3) -> str:
    """Atomic checkpoint write. Returns the final directory path."""
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:010d}")
    stage = final + ".tmp"
    if os.path.exists(stage):
        shutil.rmtree(stage)
    os.makedirs(stage)
    flat = _flatten(tree)
    arrays = {}
    meta = {"step": step, "keys": []}
    for k, v in flat.items():
        arr = np.asarray(jax.device_get(v))
        arrays[k] = arr
        meta["keys"].append({"key": k, "shape": list(arr.shape),
                             "dtype": str(arr.dtype)})
    np.savez(os.path.join(stage, "arrays.npz"),
             **{k.replace(_SEP, "__"): v for k, v in arrays.items()})
    with open(os.path.join(stage, "manifest.json"), "w") as f:
        json.dump(meta, f)
    os.replace(stage, final)  # atomic publish
    _retain(ckpt_dir, keep)
    return final


def _retain(ckpt_dir: str, keep: int) -> None:
    steps = sorted(all_steps(ckpt_dir))
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s:010d}"),
                      ignore_errors=True)


def all_steps(ckpt_dir: str):
    out = []
    if not os.path.isdir(ckpt_dir):
        return out
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_") and not name.endswith(".tmp"):
            out.append(int(name[5:]))
    return out


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return max(steps) if steps else None


def restore(ckpt_dir: str, step: Optional[int] = None, *, like=None):
    """Load a checkpoint. ``like`` (pytree of arrays or ShapeDtypeStructs
    with shardings) re-shards every leaf onto the current mesh."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {ckpt_dir}")
    final = os.path.join(ckpt_dir, f"step_{step:010d}")
    data = np.load(os.path.join(final, "arrays.npz"))
    flat = {k.replace("__", _SEP): data[k] for k in data.files}

    if like is None:
        return flat, step

    like_flat = _flatten(like)
    leaves = {}
    for k, ref in like_flat.items():
        arr = flat[k]
        sharding = getattr(ref, "sharding", None)
        if sharding is not None and not callable(sharding):
            leaves[k] = jax.device_put(arr, sharding)
        else:
            leaves[k] = jax.device_put(arr)
    # rebuild the tree in `like`'s structure
    paths, treedef = jax.tree_util.tree_flatten_with_path(like)
    ordered = []
    for path, _ in paths:
        key = _SEP.join(_path_str(p) for p in path)
        ordered.append(leaves[key])
    return jax.tree_util.tree_unflatten(treedef, ordered), step
