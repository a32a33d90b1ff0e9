"""Checkpointing with LeaseGuard-committed manifests.

Layout: ``<dir>/step_N/arrays.npz`` (flattened pytree leaves) +
``<dir>/step_N/manifest.json``. The manifest is only authoritative once it
is **committed through the coordinator's Raft log** (coord/registry):
a trainer that crashes mid-save leaves a dangling directory but the
cluster-visible "latest checkpoint" never points at a torn write. On
restart, ``latest_step()`` is a zero-roundtrip leased read.

This is the paper's mechanism doing real work in a training system: the
checkpoint commit is a Raft write; restart discovery is a linearizable
read that costs no quorum roundtrip.
"""

from __future__ import annotations

import hashlib
import json
import os
from typing import Any, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation


def _flatten(tree: Any) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(
            str(p.key) if hasattr(p, "key") else str(p.idx) for p in path)
        arr = np.asarray(leaf)
        if arr.dtype.name == "bfloat16":
            # npz has no native bf16; f32 upcast is lossless and
            # restore_checkpoint casts back to the template dtype
            arr = arr.astype(np.float32)
        flat[key] = arr
    return flat


def _sha256(path: str) -> str:
    digest = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            digest.update(chunk)
    return digest.hexdigest()


def save_checkpoint(directory: str, step: int, state: Any,
                    extra: Optional[dict] = None,
                    registry=None) -> dict:
    """Write arrays + manifest; commit the manifest via the registry
    (LeaseGuard Raft) if one is provided. Returns the manifest. Runs
    under the profiler span ``ckpt.save``, split into ``ckpt.copy``,
    ``ckpt.write``, ``ckpt.hash`` and ``ckpt.commit``."""
    with TraceAnnotation("ckpt.save", step=step):
        path = os.path.join(directory, f"step_{step}")
        os.makedirs(path, exist_ok=True)
        with TraceAnnotation("ckpt.copy"):
            flat = _flatten(state)
        npz_path = os.path.join(path, "arrays.npz")
        with TraceAnnotation("ckpt.write"):
            np.savez(npz_path, **flat)
        with TraceAnnotation("ckpt.hash"):
            sha256 = _sha256(npz_path)
        manifest = {
            "step": step,
            "path": path,
            "n_arrays": len(flat),
            "sha256": sha256,
            "extra": extra or {},
        }
        with TraceAnnotation("ckpt.commit"):
            with open(os.path.join(path, "manifest.json"), "w") as f:
                json.dump(manifest, f, indent=1)
            if registry is not None:
                res = registry.commit_checkpoint(manifest)
                if not res:
                    raise RuntimeError(
                        "coordinator rejected checkpoint commit")
        return manifest


def restore_checkpoint(state_template: Any, manifest: dict) -> Any:
    """Rebuild the pytree from a committed manifest."""
    npz = np.load(os.path.join(manifest["path"], "arrays.npz"))
    paths, treedef = jax.tree_util.tree_flatten_with_path(state_template)
    leaves = []
    for path, leaf in paths:
        key = "/".join(
            str(p.key) if hasattr(p, "key") else str(p.idx) for p in path)
        arr = npz[key]
        leaves.append(jax.numpy.asarray(arr, dtype=leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def verify_checkpoint(manifest: dict) -> bool:
    npz_path = os.path.join(manifest["path"], "arrays.npz")
    if not os.path.exists(npz_path):
        return False
    return _sha256(npz_path) == manifest["sha256"]
