"""Fault-tolerant checkpointing: atomic npz-shard checkpoints with a
manifest, latest-pointer resume, async background saves, and keep-K
retention — the checkpoint/restart half of the fault-tolerance story
(a preempted pod restarts from ``latest`` and continues).

Layout:
  <dir>/step_000100/
      manifest.json            # step, tree structure, shard index, hashes
      shard_00000.npz          # flattened leaves, chunked ~512MB
      _COMMITTED               # written LAST -> crash-safe atomicity
  <dir>/latest                 # text file: name of newest committed step
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

_SHARD_BYTES = 512 * 1024 * 1024


def _tree_paths(tree):
    flat, treedef = jax.tree.flatten(tree)
    return flat, treedef


def content_hash(tree: Any) -> dict:
    """Content identity of a pytree WITHOUT writing it to disk — the same
    sha256[:16] convention the shard manifests use, computed per leaf
    over (dtype, shape, raw bytes) in flatten order plus one combined
    digest. ``QuantArtifact`` records this for the fp params a
    quantization was calibrated against, so a serving process fails fast
    on a wrong-checkpoint mismatch instead of silently sampling garbage.
    """
    flat, _ = _tree_paths(tree)
    leaves = []
    combined = hashlib.sha256()
    for leaf in flat:
        a = np.ascontiguousarray(np.asarray(leaf))
        h = hashlib.sha256()
        h.update(str(a.dtype).encode())
        h.update(str(tuple(a.shape)).encode())
        h.update(a.tobytes())
        leaves.append(h.hexdigest()[:16])
        combined.update(h.digest())
    return {"n_leaves": len(flat), "leaves": leaves,
            "digest": combined.hexdigest()[:16]}


def save(path: str, step: int, tree: Any, keep: int = 3,
         shard_bytes: int = _SHARD_BYTES) -> str:
    """Synchronous atomic save. Returns the checkpoint directory."""
    name = f"step_{step:08d}"
    final = os.path.join(path, name)
    tmp = final + ".tmp"
    os.makedirs(tmp, exist_ok=True)

    flat, treedef = _tree_paths(tree)
    arrays = [np.asarray(l) for l in flat]

    shards, cur, cur_bytes = [], {}, 0
    index = {}
    for i, a in enumerate(arrays):
        if cur_bytes + a.nbytes > shard_bytes and cur:
            shards.append(cur)
            cur, cur_bytes = {}, 0
        cur[f"leaf_{i}"] = a
        index[str(i)] = len(shards)
        cur_bytes += a.nbytes
    shards.append(cur)

    hashes = {}
    for si, sh in enumerate(shards):
        fn = os.path.join(tmp, f"shard_{si:05d}.npz")
        np.savez(fn, **sh)
        with open(fn, "rb") as f:
            hashes[f"shard_{si:05d}.npz"] = hashlib.sha256(
                f.read()).hexdigest()[:16]

    manifest = {
        "step": step,
        "n_leaves": len(arrays),
        "index": index,
        "treedef": jax.tree_util.tree_structure(
            tree).serialize_using_proto().hex(),
        "hashes": hashes,
        "dtypes": [str(a.dtype) for a in arrays],
        "shapes": [list(a.shape) for a in arrays],
    }
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    with open(os.path.join(tmp, "_COMMITTED"), "w") as f:
        f.write("ok")
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)

    with open(os.path.join(path, "latest.tmp"), "w") as f:
        f.write(name)
    os.replace(os.path.join(path, "latest.tmp"), os.path.join(path, "latest"))

    _retain(path, keep)
    return final


_ASYNC_THREAD: Optional[threading.Thread] = None


def save_async(path: str, step: int, tree: Any, keep: int = 3) -> None:
    """Background-thread save. Blocks only on a still-running previous
    save (single-flight), then snapshots to host and returns."""
    global _ASYNC_THREAD
    if _ASYNC_THREAD is not None and _ASYNC_THREAD.is_alive():
        _ASYNC_THREAD.join()
    host_tree = jax.tree.map(lambda a: np.asarray(a), tree)   # device->host now
    _ASYNC_THREAD = threading.Thread(
        target=save, args=(path, step, host_tree, keep), daemon=True)
    _ASYNC_THREAD.start()


def wait_async() -> None:
    if _ASYNC_THREAD is not None and _ASYNC_THREAD.is_alive():
        _ASYNC_THREAD.join()


def latest_step(path: str) -> Optional[int]:
    try:
        with open(os.path.join(path, "latest")) as f:
            name = f.read().strip()
        if os.path.exists(os.path.join(path, name, "_COMMITTED")):
            return int(name.split("_")[1])
    except (FileNotFoundError, ValueError, IndexError):
        pass
    # fall back to scanning (latest pointer lost)
    best = None
    if os.path.isdir(path):
        for d in os.listdir(path):
            if d.startswith("step_") and os.path.exists(
                    os.path.join(path, d, "_COMMITTED")):
                s = int(d.split("_")[1])
                best = s if best is None else max(best, s)
    return best


def _shard_leaves(manifest: dict, shard_idx: int) -> list:
    """Leaf indices stored in shard ``shard_idx`` (manifest order)."""
    return [int(i) for i, si in manifest["index"].items()
            if int(si) == shard_idx]


def verify_shards(path: str, step: Optional[int] = None) -> None:
    """Integrity-check every npz shard of a committed checkpoint against
    the manifest's recorded sha256[:16] content hashes.

    A flipped byte in a shard otherwise surfaces as a cryptic
    numpy/zlib/zip exception deep inside ``np.load`` (or worse, decodes to
    silently wrong values in the uncompressed regions) far from the
    checkpoint path. This names the offending shard file AND the leaves it
    carries (index/dtype/shape), so the error points at what is actually
    lost. Raises ``ValueError`` on corruption, ``FileNotFoundError`` on a
    missing/truncated-away shard.
    """
    step = step if step is not None else latest_step(path)
    if step is None:
        raise FileNotFoundError(f"no committed checkpoint under {path}")
    d = os.path.join(path, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    for si_name in sorted(manifest["hashes"]):
        fn = os.path.join(d, si_name)
        if not os.path.exists(fn):
            raise FileNotFoundError(
                f"checkpoint shard {fn} is missing (manifest lists it)")
        with open(fn, "rb") as f:
            got = hashlib.sha256(f.read()).hexdigest()[:16]
        want = manifest["hashes"][si_name]
        if got == want:
            continue
        si = int(si_name[len("shard_"):-len(".npz")])
        leaves = _shard_leaves(manifest, si)
        desc = ", ".join(
            f"leaf {i} ({manifest['dtypes'][i]}"
            f"{tuple(manifest['shapes'][i])})" for i in leaves[:8])
        more = f", … {len(leaves) - 8} more" if len(leaves) > 8 else ""
        raise ValueError(
            f"checkpoint shard {fn} is corrupted: content hash {got} != "
            f"manifest {want}; expected leaves: {desc}{more}")


def restore(path: str, like: Any, step: Optional[int] = None) -> Any:
    """Restore into the structure of ``like`` (shape/dtype-checked)."""
    step = step if step is not None else latest_step(path)
    if step is None:
        raise FileNotFoundError(f"no committed checkpoint under {path}")
    d = os.path.join(path, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    flat_like, treedef = jax.tree.flatten(like)
    assert manifest["n_leaves"] == len(flat_like), \
        f"leaf count mismatch: ckpt {manifest['n_leaves']} vs {len(flat_like)}"
    cache = {}
    out = []
    for i, proto in enumerate(flat_like):
        si = manifest["index"][str(i)]
        if si not in cache:
            fn = os.path.join(d, f"shard_{si:05d}.npz")
            try:
                cache[si] = np.load(fn)
            except Exception as e:
                raise ValueError(
                    f"checkpoint shard {fn} failed to load "
                    f"({type(e).__name__}: {e}) — run "
                    "checkpoint.ckpt.verify_shards for an integrity "
                    "report") from e
        a = cache[si][f"leaf_{i}"]
        assert list(a.shape) == list(proto.shape), \
            f"leaf {i}: ckpt {a.shape} vs model {proto.shape}"
        out.append(jnp.asarray(a, dtype=proto.dtype))
    return jax.tree.unflatten(treedef, out)


def _retain(path: str, keep: int) -> None:
    steps = sorted(
        d for d in os.listdir(path)
        if d.startswith("step_") and os.path.exists(
            os.path.join(path, d, "_COMMITTED")))
    for d in steps[:-keep]:
        shutil.rmtree(os.path.join(path, d), ignore_errors=True)
