"""Sharded, versioned, elastic checkpointing.

Layout (one directory per step):

    ckpt_dir/step_000123/
        leaf files:  <flat-key>.<chunk>.zst   (msgpack+zstd array chunks)
        MANIFEST.json                          (written LAST = commit marker)

- **Atomicity / crash safety**: a step directory without MANIFEST.json is
  incomplete and ignored by discovery; restart resumes from the newest
  complete step (mirrors the paper's snapshot version IDs — stale or
  partial versions are invalidated on ingest).
- **Elasticity**: leaves store the GLOBAL array plus its logical chunking;
  restore re-shards onto any mesh via ``jax.device_put`` with the target
  sharding, so a job checkpointed on (16,16) restarts on (8,16) or
  (2,16,16) unchanged.
- **Chunked leaf files** emulate per-host shard writes (chunk = leading-dim
  slice): on a real pod each host writes its own chunks in parallel.
- **Async**: ``save_async`` hands the host copy to a worker thread.
- **Icicle integration**: every file write emits CREAT/CLOSE events to an
  optional monitor stream — the paper's indexing system watches its own
  training cluster's checkpoints (checkpoint GC queries the primary index).
"""
from __future__ import annotations

import json
import os
import queue
import re
import shutil
import threading
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard as zstd


def _flatten(tree) -> Dict[str, Any]:
    flat = {}

    def walk(prefix, node):
        if isinstance(node, dict):
            for k in sorted(node):
                walk(f"{prefix}.{k}" if prefix else str(k), node[k])
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(f"{prefix}.{i}", v)
        else:
            flat[prefix] = node
    walk("", tree)
    return flat


def _unflatten_into(abstract, flat: Dict[str, Any]):
    def walk(prefix, node):
        if isinstance(node, dict):
            return {k: walk(f"{prefix}.{k}" if prefix else str(k), node[k])
                    for k in sorted(node)}
        if isinstance(node, (list, tuple)):
            vals = [walk(f"{prefix}.{i}", v) for i, v in enumerate(node)]
            return type(node)(vals)
        return flat[prefix]
    return walk("", abstract)


_SAFE = re.compile(r"[^A-Za-z0-9_.\-]")


def _fname(key: str) -> str:
    return _SAFE.sub("_", key)


def save_checkpoint(ckpt_dir: str, step: int, tree, *,
                    chunks: int = 4, event_sink: Optional[Callable] = None,
                    extra_meta: Optional[Dict] = None) -> str:
    """Blocking save. Returns the step directory path."""
    step_dir = os.path.join(ckpt_dir, f"step_{step:09d}")
    tmp_dir = step_dir + ".tmp"
    os.makedirs(tmp_dir, exist_ok=True)
    flat = _flatten(tree)
    comp = zstd.ZstdCompressor(level=3)
    manifest = {"step": step, "leaves": {}, "time": time.time(),
                "extra": extra_meta or {}}
    for key, arr in flat.items():
        a = np.asarray(arr)
        n_chunks = min(chunks, a.shape[0]) if a.ndim >= 1 and a.shape[0] >= chunks else 1
        splits = np.array_split(a, n_chunks, axis=0) if a.ndim >= 1 else [a]
        files = []
        for ci, chunk in enumerate(splits):
            fn = f"{_fname(key)}.{ci}.zst"
            payload = msgpack.packb({
                "shape": list(chunk.shape), "dtype": str(chunk.dtype),
                "data": chunk.tobytes(),
            }, use_bin_type=True)
            with open(os.path.join(tmp_dir, fn), "wb") as f:
                f.write(comp.compress(payload))
            files.append(fn)
            if event_sink:
                event_sink("CREAT", os.path.join(step_dir, fn))
        manifest["leaves"][key] = {
            "shape": list(a.shape), "dtype": str(a.dtype), "files": files,
        }
    with open(os.path.join(tmp_dir, "MANIFEST.json"), "w") as f:
        json.dump(manifest, f)
    os.replace(tmp_dir, step_dir)  # atomic publish
    if event_sink:
        event_sink("CLOSE", os.path.join(step_dir, "MANIFEST.json"))
    return step_dir


def load_checkpoint(ckpt_dir: str, abstract_tree, *, step: Optional[int] = None,
                    shardings=None):
    """Restore (optionally re-sharded onto a different mesh)."""
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no complete checkpoint in {ckpt_dir}")
    step_dir = os.path.join(ckpt_dir, f"step_{step:09d}")
    with open(os.path.join(step_dir, "MANIFEST.json")) as f:
        manifest = json.load(f)
    dec = zstd.ZstdDecompressor()
    flat_abs = _flatten(abstract_tree)
    flat_sh = _flatten(shardings) if shardings is not None else {}
    out = {}
    for key, meta in manifest["leaves"].items():
        if key not in flat_abs:
            continue
        parts = []
        for fn in meta["files"]:
            with open(os.path.join(step_dir, fn), "rb") as f:
                payload = msgpack.unpackb(dec.decompress(f.read()), raw=False)
            parts.append(np.frombuffer(payload["data"],
                                       np.dtype(payload["dtype"])
                                       ).reshape(payload["shape"]))
        a = np.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
        a = a.reshape(meta["shape"]).astype(np.dtype(meta["dtype"]))
        want = flat_abs[key]
        a = a.astype(want.dtype)
        if key in flat_sh and flat_sh[key] is not None:
            out[key] = jax.device_put(a, flat_sh[key])
        else:
            out[key] = jnp.asarray(a)
    missing = set(flat_abs) - set(out)
    if missing:
        raise KeyError(f"checkpoint missing leaves: {sorted(missing)[:5]}...")
    return _unflatten_into(abstract_tree, out), manifest


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Newest COMPLETE step (manifest present) — partial writes skipped."""
    if not os.path.isdir(ckpt_dir):
        return None
    best = None
    for d in os.listdir(ckpt_dir):
        m = re.match(r"step_(\d+)$", d)
        if m and os.path.exists(os.path.join(ckpt_dir, d, "MANIFEST.json")):
            s = int(m.group(1))
            best = s if best is None else max(best, s)
    return best


class CheckpointManager:
    """keep_n retention + async saves + optional Icicle event emission."""

    def __init__(self, ckpt_dir: str, keep_n: int = 3,
                 event_sink: Optional[Callable] = None):
        self.dir = ckpt_dir
        self.keep_n = keep_n
        self.event_sink = event_sink
        self._q: "queue.Queue" = queue.Queue()
        self._worker: Optional[threading.Thread] = None
        os.makedirs(ckpt_dir, exist_ok=True)

    def save(self, step: int, tree, blocking: bool = True) -> None:
        host_tree = jax.tree.map(np.asarray, tree)  # device->host copy now
        if blocking:
            save_checkpoint(self.dir, step, host_tree,
                            event_sink=self.event_sink)
            self.gc()
        else:
            if self._worker is None or not self._worker.is_alive():
                self._worker = threading.Thread(target=self._drain,
                                                daemon=True)
                self._worker.start()
            self._q.put((step, host_tree))

    def _drain(self):
        while True:
            try:
                step, tree = self._q.get(timeout=2.0)
            except queue.Empty:
                return
            save_checkpoint(self.dir, step, tree, event_sink=self.event_sink)
            self.gc()

    def wait(self):
        if self._worker is not None:
            self._worker.join(timeout=60)

    def restore(self, abstract_tree, shardings=None, step=None):
        return load_checkpoint(self.dir, abstract_tree, step=step,
                               shardings=shardings)

    def latest(self) -> Optional[int]:
        return latest_step(self.dir)

    def gc(self) -> None:
        steps = sorted(
            int(m.group(1)) for d in os.listdir(self.dir)
            if (m := re.match(r"step_(\d+)$", d))
            and os.path.exists(os.path.join(self.dir, d, "MANIFEST.json")))
        for s in steps[:-self.keep_n] if self.keep_n else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:09d}"),
                          ignore_errors=True)
        # incomplete tmp dirs from crashes
        for d in os.listdir(self.dir):
            if d.endswith(".tmp"):
                shutil.rmtree(os.path.join(self.dir, d), ignore_errors=True)
