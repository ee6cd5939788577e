"""The benchmark's own namespace generator: a seeded HPC file system
snapshot, and the pipeline rows a scanner's preprocessing would feed the
counting and aggregate workflows.

``synth_namespace`` draws exactly what ``repro.core.metadata.
synth_filesystem`` draws, in the same order, so both give the same
table for a seed; the per-file path loop is a list comprehension and no
path hash is computed here (the index's route computes it). The copy
lives with the benchmark so that a change to the program's generator
cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict

import numpy as np

import reference

TYPE_FILE, TYPE_LINK, TYPE_DIR = 0, 1, 2

#: columns the primary index stores, with their storage dtypes
INDEX_COLUMNS = {
    "parent": np.int32, "depth": np.int32, "type": np.int32,
    "mode": np.int32, "uid": np.int32, "gid": np.int32,
    "size": np.float32, "atime": np.float32, "ctime": np.float32,
    "mtime": np.float32, "fileset": np.int32,
}


@dataclasses.dataclass
class Namespace:
    paths: np.ndarray      # (N,) object
    parent: np.ndarray     # (N,) int64 row of the parent dir (-1 root)
    depth: np.ndarray      # (N,) int32
    type: np.ndarray       # (N,) int32
    mode: np.ndarray       # (N,) int32
    uid: np.ndarray        # (N,) int32
    gid: np.ndarray        # (N,) int32
    size: np.ndarray       # (N,) float64
    atime: np.ndarray      # (N,) float64
    ctime: np.ndarray      # (N,) float64
    mtime: np.ndarray      # (N,) float64
    fileset: np.ndarray    # (N,) int32
    n_dirs: int

    def __len__(self) -> int:
        return len(self.paths)

    def files(self) -> "Namespace":
        """Files and links only (the files-only preprocessing); the
        directories are the first ``n_dirs`` rows."""
        return Namespace(**{f.name: (getattr(self, f.name)[self.n_dirs:]
                                     if f.name != "n_dirs" else 0)
                            for f in dataclasses.fields(self)})

    def files_permuted(self, perm: np.ndarray) -> "Namespace":
        """The same namespace with its file rows in the order ``perm``
        (the directories stay first and keep their rows)."""
        d = self.n_dirs
        return Namespace(**{f.name: (np.concatenate(
            [getattr(self, f.name)[:d], getattr(self, f.name)[d:][perm]])
            if f.name != "n_dirs" else d)
            for f in dataclasses.fields(self)})

    def index_columns(self) -> Dict[str, np.ndarray]:
        return {k: np.asarray(getattr(self, k), dt)
                for k, dt in INDEX_COLUMNS.items()}


def synth_namespace(n_files: int, n_users: int = 32, n_groups: int = 8,
                    n_dirs: int = 200, max_depth: int = 6, seed: int = 0,
                    now: float = 1.7e9,
                    size_dist: str = "lognormal") -> Namespace:
    """Directories first (geometric depth), then files: zipf owners,
    lognormal (or gamma) sizes, exponential ages, 2% links, 1%
    world-writable."""
    rng = np.random.default_rng(seed)
    dir_parent = np.full(n_dirs, -1, np.int64)
    dir_depth = np.zeros(n_dirs, np.int32)
    dir_paths = ["/fs"] + [""] * (n_dirs - 1)
    for i in range(1, n_dirs):
        p = int(rng.integers(0, i))
        if dir_depth[p] >= max_depth:
            p = 0
        dir_parent[i] = p
        dir_depth[i] = dir_depth[p] + 1
        dir_paths[i] = f"{dir_paths[p]}/d{i}"

    fdir = rng.integers(0, n_dirs, n_files)
    uid = (rng.zipf(1.6, n_files) % n_users).astype(np.int32)
    gid = (uid % n_groups).astype(np.int32)
    if size_dist == "lognormal":
        size = rng.lognormal(mean=9.0, sigma=2.5, size=n_files)
    else:
        size = rng.gamma(1.5, 16e3 / 1.5, size=n_files)
    mtime = now - rng.exponential(180 * 86400, n_files)
    atime = mtime + rng.exponential(30 * 86400, n_files)
    ctime = mtime - rng.uniform(0, 86400, n_files)
    is_link = rng.random(n_files) < 0.02
    mode = np.where(rng.random(n_files) < 0.01, 0o777,
                    rng.choice([0o644, 0o640, 0o600, 0o755], n_files))

    paths = np.empty(n_files + n_dirs, object)
    paths[:n_dirs] = dir_paths
    dirs_of = np.asarray(dir_paths, object)[fdir].tolist()
    paths[n_dirs:] = [f"{d}/f{i}" for i, d in enumerate(dirs_of)]
    return Namespace(
        paths=paths,
        parent=np.concatenate([dir_parent, fdir.astype(np.int64)]),
        depth=np.concatenate([dir_depth, dir_depth[fdir] + 1]
                             ).astype(np.int32),
        type=np.concatenate([np.full(n_dirs, TYPE_DIR, np.int32),
                             np.where(is_link, TYPE_LINK,
                                      TYPE_FILE).astype(np.int32)]),
        mode=np.concatenate([np.full(n_dirs, 0o755, np.int32),
                             mode.astype(np.int32)]),
        uid=np.concatenate([np.zeros(n_dirs, np.int32), uid]),
        gid=np.concatenate([np.zeros(n_dirs, np.int32), gid]),
        size=np.concatenate([np.zeros(n_dirs), size]),
        atime=np.concatenate([np.full(n_dirs, now), atime]),
        ctime=np.concatenate([np.full(n_dirs, now - 86400), ctime]),
        mtime=np.concatenate([np.full(n_dirs, now - 86400), mtime]),
        fileset=np.full(n_files + n_dirs, -1, np.int32),
        n_dirs=n_dirs,
    )


def namespace_for(spec: Dict, seed: int) -> Namespace:
    """The namespace a configuration's ``namespace`` block describes.
    With a ``shape_seed`` the namespace is drawn from that fixed seed and
    ``seed`` only orders its files, so that every seed does the same
    work (the same paths, owners, sizes and times) in another order."""
    n = int(spec["n_files"])
    shape = spec.get("shape_seed")
    ns = synth_namespace(
        n, n_users=int(spec["n_users"]), n_groups=int(spec["n_groups"]),
        n_dirs=max(64, n // int(spec["files_per_dir"])),
        max_depth=int(spec.get("max_depth", 6)),
        seed=seed if shape is None else int(shape),
        now=float(spec["now"]), size_dist=spec.get("size_dist", "lognormal"))
    if shape is None:
        return ns
    return ns.files_permuted(np.random.default_rng([seed, 7]).permutation(n))


def pipeline_rows(ns: Namespace, pcfg: Dict) -> Dict[str, np.ndarray]:
    """Per-file rows of the counting and aggregate workflows (the
    paper's preprocessed scan): the owner's user and group slots, one
    directory-prefix slot per depth in [dir_min, dir_max] (FNV of the
    ancestor directory's path modulo ``n_dirs``, -1 above the file),
    and the crc32 shard of the file's path. ``path_hash`` is left for
    the index's route to fill in."""
    nu, ng, nd = pcfg["n_users"], pcfg["n_groups"], pcfg["n_dirs"]
    lo, hi = pcfg["dir_min"], pcfg["dir_max"]
    levels = hi - lo + 1
    base = nu + ng
    nd_rows = ns.n_dirs
    dir_slots = np.full((nd_rows, levels), -1, np.int64)
    slot = base + (reference.fnv1a(list(ns.paths[:nd_rows])).astype(
        np.int64) % nd)
    chains = [[] for _ in range(nd_rows)]
    for d in range(nd_rows):          # parents precede children
        p = int(ns.parent[d])
        chains[d] = (chains[p] if p >= 0 else []) + [d]
        for li, depth in enumerate(range(lo, hi + 1)):
            if depth < len(chains[d]):
                dir_slots[d, li] = slot[chains[d][depth]]
    f = ns.files()
    shard = np.fromiter((zlib.crc32(p.encode()) % pcfg["n_shards"]
                         for p in f.paths), np.int32, len(f))
    return {
        "uid_slot": (f.uid.astype(np.int64) % nu).astype(np.int32),
        "gid_slot": (nu + f.gid.astype(np.int64) % ng).astype(np.int32),
        "dir_slots": dir_slots[f.parent].astype(np.int32),
        "shard_id": shard,
        "size": f.size.astype(np.float32),
        "atime": f.atime.astype(np.float32),
        "ctime": f.ctime.astype(np.float32),
        "mtime": f.mtime.astype(np.float32),
        "uid": f.uid.astype(np.int32),
        "gid": f.gid.astype(np.int32),
        "mode": f.mode.astype(np.int32),
        "type": f.type.astype(np.int32),
        "path_hash": np.zeros(len(f), np.uint32),
    }
