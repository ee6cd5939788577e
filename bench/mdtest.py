"""The benchmark's IO500 namespace generator: the files that IO500's
mdtest-easy-write and mdtest-hard-write phases leave in a run's data
directory, as index records. Nothing here imports the program.

IO500 names a run's data directory by the time the run starts
(``<datadir>/YYYY.MM.DD-HH.MM.SS``) and writes its timestamp file then,
before any phase, so every file a phase creates is newer than it. The
phases write in IO500's order, each for ``phase_s`` seconds:

- mdtest-easy (``mdtest -u``, empty files): one directory per rank,
  ``<run>/mdtest-easy/test-dir.0-0/mdtest_tree.<rank>.0/``;
- mdtest-hard (``-w 3901 -e 3901``): 3,901-byte files from every rank in
  one shared directory, ``<run>/mdtest-hard/test-dir.0-0/mdtest_tree.0/``;
- file names are mdtest's ``file.mdtest.<rank>.<item>``; each rank
  creates its items in order, spread evenly over its phase.

``namespace_for`` draws the namespace from the configuration's fixed
``shape_seed`` and lets the run's seed only order its records, so every
seed does the same work.
"""
from __future__ import annotations

import dataclasses
import time
from fractions import Fraction
from typing import Dict, List

import numpy as np

TYPE_FILE = 0


@dataclasses.dataclass
class Namespace:
    paths: np.ndarray                  # (N,) object
    columns: Dict[str, np.ndarray]     # index columns, storage dtypes
    stamp: float                       # the timestamp file's mtime
    run_dir: str                       # <datadir>/<start time>
    n_easy: int
    n_hard: int

    def __len__(self) -> int:
        return len(self.paths)

    def permuted(self, perm: np.ndarray) -> "Namespace":
        return dataclasses.replace(
            self, paths=self.paths[perm],
            columns={k: v[perm] for k, v in self.columns.items()})


def split(n: int, parts: int) -> np.ndarray:
    """``n`` items over ``parts`` ranks, counts differing by at most one
    (the first ranks take the remainder)."""
    out = np.full(parts, n // parts, np.int64)
    out[:n % parts] += 1
    return out


def run_dir(spec: Dict) -> str:
    return spec["datadir"] + "/" + time.strftime(
        "%Y.%m.%d-%H.%M.%S", time.gmtime(float(spec["stamp"])))


def generate(spec: Dict, seed: int) -> Namespace:
    """Easy files rank by rank, then hard files rank by rank."""
    rng = np.random.default_rng(seed)
    n = int(spec["n_files"])
    n_hard = int(n * Fraction(spec["hard_share"]))
    ranks = int(spec["ranks"])
    stamp = float(spec["stamp"])
    phase_s = float(spec["phase_s"])
    order: List[str] = spec["phase_order"]
    base = run_dir(spec)
    paths: List[str] = []
    cols: Dict[str, List[np.ndarray]] = {"size": [], "mtime": [],
                                         "parent": [], "depth": []}
    phases = [("mdtest-easy", n - n_hard, 0.0,
               lambda r: f"mdtest_tree.{r}.0", lambda r: r),
              ("mdtest-hard", n_hard, float(spec["hard_size"]),
               lambda r: "mdtest_tree.0", lambda r: ranks)]
    for phase, count, size, tree, parent in phases:
        start = stamp + phase_s * order.index(phase + "-write")
        for r, k in enumerate(split(count, ranks)):
            d = f"{base}/{phase}/test-dir.0-0/{tree(r)}"
            stem = f"{d}/file.mdtest.{r}."
            paths += map(stem.__add__, map(str, range(k)))
            # item i is created in the (i / k)-th part of the phase
            cols["mtime"].append(start + phase_s * (np.arange(k)
                                                    + rng.random(k)) / k)
            cols["size"].append(np.full(k, size))
            cols["parent"].append(np.full(k, parent(r)))
            cols["depth"].append(np.full(k, d.count("/")))
    n_rows = len(paths)
    mtime = np.concatenate(cols["mtime"]).astype(np.float32)
    columns = {
        "parent": np.concatenate(cols["parent"]).astype(np.int32),
        "depth": np.concatenate(cols["depth"]).astype(np.int32),
        "type": np.full(n_rows, TYPE_FILE, np.int32),
        "mode": np.full(n_rows, int(spec["mode"], 8), np.int32),
        "uid": np.full(n_rows, int(spec["uid"]), np.int32),
        "gid": np.full(n_rows, int(spec["gid"]), np.int32),
        "size": np.concatenate(cols["size"]).astype(np.float32),
        # created and written at once, not read before the find
        "atime": mtime, "ctime": mtime, "mtime": mtime,
        "fileset": np.full(n_rows, -1, np.int32),
    }
    return Namespace(paths=np.asarray(paths, object), columns=columns,
                     stamp=stamp, run_dir=base, n_easy=n - n_hard,
                     n_hard=n_hard)


def namespace_for(spec: Dict, seed: int) -> Namespace:
    """The namespace from the fixed ``shape_seed``, its records in an
    order drawn from ``seed``."""
    ns = generate(spec, int(spec["shape_seed"]))
    return ns.permuted(np.random.default_rng([seed, 7]).permutation(len(ns)))
