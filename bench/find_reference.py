"""Plain reference for IO500's find, ``find -name NAME -size SIZEc
-newer T``, over the benchmark's namespace. Nothing here imports the
program: the same semantics written straightforwardly in numpy and
Python.

- name: ``fnmatch.fnmatchcase`` on the basename, the path after its last
  ``/`` (``rsplit``);
- size: the stored size equals ``SIZE``;
- newer: the stored mtime is strictly after ``T``.

Sizes and mtimes compare at the index's storage precision, float32,
with the arguments rounded to it (``dtype`` lets a control compare at
another precision).
"""
from __future__ import annotations

import fnmatch

import numpy as np


class Find:
    """The answers to one ``-name``/``-size`` pair over a namespace, for
    any ``-newer``: the name and size tests do not depend on ``T``, so
    they run once."""

    def __init__(self, paths: np.ndarray, size: np.ndarray,
                 mtime: np.ndarray, name: str, size_arg: float,
                 dtype=np.float32):
        self.paths = paths
        self.dtype = dtype
        self.mtime = np.asarray(mtime).astype(dtype)
        rows = np.flatnonzero(np.asarray(size).astype(dtype)
                              == np.asarray(size_arg).astype(dtype))
        keep = [fnmatch.fnmatchcase(p.rsplit("/", 1)[-1], name)
                for p in paths[rows]]
        self.rows = rows[np.asarray(keep, bool)]

    def __call__(self, newer: float) -> np.ndarray:
        """Matching paths, in the namespace's row order."""
        t = np.asarray(newer).astype(self.dtype)
        return self.paths[self.rows[self.mtime[self.rows] > t]]
