"""Building a deployment through the program's normal entry points: the
pipeline configuration, the routed load of a namespace into a
``ShardedPrimaryIndex``, and the counting and aggregate workflows over
fixed-size row chunks."""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def pipeline_config(p: Dict):
    from repro.core import snapshot as snap
    from repro.core.sketches.ddsketch import DDSketchConfig
    return snap.PipelineConfig(
        n_users=p["n_users"], n_groups=p["n_groups"], n_dirs=p["n_dirs"],
        dir_min=p["dir_min"], dir_max=p["dir_max"], n_shards=p["n_shards"],
        sketch=DDSketchConfig(alpha=p["sketch_alpha"],
                              n_buckets=p["sketch_buckets"],
                              offset=p["sketch_offset"]))


def principal_names(pcfg) -> List[str]:
    return ([f"user:{i}" for i in range(pcfg.n_users)]
            + [f"group:{i}" for i in range(pcfg.n_groups)]
            + [f"dir:{i}" for i in range(pcfg.n_dirs)])


def chunks(n: int, size: int) -> List[Tuple[int, int]]:
    return [(lo, min(lo + size, n)) for lo in range(0, n, size)]


def load_chunk(primary, paths, cols: Dict[str, np.ndarray], lo: int,
               hi: int, version: int, spans) -> np.ndarray:
    """Route one chunk of paths (the hashshard kernel for chunks at or
    above the index's device-route threshold) and upsert its records at
    ``version``. Returns the path hashes."""
    p = paths[lo:hi]
    with spans("route"):
        h, _ = primary.route(p)
    fields = {k: v[lo:hi] for k, v in cols.items()}
    fields["path_hash"] = h
    with spans("upsert"):
        primary.upsert_batch(p, fields, np.full(hi - lo, version, np.int64),
                             hashes=h)
    return h


class Workflows:
    """The counting and aggregate steps (``snapshot.make_counting_step``
    and ``make_aggregate_step`` on a one-device mesh), jitted once for
    ``chunk`` rows, and the program's sketch merge."""

    def __init__(self, pcfg, chunk: int):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import snapshot as snap
        from repro.core.sketches import ddsketch as dds
        from repro.launch.mesh import make_mesh
        self.pcfg = pcfg
        self.chunk = chunk
        self.mesh = make_mesh((1, 1), ("data", "model"))
        self.count = jax.jit(snap.make_counting_step(pcfg, self.mesh))
        self.aggregate = jax.jit(snap.make_aggregate_step(pcfg, self.mesh))
        self.merge = jax.jit(dds.merge)
        self.init = lambda: dds.init(pcfg.sketch,
                                     (pcfg.n_principals, len(snap.ATTRS)))
        self._sharding = lambda nd: NamedSharding(
            self.mesh, P("data", *([None] * (nd - 1))))

    def place(self, rows: Dict[str, np.ndarray], lo: int, hi: int):
        """One chunk of rows on the device, padded to ``chunk`` rows with
        ``valid`` False on the padding."""
        import jax
        n = hi - lo
        out = {}
        for k, v in rows.items():
            x = v[lo:hi]
            if n < self.chunk:
                x = np.concatenate([x, np.zeros((self.chunk - n,)
                                                + x.shape[1:], x.dtype)])
            out[k] = jax.device_put(x, self._sharding(x.ndim))
        valid = np.zeros(self.chunk, bool)
        valid[:n] = True
        return out, jax.device_put(valid, self._sharding(1))

    def run_all(self, rows: Dict[str, np.ndarray], n: int):
        """Counts and merged sketch state over all rows, chunk by
        chunk."""
        counts = np.zeros((self.pcfg.n_principals, self.pcfg.n_shards),
                          np.float64)
        state = self.init()
        for lo, hi in chunks(n, self.chunk):
            rd, vd = self.place(rows, lo, hi)
            counts += np.asarray(self.count(rd, vd), np.float64)
            state = self.merge(state, self.aggregate(rd, vd))
        return counts.astype(np.float32), state
