"""Pallas TPU kernel: FNV-1a hashing of fixed-width byte rows -> shard ids.

The paper's ingestion layer shards work by ``zlib.crc32(row) % 64``; the
TPU analogue hashes fixed-width path-byte rows (padded/truncated to W
bytes) entirely on the VPU with 32-bit wraparound arithmetic.

The byte matrix is transposed and widened to int32 outside the kernel,
so that row r of a block sits at (r // 128, r % 128) of dense (8k, 128)
tiles: byte column i of the block is then the leading-axis slice
``bytes_ref[i]``, and W (a static unroll) costs W fused xor-multiply
passes over VMEM-resident tiles. The kernel keeps the hash in int32 (the
same bits as uint32 under xor and wrapping multiply); the unsigned view
and the shard modulus are taken outside.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.metadata import FNV_OFFSET, FNV_PRIME

LANES = 128
_OFFSET_I32 = int(np.uint32(FNV_OFFSET).view(np.int32))


def _kernel(bytes_ref, len_ref, hash_ref):
    ln = len_ref[...]                              # (SUB, 128) valid length
    h = jnp.full(ln.shape, _OFFSET_I32, jnp.int32)
    for i in range(bytes_ref.shape[0]):            # static unroll over width
        h = jnp.where(i < ln, (h ^ bytes_ref[i]) * FNV_PRIME, h)
    hash_ref[...] = h


def hashshard_pallas(byte_rows: jax.Array, lengths: jax.Array,
                     n_shards: int = 64, *, rows: int = 1024,
                     interpret: bool = True):
    """byte_rows: (N, W) uint8; lengths: (N,) int32. Returns (hash u32,
    shard id int32). ``rows`` (rows per grid step) is a multiple of 128;
    the TPU compiler needs a multiple of 1024 (whole (8, 128) tiles)."""
    assert rows % LANES == 0, rows
    n, w = byte_rows.shape
    n_pad = -(-n // rows) * rows
    sub = rows // LANES
    cols = jnp.pad(byte_rows, ((0, n_pad - n), (0, 0))).T.astype(jnp.int32)
    lens = jnp.pad(lengths.astype(jnp.int32), (0, n_pad - n))
    h = pl.pallas_call(
        _kernel,
        grid=(n_pad // rows,),
        in_specs=[pl.BlockSpec((w, sub, LANES), lambda i: (0, i, 0)),
                  pl.BlockSpec((sub, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((sub, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad // LANES, LANES), jnp.int32),
        interpret=interpret,
        name="hashshard",
    )(cols.reshape(w, n_pad // LANES, LANES),
      lens.reshape(n_pad // LANES, LANES))
    h = jax.lax.bitcast_convert_type(h.reshape(n_pad)[:n], jnp.uint32)
    return h, (h % jnp.uint32(n_shards)).astype(jnp.int32)
