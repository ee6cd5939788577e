"""jnp + host oracles for the hashshard kernel."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.metadata import FNV_OFFSET as _FNV_OFFSET
from repro.core.metadata import FNV_PRIME as _FNV_PRIME

FNV_OFFSET = np.uint32(_FNV_OFFSET)
FNV_PRIME = np.uint32(_FNV_PRIME)


def hashshard_ref(byte_rows: jax.Array, lengths: jax.Array,
                  n_shards: int = 64):
    b = byte_rows.astype(jnp.uint32)
    n, w = b.shape
    h = jnp.full((n,), jnp.uint32(FNV_OFFSET))
    col = jnp.arange(w)
    valid = col[None, :] < lengths[:, None]
    for i in range(w):
        h_new = (h ^ jnp.where(valid[:, i], b[:, i], 0)) \
            * jnp.uint32(FNV_PRIME)
        h = jnp.where(valid[:, i], h_new, h)
    return h, (h % jnp.uint32(n_shards)).astype(jnp.int32)


def hashshard_host(strings, n_shards: int = 64):
    """Host oracle — identical to metadata.path_hash."""
    out_h, out_s = [], []
    for s in strings:
        h = np.uint32(FNV_OFFSET)
        for byte in s.encode("utf-8"):
            h = np.uint32((int(h) ^ byte) * int(FNV_PRIME) & 0xFFFFFFFF)
        out_h.append(h)
        out_s.append(int(h) % n_shards)
    return np.array(out_h, np.uint32), np.array(out_s, np.int32)


def encode_strings(strings, width: int = 128):
    """Strings -> (N, W) uint8 + lengths (host-side packing)."""
    n = len(strings)
    rows = np.zeros((n, width), np.uint8)
    lens = np.zeros(n, np.int32)
    for i, s in enumerate(strings):
        raw = s.encode("utf-8")[:width]
        rows[i, :len(raw)] = np.frombuffer(raw, np.uint8)
        lens[i] = len(raw)
    return rows, lens


def encode_strings_np(strings, width: int = 128):
    """Vectorized ``encode_strings`` (numpy bytes coercion instead of a
    per-row Python loop), zero-padded to the full ``width``. Returns
    (rows, lens, truncated): ``truncated`` marks rows longer than
    ``width`` whose hash would desync from the full-length host hash —
    callers patch those through the scalar fallback. Non-ASCII batches
    fall back to the loop encoder."""
    n = len(strings)
    try:
        b = np.array(strings if isinstance(strings, list)
                     else list(strings), dtype=np.bytes_)
    except UnicodeEncodeError:
        # non-ASCII (incl. lone surrogates from os.fsdecode'd non-UTF-8
        # filenames): pack row by row with the same surrogatepass
        # encoding the scalar hash family uses
        rows = np.zeros((n, width), np.uint8)
        lens = np.zeros(n, np.int32)
        full = np.zeros(n, np.int64)
        for i, s in enumerate(strings):
            raw = s.encode("utf-8", "surrogatepass")
            full[i] = len(raw)
            raw = raw[:width]
            rows[i, :len(raw)] = np.frombuffer(raw, np.uint8)
            lens[i] = len(raw)
        return rows, lens, full > width
    w = b.dtype.itemsize
    full_lens = np.char.str_len(b).astype(np.int32)
    mat = b.view(np.uint8).reshape(n, w)
    if w < width:
        mat = np.pad(mat, ((0, 0), (0, width - w)))
    elif w > width:
        mat = np.ascontiguousarray(mat[:, :width])
    return mat, np.minimum(full_lens, width), full_lens > width


#: narrowest row width of a joined-buffer encode
MIN_ROUTE_WIDTH = 32


def width_bucket(max_len: int, cap: int) -> int:
    """Row width for a batch whose longest path is ``max_len`` bytes: the
    power of two at or above it, at least ``MIN_ROUTE_WIDTH``, capped at
    ``cap``."""
    return min(cap, max(MIN_ROUTE_WIDTH,
                        1 << max(int(max_len) - 1, 0).bit_length()))


def width_buckets(cap: int):
    """Every width ``width_bucket`` can return under ``cap``."""
    out, w = [], MIN_ROUTE_WIDTH
    while w < cap:
        out.append(w)
        w *= 2
    return out + [cap]


def encode_strings_joined(strings, width: int = 128):
    """``encode_strings_np`` for the batch-routing hot path, at the
    batch's own width: the batch is joined into one UTF-8 buffer
    (``surrogatepass``, as the scalar hash family encodes), the NUL
    separators give each row's start and byte length, and one gather
    over a strided view of the buffer cuts the rows out at
    ``width_bucket(longest, width)`` bytes. Returns (rows, lens,
    truncated) as ``encode_strings_np`` does, except that bytes past a
    row's length are not zeroed (they hold the separator and the next
    paths): the hashshard kernel and its jnp oracle read only
    ``i < len``. A batch in which some path holds a NUL cannot be split
    on the separators and falls back to ``encode_strings_np``."""
    n = len(strings)
    raw = ("\0".join(strings) + "\0" * width).encode("utf-8",
                                                       "surrogatepass")
    buf = np.frombuffer(raw, np.uint8)
    m = len(buf) - width
    seps = np.flatnonzero(buf[:m] == 0)
    if len(seps) != n - 1:
        return encode_strings_np(strings, width)
    starts = np.empty(n, np.int64)
    starts[0] = 0
    starts[1:] = seps + 1
    full = np.empty(n, np.int64)
    full[:-1] = seps
    full[-1] = m
    full -= starts
    wb = width_bucket(full.max(), width)
    rows = np.lib.stride_tricks.sliding_window_view(buf, wb)[starts]
    return rows, np.minimum(full, width).astype(np.int32), full > width
