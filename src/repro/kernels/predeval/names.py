"""Basename prefilter for ``find -name`` (DESIGN.md §13.7): a device
pass beside the predicate kernel, over a per-shard basename byte column.

The column holds each slot's basename (the path after its last ``/``)
as UTF-8 bytes, zero-padded to ``NAME_BYTES``, in a bit-major layout
``(NAME_BYTES, 32, n_pad // 32)``: row r = 32 * w + b sits at
``[:, b, w]``, so the test's (32, W) verdict packs into words with a sum
over its leading axis, lane-dense over W. A row whose basename is
longer than ``NAME_BYTES``, or is not strict UTF-8 (surrogate escapes),
carries a keep flag and always passes.

A row passes when every literal run of the glob is a byte substring of
its basename, or when its keep flag is set. Every literal run occurs in
any basename the glob matches, and UTF-8 keeps substring relations
between valid strings, so the test only ever over-matches: the host's
exact ``fnmatchcase`` on the survivors decides. The literals are a
traced operand padded to a (count, length) bucket, so new arguments of
the same bucket reuse the compiled pass. It is jax.numpy (an XLA
fusion), not a Pallas call: the predicate kernel's custom call stays the
only one on the find's device path.

Bit order of the packed words is ``ref.unpack_bits``'s: row r is bit
(r % 32) of word r // 32.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

from repro.kernels.predeval.ref import _pow2

#: basename bytes held per row; mdtest's ``file.mdtest.<rank>.<item>``
#: takes at most 21
NAME_BYTES = 32
#: literal runs tested on the device, longest first; a glob with more
#: tests only these (fewer literals only means less pruning)
MAX_LITERALS = 4
#: paths encoded per host step while the column is built
_CHUNK = 1 << 16


@dataclasses.dataclass
class NameColumn:
    """One shard epoch's basename bytes and keep flags, on the device,
    padded to the arena's ``n_pad`` rows (padding rows hold zeros and no
    flag). ``n_keep`` counts the keep-flagged rows."""

    names: jax.Array       # (NAME_BYTES, 32, n_pad // 32) uint8
    keep: jax.Array        # (32, n_pad // 32) bool
    n_pad: int
    n_keep: int


def _basenames(rows: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
    """(len(rows), NAME_BYTES) uint8 basename bytes and the keep flags,
    from one UTF-8 buffer of the NUL-joined paths."""
    m = len(rows)
    buf = np.frombuffer("\0".join(rows).encode("utf-8", "surrogatepass"),
                        np.uint8)
    seps = np.flatnonzero(buf == 0)
    if len(seps) != m - 1:         # a path holds NUL: keep every row
        return np.zeros((m, NAME_BYTES), np.uint8), np.ones(m, bool)
    ends = np.append(seps, len(buf))
    starts = np.r_[0, seps + 1]
    slashes = np.r_[-1, np.flatnonzero(buf == ord("/"))]
    base = np.maximum(starts,
                      slashes[np.searchsorted(slashes, ends) - 1] + 1)
    blen = ends - base
    buf = np.concatenate([buf, np.zeros(NAME_BYTES, np.uint8)])
    out = sliding_window_view(buf, NAME_BYTES)[base]
    out[np.arange(NAME_BYTES) >= blen[:, None]] = 0
    keep = blen > NAME_BYTES
    # a lone surrogate encodes as ED A0..BF; valid UTF-8 puts 80..9F
    # after ED
    sur = np.flatnonzero((buf[:-1] == 0xED) & (buf[1:] >= 0xA0))
    row = np.searchsorted(starts, sur, side="right") - 1
    keep[row[sur >= base[row]]] = True
    return out, keep


def pack_names(paths: np.ndarray, n: int, n_pad: int) -> NameColumn:
    """Build the column from a shard's first ``n`` slot paths, padded to
    ``n_pad`` rows (a multiple of 32: the arena's), and copy it to the
    device."""
    names = np.zeros((NAME_BYTES, 32, n_pad // 32), np.uint8)
    keep = np.zeros((32, n_pad // 32), bool)
    for lo in range(0, n, _CHUNK):
        hi = min(n, lo + _CHUNK)
        got, flag = _basenames(paths[lo:hi].tolist())
        # the chunk as whole words, its flags as one more byte plane
        w0, w1 = lo // 32, -(-hi // 32)
        blk = np.zeros((32 * (w1 - w0), NAME_BYTES + 1), np.uint8)
        blk[:hi - lo, :NAME_BYTES] = got
        blk[:hi - lo, NAME_BYTES] = flag
        planes = blk.reshape(-1, 32, NAME_BYTES + 1).T
        names[:, :, w0:w1] = planes[:NAME_BYTES]
        keep[:, w0:w1] = planes[NAME_BYTES]
    return NameColumn(jnp.asarray(names), jnp.asarray(keep), n_pad,
                      int(keep.sum()))


def name_literals(literals: Sequence[str]
                  ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """The traced literal operand: ((L, B) uint8 bytes, (L,) int32
    lengths), L and B padded to powers of two (B at least 4); padding
    literals have length 0 and pass every row. A literal is cut to its
    first ``NAME_BYTES`` bytes, a substring of it. None when no literal
    is left (the host then matches alone)."""
    enc = sorted({s.encode("utf-8", "surrogatepass")[:NAME_BYTES]
                  for s in literals if s}, key=lambda e: (-len(e), e))
    enc = enc[:MAX_LITERALS]
    if not enc:
        return None
    lits = np.zeros((_pow2(len(enc)), _pow2(len(enc[0]), 4)), np.uint8)
    lens = np.zeros(lits.shape[0], np.int32)
    for i, e in enumerate(enc):
        lits[i, :len(e)] = np.frombuffer(e, np.uint8)
        lens[i] = len(e)
    return lits, lens


def prefilter_words(names, keep, lits, lens):
    """(W,) uint32 packed verdicts: every literal occurs in the row's
    basename bytes, or its keep flag is set. Traced under jit."""
    nb = names.shape[0]
    found = jnp.ones(keep.shape, bool)
    for li in range(lits.shape[0]):
        # byte plane c against literal byte b; a byte past a short
        # literal's end always agrees
        eq = [[(names[c] == lits[li, b]) | (b >= lens[li])
               for b in range(lits.shape[1])] for c in range(nb)]
        any_at = jnp.zeros(keep.shape, bool)
        for o in range(nb):        # the literal at byte offset o
            at = jnp.ones(keep.shape, bool)
            for b in range(lits.shape[1]):
                # past the column's end only a short literal's tail
                at &= eq[o + b][b] if o + b < nb else (b >= lens[li])
            any_at |= at
        found &= any_at
    m = (keep | found).astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (32, 1), 0)
    words = jnp.sum(m << shifts, axis=0, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)


@jax.jit
def _and_row(words, j, names, keep, lits, lens):
    row = words[j]
    kept = jnp.sum(jax.lax.population_count(row), dtype=jnp.int32)
    hit = row & prefilter_words(names, keep, lits, lens)
    return words.at[j].set(hit), kept


def and_row(words: jax.Array, j: int, col: NameColumn,
            lits: Tuple[np.ndarray, np.ndarray]
            ) -> Tuple[jax.Array, jax.Array]:
    """Program ``j``'s row of the device bitmaps ``words`` ANDed with
    the prefilter's words, and the count of rows that row held before
    the AND; both stay on the device."""
    return _and_row(words, np.int32(j), col.names, col.keep,
                    jnp.asarray(lits[0]), jnp.asarray(lits[1]))
