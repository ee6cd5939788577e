"""Predicate-program compiler + numpy oracles for the fused predicate
kernel (DESIGN.md §13).

A *predicate program* is the fixed-shape encoding of one planner
predicate list (``[(col, op, arg), ...]`` — the same tuples
``discovery.eval_pred`` verifies exactly). Programs are data, not code:
K of them stack into flat arrays so one fused pass over an arena epoch
evaluates a whole query batch in a single read of the touched columns.

Encoding (all arrays little-endian numpy, stacked along K):

- ``ops``  (K, 6) int32 — per-column opcode over ``PRED_COLUMNS``
  (``size atime mtime uid gid mode``): OP_NONE / OP_RANGE / OP_NOTIN /
  OP_MASK.
- ``lo``/``hi`` (K, 6) float32 — inclusive RANGE bounds on the value
  CAST TO float32. Bounds are pre-widened by the compiler (1-ulp
  outward for float columns, integer-neighbour for int columns) so the
  f32 comparison over-includes and exact verify trims — the same
  superset discipline as the discovery runs.
- ``msk`` (K, 6) int32 — MASK operand ((v & msk) != 0), int columns
  only.
- set block, for NOTIN programs only: ``setrows`` (K_set,) int32 (which
  program row), ``setcol`` (K_set,) int32 (global column index 3..5),
  ``setvals`` (K_set, S) int32 sorted ascending and tail-padded by
  repeating the max element — membership in the padded multiset equals
  membership in the set, so no length array is needed. Padding rows use
  ``setrows = K`` (one past the last program; scatters drop them).

Bitmap format: row r of program k is bit (r % 32) of word
``words[k, r // 32]`` — uint32 words, little-endian bit order, i.e.
exactly ``np.packbits(match, bitorder="little").view(np.uint32)``.

The compiler, the zone batch op and the host oracle are pure numpy;
``predeval_ref`` is the jax.numpy oracle.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: kernel column order; first FLOAT_COLS are float32 arenas, rest int32
PRED_COLUMNS = ("size", "atime", "mtime", "uid", "gid", "mode")
FLOAT_COLS = 3
COL_INDEX = {c: i for i, c in enumerate(PRED_COLUMNS)}

OP_NONE, OP_RANGE, OP_MASK, OP_NOTIN = 0, 1, 2, 3

#: NOTIN sets larger than this are inexpressible (fall back to scan)
SET_CAP = 64

#: rows per Pallas grid step — a multiple of the f32 lane tile (128)
#: and of 32, so every block packs to whole lane-aligned words; arenas
#: are padded to a multiple of this on every evaluation path so the
#: host fallback produces identically-shaped bitmaps
BLOCK_ROWS = 4096

_I32_MIN, _I32_MAX = np.iinfo(np.int32).min, np.iinfo(np.int32).max


def widen_lo(arg, dtype: np.dtype):
    """Largest ``dtype`` value guaranteed <= every x with x > arg.
    Casting a float64 bound to the storage dtype can round it across
    stored values; widening one ulp outward keeps the candidate slice a
    SUPERSET and exact verify trims. (Canonical home of the helper the
    discovery runs use — discovery.py re-exports it.)"""
    dt = np.dtype(dtype)
    if np.issubdtype(dt, np.floating):
        f = dt.type(arg)
        return np.nextafter(f, dt.type(-np.inf))
    return arg


def widen_hi(arg, dtype: np.dtype):
    dt = np.dtype(dtype)
    if np.issubdtype(dt, np.floating):
        f = dt.type(arg)
        return np.nextafter(f, dt.type(np.inf))
    return arg


# ---------------------------------------------------------------------------
# vectorized zone-map pruning (tentpole part b)
# ---------------------------------------------------------------------------

def zone_keep(zone_lo: np.ndarray, zone_hi: np.ndarray, op: str, arg,
              dtype: np.dtype) -> np.ndarray:
    """One batch op over ALL runs' (min, max) pairs: keep[r] is False
    only when run r provably holds no match for (op, arg) — the
    vectorized form of the per-run host check inside
    ``ColumnRun.candidates``. Empty runs carry zone (inf, -inf) and
    prune under both range ops, matching the scalar path."""
    r = len(zone_lo)
    if op == "lt":
        return zone_lo <= widen_hi(arg, dtype)
    if op == "gt":
        return zone_hi >= widen_lo(arg, dtype)
    # mask / notin are not order-respecting: zones say nothing
    return np.ones(r, dtype=bool)


# ---------------------------------------------------------------------------
# program compilation
# ---------------------------------------------------------------------------

def compile_program(preds: Sequence[Tuple[str, str, object]]
                    ) -> Optional[dict]:
    """Compile one predicate list into a single-program dict, or None
    when it is not expressible as one fused pass (unknown column/op,
    mask on a float column, conflicting masks, oversized or float NOTIN
    set). Inexpressible programs fall back to the numpy scan — the
    compiler never silently drops a predicate, because a loosened
    program would still verify correctly but with unbounded candidate
    blow-up."""
    ops = np.zeros(len(PRED_COLUMNS), np.int32)
    lo = np.full(len(PRED_COLUMNS), -np.inf, np.float32)
    hi = np.full(len(PRED_COLUMNS), np.inf, np.float32)
    msk = np.zeros(len(PRED_COLUMNS), np.int32)
    set_spec: Optional[Tuple[int, np.ndarray]] = None
    for col, op, arg in preds:
        ci = COL_INDEX.get(col)
        if ci is None:
            return None
        is_float = ci < FLOAT_COLS
        if op in ("lt", "gt"):
            if ops[ci] not in (OP_NONE, OP_RANGE):
                return None
            ops[ci] = OP_RANGE
            if is_float:
                # stored values are exact f32; widen the f64 bound one
                # ulp outward exactly like the discovery runs
                if op == "lt":
                    hi[ci] = min(hi[ci], widen_hi(arg, np.float32))
                else:
                    lo[ci] = max(lo[ci], widen_lo(arg, np.float32))
            else:
                # int arenas compare as f32 in-kernel; the cast is
                # monotone, so the f32 image of the tightest integer
                # bound is a safe (superset) inclusive bound
                if op == "lt":
                    hi[ci] = min(hi[ci],
                                 np.float32(int(np.ceil(arg)) - 1))
                else:
                    lo[ci] = max(lo[ci],
                                 np.float32(int(np.floor(arg)) + 1))
        elif op == "mask":
            if is_float or ops[ci] != OP_NONE:
                return None
            ops[ci] = OP_MASK
            msk[ci] = np.int32(arg)
        elif op == "notin":
            if is_float or ops[ci] != OP_NONE or set_spec is not None:
                return None
            vals = np.unique(np.asarray(list(arg), dtype=np.int64))
            # values outside int32 can never equal a stored int32 —
            # dropping them preserves the exact semantics
            vals = vals[(vals >= _I32_MIN) & (vals <= _I32_MAX)]
            if len(vals) == 0:
                continue                       # notin {} == match all
            if len(vals) > SET_CAP:
                return None
            ops[ci] = OP_NOTIN
            set_spec = (ci, vals.astype(np.int32))
        else:
            return None
    return {"ops": ops, "lo": lo, "hi": hi, "msk": msk, "set": set_spec}


@dataclasses.dataclass
class Programs:
    """K stacked predicate programs, padded to jit-stable shapes.

    ``k`` is the true program count (rows k..k_pad-1 are OP_NONE
    padding whose bitmap rows are garbage-but-ignored); ``setrows``
    padding uses k_pad so every implementation can drop it uniformly."""

    k: int
    ops: np.ndarray        # (k_pad, 6) int32
    lo: np.ndarray         # (k_pad, 6) float32
    hi: np.ndarray         # (k_pad, 6) float32
    msk: np.ndarray        # (k_pad, 6) int32
    setrows: np.ndarray    # (ks_pad,) int32
    setcol: np.ndarray     # (ks_pad,) int32
    setvals: np.ndarray    # (ks_pad, S) int32
    has_set: bool

    @property
    def k_pad(self) -> int:
        return self.ops.shape[0]


def _pow2(n: int, floor: int = 1) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def stack_programs(programs: Sequence[dict]) -> Programs:
    """Stack compiled program dicts into one fixed-shape ``Programs``
    batch (K and the set width padded to powers of two so the jitted
    evaluators compile once per shape bucket)."""
    k = len(programs)
    if k == 0:
        raise ValueError("empty program batch")
    k_pad = _pow2(k)
    ops = np.zeros((k_pad, len(PRED_COLUMNS)), np.int32)
    lo = np.full((k_pad, len(PRED_COLUMNS)), -np.inf, np.float32)
    hi = np.full((k_pad, len(PRED_COLUMNS)), np.inf, np.float32)
    msk = np.zeros((k_pad, len(PRED_COLUMNS)), np.int32)
    sets: List[Tuple[int, int, np.ndarray]] = []
    for i, p in enumerate(programs):
        ops[i], lo[i], hi[i], msk[i] = p["ops"], p["lo"], p["hi"], p["msk"]
        if p["set"] is not None:
            sets.append((i, p["set"][0], p["set"][1]))
    if sets:
        ks_pad = _pow2(len(sets))
        s_pad = _pow2(max(len(v) for _, _, v in sets))
        setrows = np.full(ks_pad, k_pad, np.int32)   # pad -> dropped
        setcol = np.full(ks_pad, FLOAT_COLS, np.int32)
        setvals = np.zeros((ks_pad, s_pad), np.int32)
        for j, (row, ci, vals) in enumerate(sets):
            setrows[j], setcol[j] = row, ci
            # sorted + tail-padded with its own max: membership in the
            # padded multiset equals membership in the set
            setvals[j, :len(vals)] = vals
            setvals[j, len(vals):] = vals[-1]
    else:
        setrows = np.full(1, k_pad, np.int32)
        setcol = np.full(1, FLOAT_COLS, np.int32)
        setvals = np.zeros((1, 1), np.int32)
    return Programs(k=k, ops=ops, lo=lo, hi=hi, msk=msk, setrows=setrows,
                    setcol=setcol, setvals=setvals, has_set=bool(sets))


# ---------------------------------------------------------------------------
# host (numpy) oracle
# ---------------------------------------------------------------------------

def pack_words(match: np.ndarray) -> np.ndarray:
    """(K, n) bool -> (K, ceil(n/32)) uint32 in the kernel bit order."""
    k, n = match.shape
    n_pad = -(-n // 32) * 32
    if n_pad != n:
        m = np.zeros((k, n_pad), dtype=bool)
        m[:, :n] = match
        match = m
    return np.packbits(match, axis=1, bitorder="little").view(np.uint32)


def unpack_bits(words_row: np.ndarray, n: int) -> np.ndarray:
    """One program's words -> (n,) bool."""
    return np.unpackbits(np.ascontiguousarray(words_row).view(np.uint8),
                         bitorder="little")[:n].astype(bool)


def predeval_host(fcols: np.ndarray, icols: np.ndarray, alive: np.ndarray,
                  progs: Programs) -> np.ndarray:
    """Numpy mirror of the fused kernel, bit-for-bit: (k_pad, W) uint32
    packed match bitmaps over the (3, n) float32 + (3, n) int32 arena
    slabs. RANGE compares in float32 (matching the kernel's cast),
    MASK/NOTIN are exact integer ops; dead rows never match."""
    n = fcols.shape[1]
    live = alive != 0
    match = np.repeat(live[None, :], progs.k_pad, axis=0)
    for k in range(progs.k):
        for ci in range(len(PRED_COLUMNS)):
            op = progs.ops[k, ci]
            if op == OP_RANGE:
                v = (fcols[ci] if ci < FLOAT_COLS
                     else icols[ci - FLOAT_COLS].astype(np.float32))
                match[k] &= (v >= progs.lo[k, ci]) & (v <= progs.hi[k, ci])
            elif op == OP_MASK:
                match[k] &= (icols[ci - FLOAT_COLS]
                             & progs.msk[k, ci]) != 0
    if progs.has_set:
        for row, ci, vals in zip(progs.setrows, progs.setcol,
                                 progs.setvals):
            if row >= progs.k_pad:             # padding entry
                continue
            v = icols[ci - FLOAT_COLS]
            match[row] &= ~np.isin(v, vals)
    return pack_words(match[:, :n])


# ---------------------------------------------------------------------------
# jnp oracle — the CPU route (jitted by ops.py)
# ---------------------------------------------------------------------------

def predeval_ref(fcols, icols, alive, ops, lo, hi, msk,
                 setrows, setcol, setvals, has_set: bool):
    """Whole-array jax.numpy evaluator with the exact kernel semantics;
    traced under jit by ops.py."""
    k_pad = ops.shape[0]
    n = fcols.shape[1]
    match = jnp.broadcast_to((alive != 0)[None, :], (k_pad, n))
    for ci in range(len(PRED_COLUMNS)):
        opc = ops[:, ci][:, None]              # (k_pad, 1)
        v = (fcols[ci] if ci < FLOAT_COLS
             else icols[ci - FLOAT_COLS].astype(jnp.float32))[None, :]
        in_rng = (v >= lo[:, ci][:, None]) & (v <= hi[:, ci][:, None])
        match &= jnp.where(opc == OP_RANGE, in_rng, True)
        if ci >= FLOAT_COLS:
            vi = icols[ci - FLOAT_COLS][None, :]
            hitm = (vi & msk[:, ci][:, None]) != 0
            match &= jnp.where(opc == OP_MASK, hitm, True)
    if has_set:
        # set membership only for the K_set set-bearing programs (cost
        # K_set*S*n, not K*S*n — a batched dashboard mix must not pay
        # the NOTIN sweep on behalf of its range-only queries)
        sel = setcol[:, None]                  # (ks, 1)
        vi = jnp.where(
            sel == FLOAT_COLS, icols[0][None, :],
            jnp.where(sel == FLOAT_COLS + 1, icols[1][None, :],
                      icols[2][None, :]))      # (ks, n)
        hit = jnp.zeros(vi.shape, dtype=bool)
        for s in range(setvals.shape[1]):      # static unroll
            hit |= vi == setvals[:, s][:, None]
        rows = jnp.clip(setrows, 0, k_pad - 1)
        upd = match[rows] & ~hit
        # padding entries carry setrows == k_pad -> dropped
        match = match.at[setrows].set(upd, mode="drop")
    # pack: bits of disjoint weight sum to the exact word pattern;
    # int32 accumulate (bit 31 wraps negative, same bit pattern), then
    # bitcast to uint32
    w = n // 32
    mm = match.reshape(k_pad, w, 32).astype(jnp.int32)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 32), 2)
    words = jnp.sum(mm << shifts, axis=2, dtype=jnp.int32)
    return jax.lax.bitcast_convert_type(words, jnp.uint32)
