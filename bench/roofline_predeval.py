"""The bytes the predicate kernel's problem needs (``bench/roofline.py``'s
rule: real rows only, never padding, each byte once at its stored
width), for the ``predeval_roofline`` readers.

So an arena padded to a power of two rows shows as a lower share: the
kernel reads the padding, the problem does not need it.
"""
from __future__ import annotations

from roofline import WORD


def predeval_bytes(rows: int, n_columns: int, n_programs: int = 1) -> float:
    """One fused pass over ``rows`` real rows: each column the programs
    test and the alive mask read once (4 B a row each), and one bit a
    row written per program."""
    return float(rows * WORD * (n_columns + 1) + n_programs * rows / 8)
