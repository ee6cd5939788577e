"""Run one cell with the control in the program's place, to show that
its comparison fails it. Not part of the benchmark's own runs.

    python bench/control.py --workload <cell> --seed <n> --seconds <s> \\
        --control bf16

- ``bf16`` (scan cells): the reference's aggregate state with every
  value rounded to bfloat16 first (the lower precision a later change
  might reach for; breaks the exact sketch).

Prints the run's result line; its ``checks`` carry the readings.
"""
from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--control")
    control = argv[i + 1]
    del argv[i:i + 2]
    return run.main(argv, control=control)


if __name__ == "__main__":
    sys.exit(main())
