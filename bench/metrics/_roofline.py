"""Shared by the ``<kernel>_roofline`` readers: the work the benchmark
counted for a kernel's calls in the window over that kernel's summed
device time in the trace, as a share of the chip's roofline."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import roofline  # noqa: E402


def share(ctx, kernel):
    red = ctx.get("trace")
    if red is None:
        return None
    secs = red["kernel_s"].get(kernel, 0.0)
    work = ctx["work"].get(kernel, 0.0)
    if secs <= 0 or work <= 0:
        return None
    return roofline.roofline_pct(work, secs, ctx["device_kind"])
