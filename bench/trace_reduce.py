"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers: busy and idle time, kernel time by kernel, the device
operations that took most time, and idle gaps by what the host was
doing.

The trace is read with ``jax.profiler.ProfileData``. Device planes are
``/device:TPU:<i>``; their ``XLA Ops`` line holds one event per device
operation, on the same nanosecond timeline as the host planes. The
benchmark's own host spans (``jax.profiler.TraceAnnotation`` names
starting with ``bench.``) sit on the ``/host:CPU`` thread lines; the
traced window is the ``bench.window`` span.

The Pallas calls carry no ``name=`` yet, so a kernel is recognised by
the shape of its ``tpu_custom_call`` op (see ``kernel_of``).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"

_OP_NAME = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=")


def newest_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def kernel_of(op: str) -> Optional[str]:
    """Which Pallas kernel a device op is, from its HLO text: None for
    anything that is not a ``tpu_custom_call``.

    - predeval: its operands include the constant bf16 bit-packing
      matrix;
    - ddsketch: six outputs (counts, zero count, count, total, min, max);
    - segstats: four outputs (counts, sum, min, max);
    - hashshard: one int32 output from two operands, the first the
      (W, N/128, 128) byte planes.
    """
    if "tpu_custom_call" not in op or "custom-call(" not in op:
        return None
    head, rest = op.split("custom-call(", 1)
    operands = rest.split("), custom_call_target", 1)[0]
    out = head.split("=", 1)[1].strip() if "=" in head else head
    if "bf16[" in operands:
        return "predeval"
    if out.startswith("("):
        n_out = len(re.findall(r"\w+\[[\d,]*\]", out))
        if n_out == 6:
            return "ddsketch"
        if n_out == 4:
            return "segstats"
        return "other_kernel"
    n_in = len(re.findall(r"\w+\[[\d,]*\]\{", operands))
    first = re.match(r"\s*s32\[(\d+),(\d+),128\]", operands)
    if n_in == 2 and first is not None:
        return "hashshard"
    return "other_kernel"


def op_label(op: str) -> str:
    """Short stable label of a device op for the breakdown: the kernel
    name for Pallas calls, else the HLO instruction name without its
    numeric suffix."""
    k = kernel_of(op)
    if k is not None:
        return k
    m = _OP_NAME.match(op)
    return m.group(1) if m else op[:40]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce_trace(pd, n_chips: int = 1, top: int = 10) -> Dict:
    """Device numbers of the traced window.

    Returns ``window_s``, ``busy_s`` (union of device-op intervals
    inside the window, averaged over the ``n_chips`` first devices),
    ``kernel_s`` (summed device time per kernel), ``kernel_calls``,
    ``device_ops`` (top ops by summed time) and ``idle_gaps`` (idle
    device time inside the window, by the innermost benchmark span open
    on the host at each gap's midpoint, ``idle`` where none was).
    """
    host = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices.append(plane)
        elif plane.name == HOST_PLANE:
            host = plane
    devices.sort(key=lambda p: int(p.name[len(DEVICE_PREFIX):] or 0))
    devices = devices[:n_chips]

    spans: List[Tuple[int, int, str]] = []
    if host is not None:
        for line in host.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((int(e.start_ns), int(e.end_ns), e.name))
    win = [s for s in spans if s[2] == WINDOW_SPAN]
    spans = [s for s in spans if s[2] != WINDOW_SPAN]

    ops_by_dev = []
    for plane in devices:
        evs = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                evs += [(int(e.start_ns), int(e.end_ns), e.name)
                        for e in line.events]
        ops_by_dev.append(evs)
    all_ops = [o for evs in ops_by_dev for o in evs]
    if win:
        lo, hi = min(w[0] for w in win), max(w[1] for w in win)
    elif all_ops:
        lo, hi = min(o[0] for o in all_ops), max(o[1] for o in all_ops)
    else:
        lo = hi = 0
    window_ns = max(hi - lo, 0)

    busy_ns = 0
    gaps: List[Tuple[int, int]] = []
    for evs in ops_by_dev:
        iv = _clip(_union([(a, b) for a, b, _ in evs]), lo, hi)
        busy_ns += sum(b - a for a, b in iv)
        edges = [lo] + [x for ab in iv for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = max(len(ops_by_dev), 1)

    kernel_s: Dict[str, float] = {}
    kernel_calls: Dict[str, int] = {}
    by_label: Dict[str, float] = {}
    for a, b, name in all_ops:
        if b <= lo or a >= hi:
            continue
        d = (min(b, hi) - max(a, lo)) / 1e9
        lab = op_label(name)
        by_label[lab] = by_label.get(lab, 0.0) + d / n_dev
        k = kernel_of(name)
        if k is not None:
            kernel_s[k] = kernel_s.get(k, 0.0) + d / n_dev
            kernel_calls[k] = kernel_calls.get(k, 0) + 1

    idle_by: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) // 2
        open_ = [s for s in spans if s[0] <= mid < s[1]]
        lab = max(open_, key=lambda s: s[0])[2] if open_ else "idle"
        idle_by[lab] = idle_by.get(lab, 0.0) + (b - a) / 1e9 / n_dev

    def topn(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:top]

    return {"window_s": window_ns / 1e9, "busy_s": busy_ns / 1e9 / n_dev,
            "kernel_s": kernel_s, "kernel_calls": kernel_calls,
            "device_ops": topn(by_label), "idle_gaps": topn(idle_by)}


def reduce_dir(trace_dir: str, n_chips: int = 1) -> Optional[Dict]:
    path = newest_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData
    return reduce_trace(ProfileData.from_file(path), n_chips=n_chips)
