"""Share of the roofline the predicate kernel reaches in the window: the
bytes its problem needs (``roofline_predeval.predeval_bytes``, real rows
only) over its summed device time in the trace, against the chip's HBM
bandwidth, in percent."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "bench_metric_roofline", os.path.join(os.path.dirname(__file__),
                                          "_roofline.py"))
_mod = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_mod)


def read(ctx):
    return _mod.share(ctx, "predeval")
