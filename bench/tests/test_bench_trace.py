"""The benchmark's trace reduction on a small trace recorded on a TPU
v5e (``bench/record_trace.py``: each kernel three times inside a
``bench.*`` annotation), and its roofline work functions on known
shapes."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import roofline  # noqa: E402
import trace_reduce  # noqa: E402

TRACE = os.path.join(BENCH, "tests", "data", "kernels_v5e.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    from jax.profiler import ProfileData
    return trace_reduce.reduce_trace(ProfileData.from_file(TRACE))


def test_kernels_are_found_by_their_ops(reduced):
    assert reduced["kernel_calls"] == {"hashshard": 3, "predeval": 3,
                                       "ddsketch": 3, "segstats": 3}
    ks = reduced["kernel_s"]
    # the grouped DDSketch update over 1,344 principals is the slowest
    assert max(ks, key=ks.get) == "ddsketch"
    assert all(0 < v < 0.01 for v in ks.values())


def test_busy_and_window(reduced):
    assert 0 < reduced["busy_s"] < reduced["window_s"] < 1.0
    total_ops = sum(v for _, v in reduced["device_ops"])
    assert total_ops <= reduced["busy_s"] * 1.0001


def test_idle_gaps_go_to_host_spans(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert {"bench.route", "bench.query", "bench.aggregate"} <= set(gaps)
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-6)
    assert len(reduced["device_ops"]) <= 10


@pytest.mark.parametrize("op,want", [
    ("%fn.1 = s32[8,512]{1,0} custom-call(s32[8,6]{1,0} %ops.1, "
     "bf16[4096,256]{1,0} %constant.1), custom_call_target="
     "\"tpu_custom_call\"", "predeval"),
    ("%x.2 = (f32[1408,2048]{1,0}, f32[1408,1]{1,0}, f32[1408,1]{1,0}, "
     "f32[1408,1]{1,0}, f32[1408,1]{1,0}, f32[1408,1]{1,0}) "
     "custom-call(s32[1,4096]{1,0} %a), custom_call_target="
     "\"tpu_custom_call\"", "ddsketch"),
    ("%y = (f32[1408,128]{1,0}, f32[1408,1]{1,0}, f32[1408,1]{1,0}, "
     "f32[1408,1]{1,0}) custom-call(s32[1,4096]{1,0} %a), "
     "custom_call_target=\"tpu_custom_call\"", "segstats"),
    ("%h.1 = s32[128,128]{1,0} custom-call(s32[192,128,128]{2,1,0} %b, "
     "s32[128,128]{1,0} %c), custom_call_target=\"tpu_custom_call\"",
     "hashshard"),
    ("%fusion.3 = f32[1344]{0} fusion(f32[1344]{0} %p), kind=kLoop", None),
])
def test_kernel_of(op, want):
    assert trace_reduce.kernel_of(op) == want


def test_work_functions_on_known_shapes():
    assert roofline.hashshard_bytes([10, 20, 30]) == 60 + 12
    # one row, five streams, four attributes, 2 principals, 3 buckets
    assert roofline.ddsketch_bytes(1, 5, 4, 2, 3) == 4 * 5 * 12 + 4 * 2 * 8 * 4


def test_roofline_share_and_peaks():
    bw = roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert roofline.roofline_pct(bw * 1e-3, 2e-3, "TPU v5 lite") == \
        pytest.approx(50.0)
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")
