"""The trace reduction: busy and idle time, time per device op, and the
longest idle gaps against host spans."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402

DEV = "/device:TPU:0"
HOST = "/host:CPU"
MS = 1e6


def ev(plane, line, name, a_ms, b_ms):
    return (plane, line, name, a_ms * MS, b_ms * MS)


def test_union_busy_clipped_to_the_window_span():
    events = [
        ev(HOST, "bench", trace.WINDOW_SPAN, 10, 110),
        ev(DEV, "XLA Ops", "gf", 0, 15),          # clipped to 10..15
        ev(DEV, "XLA Ops", "crc", 20, 30),
        ev(DEV, "XLA Ops", "gf", 25, 40),         # overlaps crc
        ev(DEV, "XLA Modules", "jit_plan", 20, 40),   # not an op line
        ev(DEV, "XLA Ops", "gf", 100, 120),       # clipped to 100..110
        ev(HOST, "runtime", "ExecuteHelper", 40, 95),
    ]
    r = trace.reduce_events(events)
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((5 + 20 + 10) / 1e3)
    assert r["ops"]["gf"] == pytest.approx((5 + 15 + 10) / 1e3)
    assert r["ops"]["crc"] == pytest.approx(10 / 1e3)
    assert r["op_counts"] == {"gf": 3, "crc": 1}
    top = r["breakdown"]["device_ops"]
    assert [n for n, _s in top] == ["gf", "crc"]
    gaps = r["breakdown"]["idle_gaps"]
    # gaps: 15..20, 40..100 (the longest, under ExecuteHelper)
    assert gaps[0] == ["ExecuteHelper", pytest.approx(0.06)]
    assert gaps[1] == ["no host event", pytest.approx(0.005)]


def test_no_device_op_reads_all_idle():
    events = [ev(HOST, "bench", trace.WINDOW_SPAN, 0, 50),
              ev("/device:CPU:0", "XLA Ops", "dot", 1, 2)]
    r = trace.reduce_events(events)
    assert r["busy_s"] == 0 and r["device_count"] == 0
    assert r["breakdown"]["idle_gaps"] == [["no host event",
                                            pytest.approx(0.05)]]


RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_fused_encode.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    """Recorded on a v5e (my chip run, PR 22): three fused encode+CRC
    dispatches of 128 stripes (4 MiB, RS 8+3, S=4096) inside the
    benchmark's window span."""
    return trace.reduce_events(trace.load(RECORDED))


def test_recorded_trace_kernels_and_busy(recorded):
    r = recorded
    assert r["device_count"] == 1
    assert r["op_counts"]["_lambda_.2 custom-call tpu_custom_call"] == 3
    assert r["op_counts"]["_lambda_.3 custom-call tpu_custom_call"] == 3
    kernels = sum(s for n, s in r["ops"].items()
                  if n.endswith("tpu_custom_call"))
    assert 0 < kernels <= r["busy_s"] < r["window_s"]
    assert r["breakdown"]["device_ops"][0][0].endswith("tpu_custom_call")
    assert len(r["breakdown"]["idle_gaps"]) == trace.TOP
    assert sum(s for _n, s in r["breakdown"]["idle_gaps"]) <= \
        r["window_s"] - r["busy_s"] + 1e-9


def test_recorded_trace_roofline(recorded):
    """Three 128-stripe objects through the roofline metric's arithmetic:
    a share of the HBM peak, never above 100 %."""
    sys.path.insert(0, ROOT)
    from benchmark.metrics import ec_encode_roofline, device_idle_share

    w = {"trace": recorded, "traced": {"encode": {"requests": 3}},
         "device_kind": "TPU v5 lite",
         "geometry": {"k": 8, "m": 3, "chunk": 4096, "object_stripes": 128}}
    share = ec_encode_roofline.read(w)
    nbytes = 3 * 128 * (11 * 4096 + 11 * 4)
    kernels = sum(s for n, s in recorded["ops"].items()
                  if n.endswith("tpu_custom_call"))
    assert share == pytest.approx(100 * nbytes / 819e9 / kernels)
    assert 0 < share < 100
    assert 0 < device_idle_share.read(w) < 100
    with pytest.raises(KeyError):
        ec_encode_roofline.read(dict(w, device_kind="TPU v9"))
