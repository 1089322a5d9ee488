"""The encode dispatch's stages as the benchmark reads them: the stage
sums per batch in the window snapshot, and the ``ceph.dispatch_*``
annotations in a profiler trace, on the trace's clock, from the
threads that ran them."""

import asyncio
import glob
import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402

STAGES = {"dispatch_handoff": 0.010, "dispatch_pack": 0.020,
          "dispatch_guard": 0.001, "dispatch_launch": 0.300,
          "dispatch_fetch": 0.100, "dispatch_fold": 0.040,
          "dispatch_resume": 0.005, "encode_queue": 0.900,
          "encode_wait": 2.0, "queue.client": 0.1}


def window(stage_s, requests=6, batches=4):
    return {"window": {"osd": {"ops": 6, "stage_s": dict(stage_s)},
                       "encode": {"requests": requests,
                                  "batches": batches,
                                  "dispatch_s": 0.476}}}


# (metric, the reading of window(STAGES))
READINGS = [
    ("encode_queue_ms", 0.900 / 6 * 1e3),
    ("dispatch_handoff_ms", (0.010 + 0.001 + 0.005) / 4 * 1e3),
    ("dispatch_host_ms", (0.020 + 0.040) / 4 * 1e3),
    ("dispatch_launch_ms", 0.300 / 4 * 1e3),
    ("dispatch_fetch_ms", 0.100 / 4 * 1e3),
]


def metric(name):
    return importlib.import_module(f"benchmark.metrics.{name}")


@pytest.mark.parametrize("name,want", READINGS)
def test_metric_reads_the_window(name, want):
    assert metric(name).read(window(STAGES)) == pytest.approx(want)


@pytest.mark.parametrize("name", [n for n, _ in READINGS])
def test_metric_has_nothing_to_read(name):
    """A program without the stages (the parent side of a comparison)
    and a window with no batch or request read None, and do not
    raise."""
    old = {s: v for s, v in STAGES.items()
           if not s.startswith(("dispatch_", "encode_queue"))}
    assert metric(name).read(window(old)) is None
    assert metric(name).read(window(STAGES, requests=0,
                                    batches=0)) is None


def test_dispatch_metrics_add_up_to_the_dispatch_time():
    """The four dispatch metrics divide the seven stages among them."""
    w = window(STAGES)
    parts = sum(metric(n).read(w) for n, _ in READINGS[1:])
    total = sum(v for s, v in STAGES.items() if s.startswith("dispatch_"))
    assert parts == pytest.approx(total / 4 * 1e3)


def test_dispatch_annotations_land_in_the_traced_window(tmp_path,
                                                        monkeypatch):
    """A CPU profiler trace (as the harness takes it: python tracer
    off) of a burst through the encode service holds the host events
    ``ceph.dispatch_pack/launch/fetch/fold`` inside
    ``bench.traced_window``, each from a thread other than the one
    that opened the window, and the trace reduction still reads it."""
    jax = pytest.importorskip("jax")
    from ceph_tpu.common.tracing import Tracer
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.encode_service import EncodeService

    monkeypatch.setenv("CEPH_TPU_FUSE_MIN_BYTES", "0")
    codec = ErasureCodePluginRegistry.instance().factory(
        "ec_jax", {"plugin": "ec_jax", "technique": "reed_sol_van",
                   "k": "4", "m": "2"})
    sinfo = ec_util.StripeInfo(4, 4 * 4096)
    rng = np.random.default_rng(5)
    bufs = [rng.integers(0, 256, 64 << 10, dtype=np.uint8).tobytes()
            for _ in range(8)]

    async def burst():
        svc = EncodeService()
        svc.tracer = Tracer("osd.test")
        await asyncio.gather(*(svc.encode_with_hinfo(
            sinfo, codec, b, range(6), logical_len=len(b))
            for b in bufs))
        await svc.stop()

    asyncio.run(burst())                # compiles outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            asyncio.run(burst())
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    events = trace.load(path)
    (win,) = [e for e in events if e[2] == trace.WINDOW_SPAN]
    names = ("ceph.dispatch_pack", "ceph.dispatch_launch",
             "ceph.dispatch_fetch", "ceph.dispatch_fold")
    for name in names:
        got = [e for e in events if e[2] == name]
        assert got, name
        for plane, _line, _n, a, b in got:
            assert plane.startswith("/host:CPU"), plane
            assert win[3] <= a <= b <= win[4], name
    # every Python thread's line is named "python": tell them apart by
    # the line that holds the event
    lines = {ev.name: i for plane in jax.profiler.ProfileData.from_file(
                 path).planes
             for i, line in enumerate(plane.lines) for ev in line.events
             if ev.name in names + (trace.WINDOW_SPAN,)}
    assert all(lines[n] != lines[trace.WINDOW_SPAN] for n in names), lines
    assert lines["ceph.dispatch_launch"] != lines["ceph.dispatch_pack"]
    r = trace.reduce_events(events)
    assert r["window_s"] == pytest.approx((win[4] - win[3]) / 1e9)
