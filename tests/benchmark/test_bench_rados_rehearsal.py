"""The RADOS cells end to end on the CPU at a tiny size: a sound run is
correct, with every metric of the cell; the control is not."""

import pytest

import bench_rehearsal

CELLS = ["rados_r6_82.write_4m"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = bench_rehearsal.run(cell)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"goodput_mibs", "op_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["device"]["platform"] == "cpu"
    assert sum(out["path"]["dispatches_by_executor"].values()) > 0
    assert list(out)[-1] == "checks"
    assert out["checks"] == {
        name: {"value": 0, "limit": 0}
        for name in ("mismatches", "device_faults", "executor_idle")}
    assert set(out["diag"]["mismatches_by_kind"]) == {
        "shards_wrong", "hinfo_wrong", "reads_wrong", "window_empty"}


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    """The control acknowledges writes whose parity it never stored."""
    out = bench_rehearsal.run(cell, fault="parity_dropped")
    assert out["correct"] is False
    assert out["checks"]["mismatches"]["value"] > 0
    assert out["diag"]["mismatches_by_kind"]["shards_wrong"] > 0


def test_write_4m_traced_run_reports_its_layers():
    out = bench_rehearsal.run("rados_r6_82.write_4m", trace=True)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"osd_queue_ms", "osd_subop_ms",
                                   "encode_wait_ms", "encode_batch_objs",
                                   "encode_dispatch_ms", "window_compiles",
                                   "device_idle_share"}
    assert out["metrics"]["window_compiles"]["value"] == 0
