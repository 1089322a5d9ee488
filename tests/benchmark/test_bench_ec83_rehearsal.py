"""The EC 8+3 cell at Ceph's default jerasure technique (reed_sol_van)
end to end on the CPU at a tiny size: a sound run stores jerasure's
parity on every shard, with every check at 0; the control is not
correct; a traced run reports the cell's layers, the codec's compile
among them."""

import bench_rehearsal

CELL = "rados_ec83.write_4m"


def test_sound_run_is_correct():
    out = bench_rehearsal.run(CELL)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"goodput_mibs", "op_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["path"]["dispatches_by_executor"].get(
        "pallas_words+crc", 0) > 0
    assert out["checks"] == {
        name: {"value": 0, "limit": 0}
        for name in ("mismatches", "device_faults", "executor_idle")}
    assert set(out["diag"]["mismatches_by_kind"]) == {
        "shards_wrong", "hinfo_wrong", "reads_wrong", "window_empty"}


def test_control_is_not_correct():
    """The control acknowledges writes whose parity it never stored."""
    out = bench_rehearsal.run(CELL, fault="parity_dropped")
    assert out["correct"] is False
    assert out["checks"]["mismatches"]["value"] > 0
    assert out["diag"]["mismatches_by_kind"]["shards_wrong"] > 0


def test_traced_run_reports_its_layers():
    """On the CPU the device-trace kernel shares find no chip ops to
    read; every other layer of the cell reports, the codec's compile
    seconds too."""
    out = bench_rehearsal.run(CELL, trace=True)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {"osd_queue_ms", "osd_subop_ms",
                                   "encode_wait_ms", "encode_batch_objs",
                                   "encode_dispatch_ms", "window_compiles",
                                   "device_idle_share", "codec_compile_s"}
    assert out["metrics"]["window_compiles"]["value"] == 0
    assert out["metrics"]["codec_compile_s"]["value"] > 0
