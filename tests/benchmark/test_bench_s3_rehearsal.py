"""rgw_r6_82.put_64m end to end on the CPU at a tiny size, traced: a sound
run is correct and reports its per-layer metrics; the control is not."""

import bench_rehearsal


def test_put_64m_traced_run_is_correct():
    out = bench_rehearsal.run("rgw_r6_82.put_64m", trace=True)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    m = out["metrics"]
    for name in ("osd_queue_ms", "osd_subop_ms", "encode_wait_ms",
                 "encode_batch_objs", "encode_dispatch_ms",
                 "window_compiles"):
        assert name in m, name
    assert m["window_compiles"]["value"] == 0
    assert m["encode_batch_objs"]["value"] >= 1
    # no chip here: no device op, so no roofline share, and all idle
    assert "ec_encode_roofline" not in m
    assert m["device_idle_share"]["value"] == 100.0
    assert out["device"]["window_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_put_64m_control_is_not_correct():
    out = bench_rehearsal.run("rgw_r6_82.put_64m", fault="parity_dropped")
    assert out["correct"] is False
    assert out["checks"]["mismatches"]["value"] > 0
    assert out["diag"]["mismatches_by_kind"]["shards_wrong"] > 0
