"""bench.py driver-contract tier: the one-line JSON contract must go
out within the time budget even when device init hangs (the rc=124
wedged-backend failure), and even when the bench body itself dies; a
number measured off the chip never rides under the device metric.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")

sys.path.insert(0, REPO)
import bench  # noqa: E402

CONTRACT_KEYS = {"metric", "value", "unit", "vs_baseline",
                 "plan_cache", "encode_service", "tier",
                 "device_health", "tail", "load", "durability",
                 "mesh", "multihost", "trace", "group_commit",
                 "compute", "xsched", "spmd", "repair", "inference",
                 "chaos", "truncated"}


def test_contract_line_despite_hanging_backend(tmp_path):
    """A backend whose probe hangs: the bench prints the null-valued
    contract line first and fails — no CPU number under the device
    metric's name, no CPU fallback."""
    env = dict(os.environ)
    env.update({
        # the stubbed backend: hangs until the probe's hard timeout
        "CEPH_TPU_BENCH_PROBE": "import time; time.sleep(300)",
        "CEPH_TPU_BENCH_PROBE_TIMEOUT": "1",
        "CEPH_TPU_BENCH_PROBE_ATTEMPTS": "2",
        "CEPH_TPU_BENCH_PROBE_RETRY_SLEEP": "0",
        "CEPH_TPU_BENCH_SMOKE": "1",
    })
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=240, cwd=str(tmp_path),
                       env=env)
    assert r.returncode != 0, r.stderr[-2000:]
    stdout_lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert len(stdout_lines) == 1, r.stdout[-2000:]
    contract = json.loads(stdout_lines[0])
    assert set(contract) == CONTRACT_KEYS
    assert contract["metric"] == "ec_jax_encode_k8m3_4MiB_stripe"
    assert contract["value"] is None
    assert contract["vs_baseline"] is None
    assert "no device" in r.stderr
    # nothing was measured, so no details file either
    assert not (tmp_path / "bench_details.json").exists()


def test_contract_probes_on_cpu(tmp_path):
    """A backend that comes up on the CPU: main() and every probe run
    on the CPU tier; the device metric and its ratio stay null, and
    the CPU figure is labelled as such in the details."""
    env = dict(os.environ)
    env.update({
        "CEPH_TPU_BENCH_PROBE": "print('cpu')",
        "CEPH_TPU_BENCH_SMOKE": "1",
    })
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=240, cwd=str(tmp_path),
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    stdout_lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    assert stdout_lines, f"no stdout; stderr: {r.stderr[-2000:]}"
    contract = json.loads(stdout_lines[0])  # FIRST line, parseable
    assert set(contract) == CONTRACT_KEYS
    assert contract["metric"] == "ec_jax_encode_k8m3_4MiB_stripe"
    assert contract["unit"] == "GiB/s"
    assert contract["value"] is None
    assert contract["vs_baseline"] is None
    # the plan-cache probe ran: one miss (compile) and one hit on the
    # same bucketed shape
    assert contract["plan_cache"]["misses"] >= 1
    assert contract["plan_cache"]["hits"] >= 1
    # the encode-service probe ran: concurrent requests shared batched
    # dispatches (bit-exactness is asserted inside the probe)
    assert contract["encode_service"]["requests"] >= 1
    assert contract["encode_service"]["batches"] >= 1
    assert contract["encode_service"]["batched"] >= 1
    # the tier probe ran: device-batched bloom matched the host
    # oracle bit-exactly and the agent promoted + served hot reads
    assert contract["tier"]["device_bitexact"] == 1
    assert contract["tier"]["records"] >= 1
    assert contract["tier"]["promote"] >= 1
    assert contract["tier"]["hit"] >= 1
    # the device-health probe ran: forced device failure degraded to
    # the bit-exact host path, tripped the breaker, and a half-open
    # probe re-closed it once injection cleared
    assert contract["device_health"]["bitexact"] == 1
    assert contract["device_health"]["trips"] >= 1
    assert contract["device_health"]["failures"] >= 1
    assert contract["device_health"]["probes"] >= 1
    assert contract["device_health"]["recovered"] == 1
    # the hedge probe ran: the need=4 gather completed from the first
    # four distinct arrivals, the 1 s stragglers were hedged around
    # and cancelled, and nothing leaked
    assert contract["tail"]["completed_shards"] >= 4
    assert contract["tail"]["straggler_avoided"] == 1
    assert contract["tail"]["hedges_fired"] >= 1
    assert contract["tail"]["cancelled_subreads"] >= 1
    assert contract["tail"]["leaked_tasks"] == 0
    # the open-loop load probe ran: hundreds of tenants drove the
    # embedded cluster, goodput + streaming percentiles came back,
    # and the schedule generator is deterministic
    assert contract["load"]["tenants"] >= 100
    assert contract["load"]["completed"] >= 1
    assert contract["load"]["goodput_mib_s"] > 0
    assert contract["load"]["p99_ms"] is not None
    assert contract["load"]["p99_ms"] > 0
    assert contract["load"]["deterministic"] == 1
    # the crash-consistency probe ran: the smoke power-cut sweep
    # explored crash points with ZERO invariant violations, and the
    # deliberately-broken store (fsync removed) was caught by the
    # same sweep (the harness self-test)
    assert contract["durability"]["points"] >= 20
    assert contract["durability"]["violations"] == 0
    assert contract["durability"]["broken_store_caught"] == 1
    # the mesh probe ran: the same batch was bit-identical through
    # the single-device plan, the N-device mesh plan and the host
    # oracle, and a scripted sick chip SHRANK the mesh (per-device
    # breaker tripped, survivors re-planned) instead of degrading
    # the batch to host
    assert contract["mesh"]["devices"] >= 2
    assert contract["mesh"]["bitexact"] == 1
    assert contract["mesh"]["mesh_dispatches"] >= 1
    assert contract["mesh"]["sick_chip_shrunk"] == 1
    assert contract["mesh"]["host_fallbacks"] == 0
    # the multihost probe ran: a REAL 2-process jax.distributed group
    # encoded bit-exactly on the hybrid DCN x ICI mesh, and the
    # host-loss leg retired the lost host as ONE event (one shrink,
    # no per-chip breaker storm, zero host fallbacks, the fused-crc
    # family still closed)
    mh = contract["multihost"]
    assert mh["processes_max"] >= 2
    assert mh["multihost_bitexact"] == 1
    assert mh["host_loss_bitexact"] == 1
    assert mh["host_loss_shrunk"] == 1
    assert mh["host_loss_one_event"] == 1
    assert mh["host_loss_host_fallbacks"] == 0
    assert mh["host_loss_fused_crc_closed"] == 1
    # the trace probe ran: the critical-path reducer reconstructed
    # the hand-built tree (longest hedged child on the path, the
    # cancelled straggler off it), live ops fed the per-stage
    # histograms, and the spans-on-vs-kill-switch overhead was
    # measured at sample rate 0 (the ≤2% production bound is judged
    # on quiet bench hardware, not asserted in this noisy tier)
    assert contract["trace"]["cp_ok"] == 1
    assert contract["trace"]["stages_seen"] >= 1
    assert contract["trace"]["stage_samples"] >= 1
    assert isinstance(contract["trace"]["overhead_pct"], (int, float))
    # the stable decomposition enforces the <=2% bound: measured
    # span-layer cost per op over the measured live EC op cost
    assert contract["trace"]["overhead_ratio_pct"] <= 2.0
    # the group-commit probe ran: N concurrent durable writes shared
    # barriers (fsyncs strictly under the writer count) bit-exactly,
    # while the kill-switch leg paid one sync commit per txn
    gc = contract["group_commit"]
    assert gc["writers"] >= 8
    assert gc["fsyncs_lt_writers"] == 1
    assert gc["fsyncs"] < gc["writers"]
    assert gc["kv_commits"] < gc["kv_commits_inline"]
    assert gc["kv_commits_inline"] == gc["writers"]
    assert gc["bitexact"] == 1
    assert gc["batches"] >= 1
    # the coded-compute probe ran: every registered linear kernel
    # evaluated on a parity-including k-subset of one object's coded
    # shards result-domain-decoded bit-exactly to the host reference,
    # and the hedged sub-compute straggler leg completed from the
    # first k shard-results (the 1 s straggler cancelled)
    cp = contract["compute"]
    assert cp["bitexact"] == 1
    assert cp["linear_kernels"] >= 2
    assert cp["straggler_avoided"] == 1
    assert cp["first_k_bitexact"] == 1
    assert cp["cancelled_subcomputes"] >= 1
    # the codec-compiler probe ran: every compiled XOR schedule
    # executed bit-exactly against the naive row-walk oracle, the
    # memo served repeat compiles, and the best measured XOR-count
    # reduction cleared the >=25% acceptance bar
    xs = contract["xsched"]
    assert xs["bitexact"] == 1
    assert xs["xor_reduction_pct"] >= 25
    assert xs["schedules"] >= 1
    assert xs["cache_hits"] >= 1
    assert xs["xors_scheduled"] < xs["xors_naive"]
    # the native fused-tape executor leg: when the C++ executor is
    # buildable (it is, in CI) the lowered tape ran bit-exactly on a
    # packed multi-object arena AND through the execute() seam, with
    # the tape memo serving the re-lower
    assert xs["native_available"] in (0, 1)
    if xs["native_available"]:
        assert xs["native_bitexact"] == 1
        assert xs["exec_native"] >= 2
        assert xs["tape_misses"] >= 1
        assert xs["tape_hits"] >= 1
    # the SPMD collective-safety probe ran: the static collective-site
    # map is non-empty, the 2-process smoke leg's runtime-observed
    # collective trace was a subset of it, and every process observed
    # the same collective order (the analyzer's runtime cross-check
    # riding the multihost sweep)
    sp = contract["spmd"]
    assert sp["static_sites"] >= 5
    assert sp["static_lines"] >= sp["static_sites"]
    assert sp["runtime_sites"] >= 1
    assert sp["runtime_subset_static"] == 1
    assert sp["order_congruent"] == 1
    # the MSR repair probe ran: every single-erasure pattern rebuilt
    # bit-exact from d beta-fragments, and the fragment bytes beat the
    # classic k-read (the regenerating-code point: ratio < 1)
    rp = contract["repair"]
    assert rp["patterns_bitexact"] == rp["k"] + rp["m"]
    assert rp["alpha"] == rp["d"] - rp["k"] + 1
    assert 0 < rp["bytes_ratio_vs_kread"] < 1
    # the coded-inference probe ran: the full-set Fisher combine is
    # bit-exact against the host oracle, every single-shard-loss
    # pattern stayed within the error budget with an honest estimate
    # (rel <= est <= budget), and the hedged sub-infer straggler leg
    # completed from the first sufficient arrival set (slow stream
    # substituted by a fused shard, straggler cancelled)
    inf = contract["inference"]
    assert inf["bitexact"] == 1
    assert inf["within_budget"] == 1
    assert inf["patterns"] >= 3
    assert inf["max_rel_err"] <= inf["max_est_error"] <= inf["budget"]
    assert inf["straggler_avoided"] == 1
    assert inf["straggler_within_budget"] == 1
    assert inf["substituted_streams"] >= 1
    assert inf["cancelled_subinfers"] >= 1
    # the compound-chaos probe ran: a seeded composed 3-hazard
    # scenario (stragglers x device faults x kill-switch flips) over
    # live two-tenant traffic with every invariant monitor armed —
    # zero violations, zero client errors, reads verified bit-exact,
    # the seed echoed so a red round replays from the contract line
    ch = contract["chaos"]
    assert ch["violations"] == 0
    assert ch["errors"] == 0
    assert ch["seed"] == 20107
    assert ch["events_fired"] >= 2
    assert ch["reads_verified"] >= 1
    assert ch["flag_flips"] >= 1
    assert contract["truncated"] is False
    # details stayed out of stdout (they belong in bench_details.json)
    assert len(stdout_lines) == 1
    details = json.loads((tmp_path / "bench_details.json").read_text())
    assert details["backend"] == "cpu"
    assert details["encode_gibs"] > 0
    assert "plan_cache" in details and "retraces" in details["plan_cache"]


def test_fallback_contract_when_bench_body_dies(monkeypatch, capsys):
    """Even a crash in main() yields the contract line (null value)."""
    monkeypatch.setattr(bench, "_ensure_backend", lambda: "cpu")
    monkeypatch.setattr(
        bench, "main",
        lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    monkeypatch.setattr(bench, "_contract_emitted", False)
    assert bench.cli() == 0
    out = capsys.readouterr().out.strip().splitlines()
    contract = json.loads(out[0])
    assert set(contract) == CONTRACT_KEYS
    assert contract["value"] is None


def test_budget_truncates_optional_sections(tmp_path):
    """An exhausted wall-clock budget (CEPH_TPU_BENCH_BUDGET) skips
    the optional sections but still emits the full contract line,
    flagged truncated, well inside the harness timeout."""
    env = dict(os.environ)
    env.update({
        "CEPH_TPU_BENCH_PROBE": "print('cpu')",
        "CEPH_TPU_BENCH_SMOKE": "1",
        "CEPH_TPU_BENCH_BUDGET": "0",
    })
    r = subprocess.run([sys.executable, BENCH], capture_output=True,
                       text=True, timeout=240, cwd=str(tmp_path),
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    stdout_lines = [ln for ln in r.stdout.splitlines() if ln.strip()]
    contract = json.loads(stdout_lines[0])
    assert set(contract) == CONTRACT_KEYS
    assert contract["truncated"] is True
    # the probe said cpu: the run goes on, but the device metric and
    # its ratio stay null; the CPU figure is in the details, labelled
    assert contract["value"] is None
    assert contract["vs_baseline"] is None
    details = json.loads((tmp_path / "bench_details.json").read_text())
    assert details["backend"] == "cpu"
    assert details["encode_gibs"] > 0
    assert details["truncated"] is True
    assert details["skipped_sections"]
    # the new open-loop sections ride the SAME single budget
    # decision: a tiny budget must skip them (never hang on them),
    # and the skip is recorded
    assert "load" in details["skipped_sections"]
    assert "load_sweep" not in details
    # the mesh sweep section too (the probe's `mesh` contract key is
    # pre-contract and still rides, budget permitting)
    assert "mesh" in details["skipped_sections"]
    assert "mesh_sweep" not in details
    # and the multihost process sweep
    assert "multihost" in details["skipped_sections"]
    assert "process_sweep" not in details
    # and the trace decomposition section
    assert "trace" in details["skipped_sections"]
    assert "trace_stage_summary" not in details
    # and the codec-compiler sweep (its `xsched` contract key is
    # pre-contract and still rides, budget permitting)
    assert "xsched" in details["skipped_sections"]
    assert "xsched_sweep" not in details
    # and the small-op open-loop section
    assert "smallop" in details["skipped_sections"]
    assert "smallop_modes" not in details
    # and the coded-inference serving section (its `inference`
    # contract key is pre-contract and still rides, budget permitting)
    assert "inference" in details["skipped_sections"]
    assert "inference_modes" not in details
    # the full chaos matrix is smoke-gated (like qos/durability), so
    # a budget-0 smoke run skips the section body without recording
    # it — but the pre-contract chaos probe key must NOT ride when
    # the budget is already spent
    assert "chaos_violations" not in details


def test_watchdog_contract_line_survives_outer_kill(tmp_path):
    """The BENCH_r05 rc=124 regression: a bench body that WEDGES in a
    mandatory stage under a tiny wall-clock budget must still flush a
    parseable (truncated) contract line via the deadline watchdog
    BEFORE the outer harness timeout kills the process."""
    env = dict(os.environ)
    env.update({
        "CEPH_TPU_BENCH_PROBE": "print('cpu')",
        "CEPH_TPU_BENCH_SMOKE": "1",
        "CEPH_TPU_BENCH_BUDGET": "1",         # artificially tiny
        "CEPH_TPU_BENCH_WATCHDOG_MARGIN": "2",
        "CEPH_TPU_BENCH_STALL_S": "120",      # the wedge
    })
    proc = subprocess.Popen([sys.executable, BENCH],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True,
                            cwd=str(tmp_path), env=env)
    # wait only until the watchdog's line actually lands (~budget +
    # margin = 3 s), then play the harness and kill the stalled
    # process — no need to burn the whole stall on the clock
    import threading

    box: dict = {}

    def reader():
        box["line"] = proc.stdout.readline()

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    t.join(45)
    proc.kill()
    proc.wait()
    assert proc.returncode != 0  # the outer kill DID happen (rc=124 shape)
    line = box.get("line", "")
    assert line.strip(), "no contract line before the kill"
    contract = json.loads(line)
    assert set(contract) == CONTRACT_KEYS
    assert contract["metric"] == "ec_jax_encode_k8m3_4MiB_stripe"
    assert contract["truncated"] is True
    assert contract["value"] is None  # no measurement this round


def test_probe_timeout_contained():
    """A hanging probe is killed at the timeout, not waited out."""
    env_probe = os.environ.get("CEPH_TPU_BENCH_PROBE")
    os.environ["CEPH_TPU_BENCH_PROBE"] = "import time; time.sleep(60)"
    try:
        assert bench._probe_backend(timeout_s=1.0) is None
    finally:
        if env_probe is None:
            os.environ.pop("CEPH_TPU_BENCH_PROBE", None)
        else:
            os.environ["CEPH_TPU_BENCH_PROBE"] = env_probe


def test_probe_reports_platform():
    env_probe = os.environ.get("CEPH_TPU_BENCH_PROBE")
    os.environ["CEPH_TPU_BENCH_PROBE"] = "print('cpu')"
    try:
        assert bench._probe_backend(timeout_s=30.0) == "cpu"
    finally:
        if env_probe is None:
            os.environ.pop("CEPH_TPU_BENCH_PROBE", None)
        else:
            os.environ["CEPH_TPU_BENCH_PROBE"] = env_probe
