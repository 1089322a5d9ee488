"""Mesh-sharded EC data plane tier: the same batch must be
bit-identical through the single-device plan, the N-device mesh plan,
and the host numpy oracle (odd chunk widths, ragged batches, batches
smaller than the mesh); a scripted sick chip must SHRINK the mesh —
its ``device:<id>`` breaker trips, the family breaker is absolved,
the dispatch re-plans on the survivors — never degrade the batch to
host; and the healthy-set mesh in parallel/backend.py must reshape
cleanly for awkward survivor counts.

Runs on the conftest 8-virtual-CPU-device mesh (the same sharding
code paths the real multi-chip mesh compiles).
"""

from __future__ import annotations

import os

import numpy as np
import pytest

import conftest

jax = pytest.importorskip("jax")

from ceph_tpu.common import circuit  # noqa: E402
from ceph_tpu.ec import plan  # noqa: E402
from ceph_tpu.models import reed_solomon as rs  # noqa: E402
from ceph_tpu.ops import checksum as cks  # noqa: E402
from ceph_tpu.ops import gf  # noqa: E402
from ceph_tpu.parallel import backend, striped  # noqa: E402

RNG = np.random.default_rng(4242)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs the conftest 8-virtual-device CPU mesh")


@pytest.fixture(autouse=True)
def _mesh_engaged(monkeypatch):
    """Every test here wants the mesh gates open (tiny batches) and a
    clean breaker/plan slate on both sides."""
    monkeypatch.setenv("CEPH_TPU_MESH_MIN_BYTES", "0")
    monkeypatch.delenv("CEPH_TPU_MESH", raising=False)
    monkeypatch.delenv("CEPH_TPU_MESH_MAX_DEVICES", raising=False)
    circuit.reset_all()
    plan.reset_stats()
    yield
    circuit.reset_all()


def _host_parity(mat, data):
    return np.stack([gf.gf_matmul_host(mat, data[i])
                     for i in range(data.shape[0])])


def _host_crcs(data, parity):
    b = data.shape[0]
    out = np.zeros((b, data.shape[1] + parity.shape[1]),
                   dtype=np.uint32)
    for i in range(b):
        chunks = np.concatenate([data[i], parity[i]], axis=0)
        for j in range(chunks.shape[0]):
            out[i, j] = cks.crc32c(0, chunks[j].tobytes())
    return out


# -- bit-exactness: 1-device plan vs N-device mesh plan vs host oracle ------


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live mesh-dispatch counters;\
 subject absent under scripted device-fault injection")
@pytest.mark.parametrize("b,s", [
    (16, 1024),    # even batch, pow2 chunk
    (5, 1001),     # ragged batch, odd chunk width
    (3, 768),      # batch smaller than the 8-device mesh
    (17, 4096),    # ragged past a pow2 bucket edge
])
def test_mesh_encode_bitexact_vs_single_device_and_host(
        monkeypatch, b, s):
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (b, 4, s), dtype=np.uint8)
    want = _host_parity(mat, data)

    meshed = plan.encode_with_crc(mat, data, sig=f"mesh-{b}-{s}")
    assert meshed is not None and np.array_equal(meshed[0], want)
    assert np.array_equal(meshed[1], _host_crcs(data, want))
    assert plan.stats()["mesh_dispatches"] >= 1

    monkeypatch.setenv("CEPH_TPU_MESH", "0")
    single = plan.encode_with_crc(mat, data, sig=f"mesh-{b}-{s}")
    assert single is not None and np.array_equal(single[0], want)
    assert np.array_equal(meshed[1], single[1])


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live mesh-dispatch counters;\
 subject absent under scripted device-fault injection")
@pytest.mark.parametrize("b,s", [(12, 2048), (7, 1000)])
def test_mesh_fused_crc_bitexact(monkeypatch, b, s):
    """The flush path's product shape: parity AND the zero-seeded
    per-chunk crc32c from one stripe-parallel dispatch, vs the host
    ledger and the single-device fused plan."""
    mat = rs.reed_sol_van_matrix(6, 3)
    data = RNG.integers(0, 256, (b, 6, s), dtype=np.uint8)
    want_parity = _host_parity(mat, data)
    want_crcs = _host_crcs(data, want_parity)

    meshed = plan.encode_with_crc(mat, data, sig=f"crc-{b}-{s}")
    assert meshed is not None
    assert np.array_equal(meshed[0], want_parity)
    assert np.array_equal(meshed[1], want_crcs)
    assert plan.stats()["mesh_dispatches"] >= 1

    monkeypatch.setenv("CEPH_TPU_MESH", "0")
    single = plan.encode_with_crc(mat, data, sig=f"crc-{b}-{s}")
    assert single is not None
    assert np.array_equal(single[0], meshed[0])
    assert np.array_equal(single[1], meshed[1])


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch results;\
 subject absent under scripted device-fault injection")
def test_small_batches_stay_single_device():
    """Below the stripe gate the mesh declines — one stripe must not
    pay an 8-chip fan-out."""
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (1, 4, 512), dtype=np.uint8)
    out = plan.encode_with_crc(mat, data, sig="tiny")
    assert out is not None and np.array_equal(out[0],
                                              _host_parity(mat, data))
    assert plan.stats()["mesh_dispatches"] == 0


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts live device-dispatch results;\
 subject absent under scripted device-fault injection")
def test_mesh_min_bytes_gate(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_MESH_MIN_BYTES", str(1 << 30))
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (16, 4, 512), dtype=np.uint8)
    out = plan.encode_with_crc(mat, data, sig="gated")
    assert out is not None and np.array_equal(out[0],
                                              _host_parity(mat, data))
    assert plan.stats()["mesh_dispatches"] == 0


# -- sick chip: shrink the mesh, never fall to host -------------------------


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="scripts its own injection spec")
def test_sick_chip_shrinks_mesh_not_host(monkeypatch):
    sick = jax.devices()[-1].id
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", f"sick={sick}")
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (16, 4, 512), dtype=np.uint8)
    want_parity = _host_parity(mat, data)

    out = plan.encode_with_crc(mat, data, sig="sick")
    assert out is not None and np.array_equal(out[0], want_parity)
    st = plan.stats()
    # the mesh SHRANK (sick chip probed out, survivors re-planned):
    # no host fallback, the family breaker absolved (closed), the
    # chip's own breaker tripped
    assert st["mesh_shrinks"] >= 1
    assert st["mesh_dispatches"] >= 1
    assert st["host_fallbacks"] == 0
    assert circuit.device_breaker(sick).state == circuit.OPEN
    assert circuit.breaker("fused-crc").state == circuit.CLOSED

    # steady state: with the chip pinned out (its jittered backoff
    # could otherwise expire within ms and trigger a legitimate
    # re-probe cycle), the survivor mesh serves the next batch
    # without another shrink
    circuit.device_breaker(sick).force_open(duration=3600.0)
    out2 = plan.encode_with_crc(mat, data, sig="sick")
    assert out2 is not None and np.array_equal(out2[0], want_parity)
    assert plan.stats()["mesh_shrinks"] == st["mesh_shrinks"]
    assert sick not in plan.mesh_info()["healthy"]

    # heal: injection cleared + backoff expired -> the chip's next
    # mesh dispatch is its de-facto half-open probe and it recovers
    monkeypatch.delenv("CEPH_TPU_INJECT_DEVICE_FAIL")
    circuit.device_breaker(sick).force_probe()
    out3 = plan.encode_with_crc(mat, data, sig="sick")
    assert out3 is not None and np.array_equal(out3[0], want_parity)
    assert sick in plan.mesh_info()["healthy"]


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="scripts its own injection spec")
def test_sick_chip_decode_path_shrinks(monkeypatch):
    """The matmul/decode kind rides the healthy-set mesh too: a sick
    chip shrinks it, output bit-exact, no host fold."""
    sick = jax.devices()[-1].id
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", f"sick={sick}")
    mat = rs.reed_sol_van_matrix(6, 3)
    data = RNG.integers(0, 256, (8, 6, 512), dtype=np.uint8)
    out = plan.matmul(mat, data, sig="sick-mm")
    assert out is not None
    assert np.array_equal(out, _host_parity(mat, data))
    st = plan.stats()
    assert st["mesh_shrinks"] >= 1
    assert st["host_fallbacks"] == 0
    assert circuit.device_breaker(sick).state == circuit.OPEN


def test_probe_devices_attributes_only_the_sick_chip(monkeypatch):
    ids = [d.id for d in jax.devices()]
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL",
                       f"sick={ids[3]}")
    sick = plan._probe_devices(tuple(ids))
    assert sick == [ids[3]]
    assert circuit.device_breaker(ids[3]).state == circuit.OPEN
    for other in ids:
        if other != ids[3]:
            assert circuit.device_breaker(other).state == \
                circuit.CLOSED


# -- plan keys + policy -----------------------------------------------------


def test_mesh_plan_keys_are_device_set_aware():
    sig = "a" * 16
    base = plan.plan_key(sig, "matmul", 2, 4, 16, 1024)
    m1 = plan.plan_key(sig, "matmul", 2, 4, 16, 1024,
                       mesh=(0, 1, 2, 3))
    m2 = plan.plan_key(sig, "matmul", 2, 4, 16, 1024,
                       mesh=(0, 1, 2))
    assert len({base, m1, m2}) == 3
    # whole stripes per chip: the pow2 bucket rounds UP to a multiple
    # of the mesh size
    assert m2[4] % 3 == 0
    # the fused-crc kinds keep the chunk axis length-exact
    mk = plan.plan_key(sig, "mesh_encode_crc", 2, 4, 16, 1001,
                       mesh=(0, 1))
    assert mk[5] == 1001


def test_mesh_devices_policy(monkeypatch):
    devs = plan._mesh_devices(16, 1 << 20)
    assert devs is not None and len(devs) == 8
    # one chip per stripe at most
    assert len(plan._mesh_devices(3, 1 << 20)) == 3
    # gates
    assert plan._mesh_devices(1, 1 << 20) is None
    monkeypatch.setenv("CEPH_TPU_MESH", "0")
    assert plan._mesh_devices(16, 1 << 20) is None
    monkeypatch.delenv("CEPH_TPU_MESH")
    monkeypatch.setenv("CEPH_TPU_MESH_MAX_DEVICES", "4")
    assert len(plan._mesh_devices(16, 1 << 20)) == 4


# -- backend: healthy-set mesh, awkward survivor counts ---------------------


def test_backend_mesh_derives_from_healthy_set():
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (8, 4, 256), dtype=np.uint8)
    want = _host_parity(mat, data)
    assert np.array_equal(backend.matmul(mat, data), want)
    full = dict(backend.default_mesh().shape)
    assert full.get("dp", 1) * full.get("sp", 1) == 8
    # hold one chip out: the mesh reshapes over the 7 survivors (an
    # awkward count -> pure data-parallel) and stays bit-exact
    sick = jax.devices()[-1].id
    circuit.device_breaker(sick).force_open(duration=3600.0)
    try:
        mesh = backend.default_mesh()
        ids = [d.id for d in mesh.devices.flat]
        assert sick not in ids and len(ids) == 7
        assert dict(mesh.shape).get("sp", 1) == 1
        assert np.array_equal(backend.matmul(mat, data), want)
        assert backend.stats["mesh_rebuilds"] >= 1
    finally:
        circuit.reset_all()


@pytest.mark.parametrize("n", [3, 5, 6, 7])
def test_partial_meshes_reshape_instead_of_raising(n):
    from ceph_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(jax.devices()[:n])
    shape = dict(mesh.shape)
    assert shape.get("dp", 1) * shape.get("sp", 1) == n
    # a pipeline over the partial mesh accepts chunk widths the full
    # mesh's sp split could not divide
    pipe = striped.ShardedPipeline(
        make_mesh(jax.devices()[:n], dp=n, sp=1), 4, 2, 100,
        rs.reed_sol_van_matrix(4, 2))
    assert pipe.sp == 1 and pipe.dp == n


def test_kill_switch_pins_backend_to_one_device(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_MESH", "0")
    assert len(backend.healthy_devices()) == 1
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (4, 4, 256), dtype=np.uint8)
    assert np.array_equal(backend.matmul(mat, data),
                          _host_parity(mat, data))


# -- logical axis rules -----------------------------------------------------


def test_logical_axis_rules_map_stripe_to_dp():
    from jax.sharding import PartitionSpec as P

    mesh = striped.stripe_mesh(jax.devices())
    assert striped.logical_spec("stripe", "shard", "byte",
                                mesh=mesh) == P("dp", None, None)
    full = backend.default_mesh()
    if "sp" in dict(full.shape):
        assert striped.logical_spec("stripe", "shard", "byte",
                                    mesh=full) == P("dp", None, "sp")
    # absent mesh axes resolve to replicated, same kernel everywhere
    assert striped.logical_spec("stripe", mesh=mesh) == P("dp")


# -- surfaces ---------------------------------------------------------------


def test_mesh_info_and_stats_surface():
    info = plan.mesh_info()
    assert info["enabled"] is True
    assert info["devices_total"] == 8
    assert info["healthy"] == [d.id for d in jax.devices()]
    st = plan.stats()
    assert "mesh" in st and st["mesh"]["devices_total"] == 8
    for key in ("mesh_dispatches", "mesh_rows", "mesh_shrinks",
                "mesh_probes"):
        assert key in st


def test_prometheus_devices_label_map():
    """Per-chip breaker rows flatten to a `device` label, state as a
    gauge — the ceph_osd_device_*{device=...} satellite surface."""
    from ceph_tpu.mgr.prometheus import PrometheusModule

    circuit.device_breaker(0).record_success()
    circuit.device_breaker(1).force_open()
    devices = {dev: {k: v for k, v in st.items()
                     if not isinstance(v, str)}
               for dev, st in circuit.device_stats().items()}
    for dev, st in devices.items():
        st["mesh_member"] = int(not circuit.device_degraded(int(dev)))
    lines: list = []
    PrometheusModule._emit_perf(
        lines, set(), "ceph_osd_device_health_devices", devices,
        {"ceph_daemon": "osd.0"})
    text = "\n".join(lines)
    assert ('ceph_osd_device_health_device_state_code'
            '{ceph_daemon="osd.0",device="1"} 2') in text
    assert ('ceph_osd_device_health_device_dispatches'
            '{ceph_daemon="osd.0",device="0"} 1') in text
    assert ('ceph_osd_device_health_device_mesh_member'
            '{ceph_daemon="osd.0",device="1"} 0') in text
    assert "# TYPE ceph_osd_device_health_device_state_code gauge" \
        in text
    assert "# TYPE ceph_osd_device_health_device_mesh_member gauge" \
        in text


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="asserts per-chip success/failure verdicts;\
 every dispatch fails under scripted injection")
def test_device_call_attribution():
    """The choke point records per-chip SUCCESS on every participant;
    failures are attributed only by an actual probe (family IS the
    chip's breaker) — an ordinary dispatch failure, single- or
    multi-chip, must not trip a threshold-1 chip breaker on a
    transient the family breaker would tolerate."""
    status, out = circuit.device_call(
        "test-mesh-fam", lambda: 7, devices=(0, 1, 2))
    assert status == "ok" and out == 7
    for d in (0, 1, 2):
        assert circuit.device_breaker(d).counters["successes"] >= 1
    # multi-chip failure: unattributed (the mesh layer probes)
    status, _ = circuit.device_call(
        "test-mesh-fam", lambda: (_ for _ in ()).throw(
            RuntimeError("boom")), devices=(3, 4))
    assert status == "fail"
    assert circuit.device_breaker(3).state == circuit.CLOSED
    assert circuit.device_breaker(4).state == circuit.CLOSED
    # ordinary single-chip failure: family verdict only — the chip's
    # breaker stays closed (a 1-chip host must not lose its only
    # device to one transient)
    status, _ = circuit.device_call(
        "test-mesh-fam2", lambda: (_ for _ in ()).throw(
            RuntimeError("boom")), devices=(5,))
    assert status == "fail"
    assert circuit.device_breaker(5).state == circuit.CLOSED
    # an actual probe (family IS the chip's breaker): decisive,
    # threshold 1 trips
    status, _ = circuit.device_call(
        f"{circuit.DEVICE_FAMILY_PREFIX}6",
        lambda: (_ for _ in ()).throw(RuntimeError("boom")),
        devices=(6,))
    assert status == "fail"
    assert circuit.device_breaker(6).state == circuit.OPEN
