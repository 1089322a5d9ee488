"""Cross-host EC data plane tier (parallel/multihost.py).

Four acceptance legs:

* **Real multi-process bit-exactness** — encode (fused crc) AND
  decode (decode-matrix matmul) across a REAL 2-process
  ``jax.distributed`` group (gloo CPU collectives, 2 virtual devices
  per process, hybrid ("dcn", "dp") mesh) must equal the
  single-process plans and the host numpy oracle, on odd chunk
  widths and ragged batches.
* **Host-loss shrink** — over the emulated 2-host topology, a
  ``down_host`` injection must retire the host as ONE event: one
  ``host:<id>`` breaker trip, zero per-chip breaker trips (no
  storm), ONE mesh shrink, zero host fallbacks, the ``fused-crc``
  family still closed, output bit-exact; healing re-admits the host.
* **Plan-key topology stability** — the process-topology element
  keeps plans from different cluster shapes (1x8 vs 2x4 over the
  same chips) apart, while identical topologies key identically.
* **Kill switch** — CEPH_TPU_MULTIHOST=0 collapses everything to the
  single-process PR-9 behavior bit-identically.
"""

from __future__ import annotations

import hashlib
import json
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import conftest

jax = pytest.importorskip("jax")

from ceph_tpu.common import circuit  # noqa: E402
from ceph_tpu.ec import plan  # noqa: E402
from ceph_tpu.models import reed_solomon as rs  # noqa: E402
from ceph_tpu.ops import gf  # noqa: E402
from ceph_tpu.parallel import multihost, striped  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RNG = np.random.default_rng(1313)

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs the conftest 8-virtual-device CPU mesh")

# the shared worker-vs-local case list: odd chunks, ragged batches
CASES = [(16, 1024), (5, 1001), (3, 768)]


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_MESH_MIN_BYTES", "0")
    monkeypatch.delenv("CEPH_TPU_MESH", raising=False)
    monkeypatch.delenv("CEPH_TPU_MULTIHOST_HOSTS", raising=False)
    circuit.reset_all()
    plan.reset_stats()
    yield
    circuit.reset_all()


def _case_results(encode_crc, matmul):
    """Run every case through the given entry points; digest the
    outputs so in-process and subprocess runs compare equal."""
    out = {}
    mat = rs.reed_sol_van_matrix(4, 2)
    for b, s in CASES:
        rng = np.random.default_rng(b * 100000 + s)
        data = rng.integers(0, 256, (b, 4, s), dtype=np.uint8)
        enc = encode_crc(mat, data, f"mh-{b}-{s}")
        assert enc is not None, (b, s)
        parity, crcs = enc
        # decode leg: chunks 0,1 erased, survivors 2,3 + both parity
        # (a decode IS the decode-rows matmul, so the mesh encode
        # kind carries it across hosts — odd widths included)
        dmat = rs.decode_matrix(mat, 4, [0, 1], [2, 3, 4, 5])
        surv = np.concatenate([data[:, 2:4, :], parity], axis=1)
        dec = matmul(dmat, np.ascontiguousarray(surv),
                     f"mh-dec-{b}-{s}")
        assert dec is not None and np.array_equal(
            np.asarray(dec), data[:, :2, :]), (b, s)
        assert dec is not None, (b, s)
        out[f"{b}x{s}"] = {
            "parity_sha": hashlib.sha256(
                np.ascontiguousarray(parity)).hexdigest(),
            "crc_sha": hashlib.sha256(
                np.ascontiguousarray(crcs)).hexdigest(),
            "decode_sha": hashlib.sha256(
                np.ascontiguousarray(dec)).hexdigest(),
        }
    return out


def _host_oracle_results():
    def encode_crc(mat, data, sig):
        b = data.shape[0]
        parity = np.stack([gf.gf_matmul_host(mat, data[i])
                           for i in range(b)])
        from ceph_tpu.ops import checksum as cks

        crcs = np.zeros((b, 6), dtype=np.uint32)
        for i in range(b):
            chunks = np.concatenate([data[i], parity[i]], axis=0)
            for j in range(6):
                crcs[i, j] = cks.crc32c(0, chunks[j].tobytes())
        return parity, crcs

    def matmul(mat, data, sig):
        return np.stack([gf.gf_matmul_host(mat, data[i])
                         for i in range(data.shape[0])])

    return _case_results(encode_crc, matmul)


def _plan_results():
    return _case_results(
        lambda m, d, s: plan.encode_with_crc(m, d, sig=s),
        lambda m, d, s: plan.matmul(m, d, sig=s))


_WORKER_SRC = textwrap.dedent("""
    import hashlib, json, os, sys
    import numpy as np
    sys.path.insert(0, {repo!r})
    sys.path.insert(0, os.path.join({repo!r}, "tests"))
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["CEPH_TPU_MESH_MIN_BYTES"] = "0"
    from ceph_tpu.parallel import multihost
    assert multihost.bootstrap_from_env(), "group did not form"
    import test_multihost as tm
    out = tm._plan_results()
    out["topology"] = list(multihost.topology_signature())
    out["processes"] = multihost.process_count()
    print("RESULT " + json.dumps(out), flush=True)
""")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


@pytest.mark.skipif(conftest.DEVICE_INJECTION,
                    reason="spawns its own process group; injection\
 would fail every dispatch inside it")
def test_two_process_encode_decode_bitexact(tmp_path):
    """THE tentpole acceptance: bit-exact encode (fused crc) and
    decode across >= 2 jax.distributed processes vs the
    single-process plans and the host oracle (odd chunks, ragged
    batches)."""
    worker = tmp_path / "worker.py"
    worker.write_text(_WORKER_SRC.format(repo=REPO))
    port = _free_port()
    procs = []
    for pid in range(2):
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS",)}
        env.update({
            "CEPH_TPU_MULTIHOST_COORD": f"127.0.0.1:{port}",
            "CEPH_TPU_MULTIHOST_NPROC": "2",
            "CEPH_TPU_MULTIHOST_PID": str(pid),
            "CEPH_TPU_MULTIHOST_LOCAL_DEVICES": "2",
            "JAX_PLATFORMS": "cpu",
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(worker)], stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env))
    try:
        outs = [p.communicate(timeout=240) for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, (so, se) in zip(procs, outs):
        assert p.returncode == 0, se[-2000:]
    reports = []
    for so, _se in outs:
        line = [ln for ln in so.splitlines()
                if ln.startswith("RESULT ")][-1]
        reports.append(json.loads(line[len("RESULT "):]))
    # both processes computed the SAME global result (SPMD + gather)
    assert reports[0] == reports[1]
    assert reports[0]["processes"] == 2
    assert reports[0]["topology"][0] == 2  # two host domains
    # vs the host oracle and the single-process plans, case by case
    oracle = _host_oracle_results()
    single = _plan_results()
    for case in oracle:
        assert reports[0][case] == oracle[case], case
        assert single[case] == oracle[case], case


def test_host_loss_is_one_event(monkeypatch):
    """Losing a host retires ALL its chips in ONE event: a single
    host:<id> breaker trip, a single mesh shrink, zero per-chip
    breaker trips, zero host fallbacks, fused-crc still closed —
    then healing re-admits the host."""
    monkeypatch.setenv("CEPH_TPU_MULTIHOST_HOSTS", "2")
    assert multihost.host_count() == 2
    ids = [d.id for d in jax.devices()]
    lost_host = 1
    lost_ids = set(multihost.hosts()[lost_host])
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (16, 4, 1024), dtype=np.uint8)
    want = np.stack([gf.gf_matmul_host(mat, data[i])
                     for i in range(16)])

    out = plan.encode_with_crc(mat, data, sig="hostloss")
    assert out is not None and np.array_equal(out[0], want)
    assert plan.stats()["mesh_shrinks"] == 0

    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL",
                       f"down_host={lost_host}")
    out2 = plan.encode_with_crc(mat, data, sig="hostloss")
    assert out2 is not None and np.array_equal(out2[0], want)
    st = plan.stats()
    # ONE shrink, ONE host retirement, zero host fallbacks
    assert st["mesh_shrinks"] == 1
    assert st["host_retirements"] == 1
    assert st["host_fallbacks"] == 0
    # the host breaker holds every chip out; NO chip breaker tripped
    assert circuit.host_degraded(lost_host)
    for did in ids:
        assert circuit.device_breaker(did).state == circuit.CLOSED
        assert circuit.device_degraded(did) == (did in lost_ids)
    assert circuit.breaker("fused-crc").state == circuit.CLOSED
    healthy = plan.mesh_info()["healthy"]
    assert set(healthy).isdisjoint(lost_ids)

    # steady state: survivors keep serving without another shrink
    circuit.host_breaker(lost_host).force_open(duration=3600.0)
    out3 = plan.encode_with_crc(mat, data, sig="hostloss")
    assert out3 is not None and np.array_equal(out3[0], want)
    assert plan.stats()["mesh_shrinks"] == 1

    # heal: injection cleared + backoff expired -> the host's chips
    # rejoin and the first successful dispatch re-closes its breaker
    monkeypatch.delenv("CEPH_TPU_INJECT_DEVICE_FAIL")
    circuit.host_breaker(lost_host).force_probe()
    out4 = plan.encode_with_crc(mat, data, sig="hostloss")
    assert out4 is not None and np.array_equal(out4[0], want)
    assert set(plan.mesh_info()["healthy"]) >= lost_ids
    assert circuit.host_breaker(lost_host).state == circuit.CLOSED


def test_single_sick_chip_still_chip_level_under_host_topology(
        monkeypatch):
    """A single sick chip inside a live host must NOT retire the
    host: chip-level attribution survives the host-aware path."""
    monkeypatch.setenv("CEPH_TPU_MULTIHOST_HOSTS", "2")
    sick = jax.devices()[-1].id
    monkeypatch.setenv("CEPH_TPU_INJECT_DEVICE_FAIL", f"sick={sick}")
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (16, 4, 512), dtype=np.uint8)
    want = np.stack([gf.gf_matmul_host(mat, data[i])
                     for i in range(16)])
    out = plan.encode_with_crc(mat, data, sig="sickchip")
    assert out is not None and np.array_equal(out[0], want)
    st = plan.stats()
    assert st["mesh_shrinks"] >= 1
    assert st["host_retirements"] == 0
    assert st["host_fallbacks"] == 0
    assert circuit.device_breaker(sick).state == circuit.OPEN
    assert not circuit.host_degraded(multihost.host_of_id(sick))


def test_plan_key_topology_stability():
    """The process-topology element: identical topologies key
    identically; different cluster shapes over the same chips never
    collide; the trivial single-host shape keys exactly as the
    pre-multihost form did (same leading elements, empty
    proc)."""
    sig = "b" * 16
    topo_2x4 = (2, ((0, (0, 1, 2, 3)), (1, (4, 5, 6, 7))))
    topo_4x2 = (4, ((0, (0, 1)), (1, (2, 3)), (2, (4, 5)),
                    (3, (6, 7))))
    base = plan.plan_key(sig, "matmul", 2, 4, 16, 1024,
                         mesh=tuple(range(8)))
    k24 = plan.plan_key(sig, "matmul", 2, 4, 16, 1024,
                        mesh=tuple(range(8)), proc=topo_2x4)
    k42 = plan.plan_key(sig, "matmul", 2, 4, 16, 1024,
                        mesh=tuple(range(8)), proc=topo_4x2)
    assert len({base, k24, k42}) == 3
    assert k24 == plan.plan_key(sig, "matmul", 2, 4, 16, 1024,
                                mesh=tuple(range(8)), proc=topo_2x4)
    # single-host: proc is empty and the key round-trips through
    # JSON identically (process-stable, like the PR-2 stability test)
    assert base[-1] == ()
    norm = json.loads(json.dumps(list(base)[:6]))
    assert norm == list(base)[:6]


def test_topology_signature_shapes(monkeypatch):
    # trivial single-host: empty (keys stay PR-9-compatible)
    assert multihost.topology_signature() == ()
    monkeypatch.setenv("CEPH_TPU_MULTIHOST_HOSTS", "2")
    sig = multihost.topology_signature()
    assert sig[0] == 2 and len(sig[1]) == 2
    hostmap = multihost.hosts()
    assert sorted(sum((list(v) for v in hostmap.values()), [])) == \
        sorted(d.id for d in jax.devices())
    # every device maps into its block
    for h, ids in hostmap.items():
        for did in ids:
            assert multihost.host_of_id(did) == h


def test_kill_switch_single_process_parity(monkeypatch):
    """CEPH_TPU_MULTIHOST=0: emulated topology ignored, bootstrap
    refuses to join a group, plan outputs bit-identical to the
    multihost-on single-host run."""
    mat = rs.reed_sol_van_matrix(4, 2)
    data = RNG.integers(0, 256, (8, 4, 1024), dtype=np.uint8)
    on = plan.encode_with_crc(mat, data, sig="ks")
    monkeypatch.setenv("CEPH_TPU_MULTIHOST", "0")
    monkeypatch.setenv("CEPH_TPU_MULTIHOST_HOSTS", "2")
    monkeypatch.setenv("CEPH_TPU_MULTIHOST_COORD", "127.0.0.1:1")
    monkeypatch.setenv("CEPH_TPU_MULTIHOST_NPROC", "2")
    assert multihost.topology_signature() == ()
    assert multihost.host_count() == 1
    assert not multihost.initialize()
    off = plan.encode_with_crc(mat, data, sig="ks")
    assert on is not None and off is not None
    assert np.array_equal(on[0], off[0])
    assert np.array_equal(on[1], off[1])


def test_hybrid_mesh_and_logical_rules(monkeypatch):
    """Devices spanning two hosts lay out as ("dcn", "dp") with
    `stripe` mapping across BOTH axes; a one-host set stays flat
    ("dp",) with `stripe` -> "dp" exactly as before."""
    from jax.sharding import PartitionSpec as P

    monkeypatch.setenv("CEPH_TPU_MULTIHOST_HOSTS", "2")
    mesh = striped.stripe_mesh(jax.devices())
    assert mesh.axis_names == ("dcn", "dp")
    assert dict(mesh.shape) == {"dcn": 2, "dp": 4}
    assert striped.logical_spec("stripe", "shard", "byte",
                                mesh=mesh) == \
        P(("dcn", "dp"), None, None)
    assert striped.data_parallel_size(mesh) == 8
    # one host's devices: flat, and the spec collapses to plain "dp"
    sub = striped.stripe_mesh(jax.devices()[:4])
    assert sub.axis_names == ("dp",)
    assert striped.logical_spec("stripe", "shard", "byte",
                                mesh=sub) == P("dp", None, None)
    # ragged per-host counts fall back to flat (still dispatchable)
    ragged = striped.stripe_mesh(jax.devices()[:7])
    assert ragged.axis_names == ("dp",)


def test_down_host_injection_spec():
    spec = circuit.parse_injection("down_host=3")
    assert spec["down_host"] == 3
    spec = circuit.parse_injection("p=0.1,down-host=1")
    assert spec["down_host"] == 1 and spec["p"] == 0.1


def test_retire_host_is_one_breaker_event(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_MULTIHOST_HOSTS", "2")
    circuit.retire_host(1)
    st = circuit.host_breaker(1).stats()
    assert st["trips"] == 1
    assert circuit.host_degraded(1)
    # every chip of host 1 degraded through the ONE host breaker
    for did in multihost.hosts()[1]:
        assert circuit.device_degraded(did)
        assert circuit.device_breaker(did).state == circuit.CLOSED
    for did in multihost.hosts()[0]:
        assert not circuit.device_degraded(did)
    # host families stay out of perf_dump (label-map surface instead)
    assert not any(f.startswith("host:") for f in circuit.perf_dump())
    assert "1" in circuit.host_stats()


def test_agreement_single_process_identity():
    assert multihost.agree("t", "x") == {0: "x"}
    assert multihost.agreed_healthy([3, 1, 2]) == (1, 2, 3)


def test_mesh_info_surfaces_hosts(monkeypatch):
    monkeypatch.setenv("CEPH_TPU_MULTIHOST_HOSTS", "2")
    info = plan.mesh_info()
    assert info["host_count"] == 2
    assert set(info["hosts"]) == {"0", "1"}
    assert info["hosts"]["0"]["degraded"] == 0
    assert "host_retirements" in info
