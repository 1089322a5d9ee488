#!/usr/bin/env python3
"""Chip smoke: the OSD write/read path and bulk placement on one TPU chip.

One process holds the chip and drives the system through the entry
points a user calls:

* cluster — a mon and 12 OSD daemons in one asyncio loop, on the real
  client -> messenger -> OSD op queue -> encode service -> ec_jax ->
  store path, with an EC pool of ec_jax reed_sol_van k=8 m=3 and
  crush-failure-domain=osd (BASELINE configs #2/#5);
* write/read — 256 objects of 4 MiB with 16 in flight (Ceph's
  documented ``rados bench -b 4M -t 16`` defaults), every object read
  back bit-exact;
* scrub — a deep scrub of one PG, which must come back clean;
* degraded — one OSD killed, 32 objects that lost a data shard read
  back bit-exact through the device decode;
* placement — ``crushtool --test --num-rep 3`` (BASELINE #4) on a
  10k-OSD map over 2^18 inputs, batched, with a sample of 4096
  placements compared with the host mapper (0 differences).

The run fails, and prints no ok line, when JAX finds no TPU, when the
device backend does not initialize, when the Pallas encode or crc
kernels never ran, or when any device breaker records a failure,
fallback, timeout or OOM.  The last stdout line is exactly
``{"ok": true, "device": {"platform", "kind", "count"}}``; each phase
prints one JSON line before it.

``--chips 4`` runs only the stripe-mesh path over four chips, compared
bit-exact with the one-chip plan and the host oracle on the same bytes.
``--cpu-rehearsal`` runs the same phases at tiny sizes on the CPU with
the Pallas kernels in interpret mode; its last line names the platform
it really had.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys
import time
from dataclasses import dataclass

REPO = os.path.dirname(os.path.abspath(__file__))

K, M = 8, 3
PROFILE = {"plugin": "ec_jax", "technique": "reed_sol_van",
           "k": str(K), "m": str(M), "crush-failure-domain": "osd"}
STRIPE_UNIT = 4096            # osd_pool_erasure_code_stripe_unit default
BREAKER_BAD = ("failures", "fallbacks", "watchdog_timeouts", "trips")


@dataclass(frozen=True)
class Size:
    objects: int
    obj_bytes: int
    inflight: int
    degraded: int
    pg_num: int
    crush_osds: int
    crush_inputs: int
    crush_sample: int


CHIP = Size(objects=256, obj_bytes=4 << 20, inflight=16, degraded=32,
            pg_num=32, crush_osds=10000, crush_inputs=1 << 18,
            crush_sample=4096)
REHEARSAL = Size(objects=8, obj_bytes=64 << 10, inflight=4, degraded=2,
                 pg_num=8, crush_osds=400, crush_inputs=1 << 12,
                 crush_sample=512)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


@contextlib.contextmanager
def flag(name: str, value: str):
    """Set one ceph_tpu env flag for a block, restoring it after."""
    from ceph_tpu.common import flags

    prev = flags.peek(name)
    flags.set_flag(name, value)
    try:
        yield
    finally:
        if prev is None:
            flags.clear(name)
        else:
            flags.set_flag(name, prev)


class CompileClock:
    """Sums JAX's own compile events so each phase reports them:
    tracing plus lowering (never skipped by the persistent cache), the
    backend compile (skipped on a cache hit), and the persistent
    cache's hits and misses."""

    _DURATIONS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_lower_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_lower_s",
        "/jax/core/compile/backend_compile_duration": "backend_compile_s",
    }
    _EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
               "/jax/compilation_cache/cache_misses": "cache_misses"}

    def __init__(self):
        import jax

        self.totals = dict.fromkeys(
            ("trace_lower_s", "backend_compile_s", "cache_hits",
             "cache_misses"), 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if event in self._DURATIONS:
            self.totals[self._DURATIONS[event]] += duration

    def _on_event(self, event: str, **_kw) -> None:
        if event in self._EVENTS:
            self.totals[self._EVENTS[event]] += 1

    def mark(self) -> dict:
        return dict(self.totals)

    def since(self, mark: dict) -> dict:
        return {k: v - mark[k] for k, v in self.totals.items()}


def device_summary() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def breaker_faults() -> dict:
    """Every breaker family's bad counters that are non-zero."""
    from ceph_tpu.common import circuit

    bad = {}
    for fam, st in circuit.stats_all().items():
        hits = {c: st.get(c, 0) for c in BREAKER_BAD if st.get(c, 0)}
        if hits or st.get("state") != "closed":
            bad[fam] = {**hits, "state": st.get("state")}
    return bad


def tier_counts() -> dict:
    from ceph_tpu.ec import plan
    from ceph_tpu.parallel import backend

    ps = plan.stats()
    executors = {}
    for row in ps["per_plan"].values():
        # rows with no executor are jits traced inside a plan (the
        # stripe pipeline's), whose dispatches the plan counts
        if "executor" in row:
            ex = row["executor"]
            executors[ex] = executors.get(ex, 0) + int(row["dispatches"])
    return {"plan_dispatches_by_executor": executors,
            "plan_retraces": ps["retraces"],
            "plan_host_fallbacks": ps["host_fallbacks"],
            "plan_oom_splits": ps["oom_splits"],
            "mesh_dispatches": ps["mesh_dispatches"],
            "backend": dict(backend.stats)}


def assert_device_clean(where: str) -> None:
    from ceph_tpu.ec import plan

    bad = breaker_faults()
    check(not bad, f"{where}: breaker faults {bad}")
    ps = plan.stats()
    check(ps["host_fallbacks"] == 0,
          f"{where}: {ps['host_fallbacks']} plan host fallbacks")
    check(ps["oom_splits"] == 0, f"{where}: {ps['oom_splits']} OOM splits")


# ---------------------------------------------------------------------------
# One chip: warm-up, cluster, placement
# ---------------------------------------------------------------------------


def warm_up(size: Size, clock: CompileClock) -> None:
    """Compile every EC shape the cluster phases dispatch, with the
    dispatch watchdog raised: a cold compile must not trip a breaker.
    The encode service flushes at 8 MiB, so batches of 1-3 objects:
    stripe buckets of 128, 256 and 512 at 4 MiB objects."""
    import numpy as np

    from ceph_tpu.ec.registry import create_erasure_code

    codec = create_erasure_code(dict(PROFILE))
    check(codec.use_tpu, "ec_jax codec did not take the device path")
    stripes = size.obj_bytes // (K * STRIPE_UNIT)
    buckets = sorted({stripes * n for n in (1, 2, 3)})
    t0, c0 = time.monotonic(), clock.mark()
    with flag("CEPH_TPU_DEVICE_TIMEOUT_S", "1800"):
        for b in buckets:
            data = np.zeros((b, K, STRIPE_UNIT), dtype=np.uint8)
            check(codec.encode_batch_with_crc(data) is not None,
                  f"warm-up: fused encode at {b} stripes")
            for lost in range(1, M + 1):
                have = tuple(range(lost, K + lost))
                codec.decode_batch(have, tuple(range(lost)), data)
    emit("warm_up", stripe_buckets=buckets,
         wall_s=time.monotonic() - t0, **clock.since(c0))
    assert_device_clean("warm-up")


def _pg_of(osdmap, pool, oid: str):
    from ceph_tpu.ops.rjenkins import ceph_str_hash_rjenkins
    from ceph_tpu.osd.osdmap import PgId

    pg = pool.raw_pg_to_pg(
        PgId(pool.id, ceph_str_hash_rjenkins(oid.encode())))
    acting, primary = osdmap.pg_to_acting_osds(pg)
    return pg, acting, primary


async def cluster_phases(size: Size, seed: int,
                         clock: CompileClock) -> None:
    import numpy as np

    sys.path.insert(0, os.path.join(REPO, "tests"))
    from cluster_helpers import Cluster

    from ceph_tpu.common import circuit

    rng = np.random.default_rng(seed)
    blob = rng.integers(0, 256, size.objects * size.obj_bytes,
                        dtype=np.uint8).tobytes()
    mv = memoryview(blob)

    def payload(i: int) -> memoryview:
        return mv[i * size.obj_bytes:(i + 1) * size.obj_bytes]

    names = [f"smoke-{i:05d}" for i in range(size.objects)]
    cluster = Cluster(num_osds=12, osds_per_host=4)
    t0 = time.monotonic()
    await cluster.start()
    try:
        await cluster.client.create_ec_pool("smoke-ec", dict(PROFILE),
                                            pg_num=size.pg_num)
        await cluster.wait_for_clean(timeout=120)
        io = cluster.client.open_ioctx("smoke-ec")
        emit("cluster_up", osds=12, pg_num=size.pg_num,
             wall_s=time.monotonic() - t0)
        gate = asyncio.Semaphore(size.inflight)

        async def bounded(coro):
            async with gate:
                return await coro

        # -- write ---------------------------------------------------
        before = tier_counts()["plan_dispatches_by_executor"]
        t0, c0 = time.monotonic(), clock.mark()
        await asyncio.gather(*(bounded(io.write_full(n, bytes(payload(i))))
                               for i, n in enumerate(names)))
        dt = time.monotonic() - t0
        after = tier_counts()["plan_dispatches_by_executor"]
        emit("write", objects=size.objects, obj_bytes=size.obj_bytes,
             inflight=size.inflight,
             logical_bytes=size.objects * size.obj_bytes,
             shard_bytes=size.objects * size.obj_bytes * (K + M) // K,
             wall_s=dt, **clock.since(c0),
             plan_dispatches_by_executor={
                 ex: n - before.get(ex, 0) for ex, n in after.items()})
        assert_device_clean("write")

        # -- read back -----------------------------------------------
        t0, c0 = time.monotonic(), clock.mark()

        async def read_ok(i: int, name: str) -> bool:
            return await io.read(name) == payload(i)

        ok = await asyncio.gather(*(bounded(read_ok(i, n))
                                    for i, n in enumerate(names)))
        check(all(ok), f"read: {ok.count(False)} objects differ")
        emit("read", objects_verified=len(ok),
             bytes_verified=len(ok) * size.obj_bytes,
             wall_s=time.monotonic() - t0, **clock.since(c0))

        # -- deep scrub of one PG ------------------------------------
        osdmap = cluster.mon.osdmap
        pool = osdmap.pools[osdmap.lookup_pool("smoke-ec")]
        pg, acting, primary = _pg_of(osdmap, pool, names[0])
        prim = cluster.osds[primary]
        t0 = time.monotonic()
        res = await prim.scrub_pg(prim.pgs[pg], pool)
        check(res["objects"] >= 1 and res["errors"] == 0,
              f"scrub {pg}: {res}")
        emit("scrub", pg=str(pg), **res, wall_s=time.monotonic() - t0)

        # -- one OSD down, degraded reads through the device decode ---
        victim = acting[1]
        lost = []
        for i, n in enumerate(names):
            _pg, n_acting, n_primary = _pg_of(osdmap, pool, n)
            if victim in n_acting[:K] and n_primary != victim:
                lost.append((i, n))
        lost = lost[:size.degraded]
        check(len(lost) == size.degraded,
              f"only {len(lost)} objects hold a data shard on"
              f" osd.{victim}")
        await cluster.kill_osd(victim)
        await cluster.wait_for_osd_down(victim, timeout=60)
        decode_before = circuit.breaker("ec-decode").stats()["successes"]
        t0, c0 = time.monotonic(), clock.mark()
        ok = await asyncio.gather(*(bounded(read_ok(i, n))
                                    for i, n in lost))
        check(all(ok), f"degraded read: {ok.count(False)} objects differ")
        decodes = circuit.breaker("ec-decode").stats()["successes"] \
            - decode_before
        check(decodes > 0, "degraded reads never reached the device decode")
        emit("degraded_read", killed_osd=victim, objects_verified=len(ok),
             device_decodes=decodes,
             wall_s=time.monotonic() - t0, **clock.since(c0))
        assert_device_clean("degraded read")
    finally:
        await cluster.stop()


def placement_phase(size: Size, clock: CompileClock) -> None:
    import numpy as np

    from ceph_tpu.crush import kernel as crush_kernel
    from ceph_tpu.crush import mapper as crush_mapper
    from ceph_tpu.crush.map import CRUSH_ITEM_NONE, build_flat_cluster
    from ceph_tpu.tools import crushtool

    cmap = build_flat_cluster(size.crush_osds, osds_per_host=20,
                              hosts_per_rack=10)
    ruleno = cmap.add_simple_rule("data", "default", "host",
                                  mode="firstn")
    weights = cmap.full_weight_vector()
    xs = np.arange(size.crush_inputs, dtype=np.int64)
    t0, c0 = time.monotonic(), clock.mark()
    rows, tier = crushtool._bulk_do_rule(cmap, ruleno, xs, 3, weights)
    dt = time.monotonic() - t0
    check(tier == "device", f"placement served by the {tier} mapper")
    check(rows.shape == (len(xs), 3), f"placement shape {rows.shape}")
    check(bool(((rows >= 0) & (rows < size.crush_osds)).all()),
          "placement holds an out-of-range or unplaced osd")
    sample = xs[::max(1, len(xs) // size.crush_sample)]
    diffs = 0
    for x in sample:
        want = crush_mapper.crush_do_rule(cmap, ruleno, int(x), 3, weights)
        want = list(want) + [CRUSH_ITEM_NONE] * (3 - len(want))
        diffs += [int(v) for v in rows[int(x)]] != want
    emit("placement", osds=size.crush_osds, inputs=len(xs),
         batch=min(len(xs), crush_kernel.MAX_BATCH), tier=tier, wall_s=dt,
         **clock.since(c0), sampled=len(sample), diffs=diffs)
    check(diffs == 0, f"placement: {diffs} of {len(sample)} differ from"
          " the host mapper")


def setup(rehearsal: bool) -> None:
    from ceph_tpu import native
    from ceph_tpu.common import circuit, jaxcache
    from ceph_tpu.ec import plan
    from ceph_tpu.ops import gf

    cache = None if rehearsal else jaxcache.enable()
    check(gf.backend_available(), "jax backend did not initialize")
    emit("setup", device=device_summary(), compile_cache=cache,
         native_lib=native.get_lib() is not None,
         native_error=native.build_error())
    circuit.reset_all()
    # the checks below count this run's plan fallbacks, not those of
    # whatever ran earlier in the process
    plan.reset_stats()


def one_chip(size: Size, seed: int) -> None:
    from ceph_tpu.common import circuit

    clock = CompileClock()
    warm_up(size, clock)
    t0 = time.monotonic()
    asyncio.run(cluster_phases(size, seed, clock))
    cluster_s = time.monotonic() - t0
    placement_phase(size, clock)
    counts = tier_counts()
    emit("tiers", cluster_wall_s=cluster_s, compile_totals=clock.totals,
         **counts, breakers=circuit.stats_all())
    # the fused plan's executor holds both kernels: words GF and crc
    check(counts["plan_dispatches_by_executor"].get(
        "pallas_words+crc", 0) > 0,
        "the Pallas encode and crc kernels never ran")
    assert_device_clean("end of run")


# ---------------------------------------------------------------------------
# Four chips: the stripe mesh against one chip and the host oracle
# ---------------------------------------------------------------------------


def four_chips(size: Size, seed: int) -> None:
    import jax
    import numpy as np

    from ceph_tpu.ec import plan
    from ceph_tpu.models import reed_solomon as rs
    from ceph_tpu.ops import checksum as cks
    from ceph_tpu.ops import gf, gf_pallas
    from ceph_tpu.parallel import backend

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4: jax sees {len(devices)} devices")
    matrix = rs.reed_sol_van_matrix(K, M)
    gf_pallas.register_matrix(matrix)          # what ec_jax init() does
    stripes = size.obj_bytes // (K * STRIPE_UNIT)
    rng = np.random.default_rng(seed)
    # 16 objects: past the plan's 1 MiB mesh floor at either size
    data = rng.integers(0, 256, (16 * stripes, K, STRIPE_UNIT),
                        dtype=np.uint8)
    t0 = time.monotonic()
    parity_host = gf.gf_matmul_host(
        matrix, np.ascontiguousarray(np.moveaxis(data, 1, 0)).reshape(
            K, -1)).reshape(M, len(data), STRIPE_UNIT).swapaxes(0, 1)
    chunks = np.concatenate([data, parity_host], axis=1)
    crc_host = cks.crc32c_blocks(chunks, STRIPE_UNIT, init=0).reshape(
        len(data), K + M)
    host_s = time.monotonic() - t0

    # the process-default mesh the daemons' EC dispatch rides
    mesh = backend.default_mesh()
    check(mesh.devices.size == 4,
          f"default_mesh spans {mesh.devices.size} devices")
    pipe = backend._pipeline(K, M, STRIPE_UNIT, backend._mesh_sig(mesh))
    t0 = time.monotonic()
    parity_dev, crc_dev, _ = pipe.encode(pipe.put_stripes(data))
    parity_mesh = np.asarray(parity_dev)
    mesh_s = time.monotonic() - t0
    holders = {s.device.id for s in parity_dev.addressable_shards
               if s.data.size}
    crc_seed = cks.crc32c_zeros(0xFFFFFFFF, STRIPE_UNIT)
    mesh_crc_ok = bool(np.array_equal(np.asarray(crc_dev) ^ np.uint32(
        crc_seed), crc_host))

    # the ExecPlan stripe mesh (ec/plan.py), then the one-chip plan
    t0 = time.monotonic()
    before = plan.stats()["mesh_dispatches"]
    par_plan, crc_plan = plan.encode_with_crc(matrix, data)
    plan_mesh_s = time.monotonic() - t0
    mesh_plan_dispatches = plan.stats()["mesh_dispatches"] - before
    with flag("CEPH_TPU_MESH", "0"):
        t0 = time.monotonic()
        par_one, crc_one = plan.encode_with_crc(matrix, data)
        one_s = time.monotonic() - t0
    emit("mesh4", devices=len(devices), mesh_shape=dict(mesh.shape),
         stripes=len(data), data_bytes=int(data.nbytes),
         output_shard_devices=sorted(holders),
         sharding_device_set=len(parity_dev.sharding.device_set),
         mesh_plan_dispatches=mesh_plan_dispatches,
         pipeline_vs_host=bool(np.array_equal(parity_mesh, parity_host)),
         pipeline_crc_vs_host=mesh_crc_ok,
         plan_mesh_vs_host=bool(np.array_equal(par_plan, parity_host)
                                and np.array_equal(crc_plan, crc_host)),
         one_chip_vs_host=bool(np.array_equal(par_one, parity_host)
                               and np.array_equal(crc_one, crc_host)),
         wall_s={"host_oracle": host_s, "pipeline_mesh": mesh_s,
                 "plan_mesh": plan_mesh_s, "plan_one_chip": one_s})
    check(len(parity_dev.sharding.device_set) == 4 and len(holders) == 4,
          f"output shards sit on {sorted(holders)}")
    check(mesh_plan_dispatches > 0, "the plan never took the mesh")
    check(np.array_equal(parity_mesh, parity_host) and mesh_crc_ok,
          "default-mesh pipeline differs from the host oracle")
    check(np.array_equal(par_plan, parity_host)
          and np.array_equal(crc_plan, crc_host),
          "mesh plan differs from the host oracle")
    check(np.array_equal(par_one, par_plan)
          and np.array_equal(crc_one, crc_plan),
          "one-chip plan differs from the mesh plan")
    assert_device_clean("mesh")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the stripe-mesh phase")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny sizes on the CPU, Pallas interpreted")
    ap.add_argument("--seed", type=int, default=20260)
    args = ap.parse_args(argv)
    try:
        import jax

        platform = jax.devices()[0].platform
    except Exception as e:      # no backend at all
        print(f"# no JAX backend: {e!r}", file=sys.stderr)
        return 2
    if platform != "tpu" and not args.cpu_rehearsal:
        print(f"# JAX found no TPU (platform {platform!r});"
              " --cpu-rehearsal runs the tiny CPU version",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    from ceph_tpu.ops import crc_pallas, gf_pallas

    size = REHEARSAL if args.cpu_rehearsal else CHIP
    saved = gf_pallas.FORCE_INTERPRET, crc_pallas.FORCE_INTERPRET
    # the rehearsal runs the chip's tiers in the interpreter; the fused
    # plan engages below 1 MiB on the CPU only when told to
    tiers = contextlib.nullcontext()
    if args.cpu_rehearsal:
        gf_pallas.FORCE_INTERPRET = crc_pallas.FORCE_INTERPRET = True
        tiers = flag("CEPH_TPU_FUSE_MIN_BYTES", "0")
    try:
        with tiers:
            setup(args.cpu_rehearsal)
            if args.chips == 4:
                four_chips(size, args.seed)
            else:
                one_chip(size, args.seed)
    except SmokeFailure as e:
        print(f"# FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        gf_pallas.FORCE_INTERPRET, crc_pallas.FORCE_INTERPRET = saved
    print(json.dumps({"ok": True, "device": device_summary()}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
