"""Compile the main-path kernels for a described TPU v5e (no chip).

The TPU compiler is installed here and compiles for a chip that is
described, not attached: what Mosaic or XLA would refuse on the chip
fails here at no chip time.  The words kernels (RS 8+3 at 4 KiB and
512 KiB chunks), the crc kernel (4 KiB and 8 KiB blocks) and the fused
encode+crc plan step compile fresh, again after the CRUSH kernel module
is imported, and again under a process-wide x64 — the regression guard
for the fault that turned every Pallas index map into i64.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""

from __future__ import annotations

import importlib

import numpy as np
import pytest

jax = pytest.importorskip("jax")
jnp = jax.numpy

from ceph_tpu.ec import plan  # noqa: E402
from ceph_tpu.models import reed_solomon as rs  # noqa: E402
from ceph_tpu.ops import crc_pallas, gf_pallas  # noqa: E402

MATRIX = rs.reed_sol_van_matrix(8, 3)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _words(b, chunk, sharding):
    return jax.ShapeDtypeStruct((b, 8, chunk // 512, 128), jnp.int32,
                                sharding=sharding)


def _spec(sharding, chunk):
    r4 = chunk // 512
    call = gf_pallas._spec_call(gf_pallas._coeff_key(MATRIX), 1, r4,
                                gf_pallas._pick_ts(r4))
    return call, (_words(1, chunk, sharding),)


def _gen(sharding, chunk):
    r4 = chunk // 512
    call = gf_pallas._gen_call(3, 8, 1, r4, gf_pallas._pick_ts(r4))
    mat = jax.ShapeDtypeStruct((3, 8), jnp.int32, sharding=sharding)
    return call, (mat, _words(1, chunk, sharding))


def _crc(sharding, w):
    call = crc_pallas._crc_call(1, w)
    return call, (
        jax.ShapeDtypeStruct((crc_pallas._BT, w), jnp.int32,
                             sharding=sharding),
        jax.ShapeDtypeStruct((32, w, 128), jnp.int8, sharding=sharding))


def _fused(sharding, chunk):
    return (lambda w: plan.fused_encode_crc_words(MATRIX, w),
            (_words(128, chunk, sharding),))


KERNELS = {
    "spec-4KiB": (_spec, 4096), "spec-512KiB": (_spec, 512 << 10),
    "gen-4KiB": (_gen, 4096), "gen-512KiB": (_gen, 512 << 10),
    "crc-w1024": (_crc, 1024), "crc-w2048": (_crc, 2048),
    "fused-plan-4KiB": (_fused, 4096),
}


@pytest.mark.parametrize("state", ["fresh", "after-crush-import",
                                   "x64-on"])
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, state):
    build, arg = KERNELS[kernel]
    if state == "after-crush-import":
        importlib.import_module("ceph_tpu.crush.kernel")
        assert not jax.config.jax_enable_x64, \
            "importing the CRUSH kernel turned on x64 for the process"
    call, shapes = build(one_chip, arg)
    if state == "x64-on":
        with jax.enable_x64(True):
            compiled = jax.jit(call).lower(*shapes).compile()
    else:
        compiled = jax.jit(call).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None


def test_words_kernel_matches_host_in_interpret_mode():
    """The kernels compiled above compute the host oracle's bytes
    (interpret mode on the CPU: the same kernel bodies)."""
    prev = gf_pallas.FORCE_INTERPRET, crc_pallas.FORCE_INTERPRET
    gf_pallas.FORCE_INTERPRET = crc_pallas.FORCE_INTERPRET = True
    try:
        from ceph_tpu.ops import checksum as cks
        from ceph_tpu.ops import gf

        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, (2, 8, 4096), dtype=np.uint8)
        with jax.enable_x64(True):
            par_w, crcs = jax.jit(
                lambda w: plan.fused_encode_crc_words(MATRIX, w))(
                    gf_pallas.words_from_bytes(data))
        parity = gf_pallas.bytes_from_words(np.asarray(par_w))
        want = np.stack([gf.gf_matmul_host(MATRIX, d) for d in data])
        np.testing.assert_array_equal(parity, want)
        chunks = np.concatenate([data, want], axis=1)
        np.testing.assert_array_equal(
            np.asarray(crcs),
            cks.crc32c_blocks(chunks, 4096, init=0).reshape(2, 11))
    finally:
        gf_pallas.FORCE_INTERPRET, crc_pallas.FORCE_INTERPRET = prev
