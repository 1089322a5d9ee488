"""The readers of the EC 8+3 cell's new per-layer metrics: the GF
kernel's roofline share on a recorded trace fragment, and the codec's
compile seconds, which reads nothing from a program that lacks its
counter."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import trace  # noqa: E402
from benchmark.metrics import codec_compile_s  # noqa: E402
from benchmark.metrics import gf_encode_roofline as gfr  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e_ec83_fused_encode.xplane.pb")
GEOMETRY = {"k": 8, "m": 3, "chunk": 4096, "object_stripes": 128}


@pytest.fixture(scope="module")
def recorded():
    """Recorded on a TPU v5e: three fused encode+CRC
    dispatches of 128 stripes (4 MiB, reed_sol_van 8+3, S=4096) inside
    the benchmark's window span."""
    return trace.reduce_events(trace.load(RECORDED))


def _stats(per_plan, **top):
    return lambda: {"per_plan": per_plan, **top}


def test_recorded_fragment_names_the_specialised_kernel(recorded):
    gf = {n: c for n, c in recorded["op_counts"].items()
          if n.startswith("gf_words")}
    assert gf == {"gf_words.1 custom-call tpu_custom_call": 3}
    assert gfr.kernel_seconds(recorded["ops"]) == \
        recorded["ops"]["gf_words.1 custom-call tpu_custom_call"]
    # the generic kernel is not the specialised one
    assert gfr.kernel_seconds({"gf_words_smem.1 custom-call": 1.0,
                               "crc32c_words.1 custom-call": 1.0}) == 0


def test_gf_encode_roofline_on_recorded_trace(recorded):
    w = {"trace": recorded, "traced": {"encode": {"requests": 3}},
         "device_kind": "TPU v5 lite", "geometry": GEOMETRY}
    share = gfr.share(w, 3)
    nbytes = 3 * 128 * (8 + 3) * 4096
    secs = recorded["ops"]["gf_words.1 custom-call tpu_custom_call"]
    assert share == pytest.approx(100 * nbytes / 819e9 / secs)
    assert 0 < share < 100
    assert gfr.share(w, None) is None
    assert gfr.share(dict(w, trace=None), 3) is None
    with pytest.raises(KeyError):
        gfr.share(dict(w, device_kind="TPU v9"), 3)


def test_gf_encode_roofline_writes_the_profiles_parity_rows(recorded):
    """read() counts the profile's m parity rows out; a run with no
    geometry (no EC pool) or no GF kernel in its trace reads nothing."""
    w = {"trace": recorded, "traced": {"encode": {"requests": 3}},
         "device_kind": "TPU v5 lite", "geometry": GEOMETRY}
    assert gfr.read(w) == gfr.share(w, 3)
    assert gfr.read(dict(w, geometry=dict(GEOMETRY, m=2))) == \
        pytest.approx(gfr.share(w, 3) * (8 + 2) / (8 + 3))
    assert gfr.read(dict(w, geometry=None)) is None
    assert gfr.read(dict(w, trace={"ops": {}})) is None


def test_codec_compile_s_reads_the_stage_seconds(monkeypatch):
    from ceph_tpu.ec import plan

    monkeypatch.setattr(plan, "stats", _stats(
        {}, codec_compiles=3, codec_compile_s=6.5))
    assert codec_compile_s.read({}) == 6.5
    monkeypatch.setattr(plan, "stats", _stats({}))
    assert codec_compile_s.read({}) is None
