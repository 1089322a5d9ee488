"""CLI tool tests: benchmark, ec-tool, non-regression, crushtool.

Each tool is driven through its run(argv) entry (what `python -m
ceph_tpu.tools.<name>` calls), mirroring the reference's smoke tests
(src/test/ceph-erasure-code-tool/test_ceph-erasure-code-tool.sh and the
crushtool round-trip fixtures).
"""

import json
import os

import numpy as np
import pytest

from ceph_tpu.tools import (
    crushtool,
    erasure_code_benchmark as ecb,
    erasure_code_tool as ect,
    non_regression,
)


# -- ceph_erasure_code_benchmark -------------------------------------------


def test_benchmark_encode(capsys):
    assert ecb.run(["-p", "jerasure", "-P", "k=4", "-P", "m=2",
                    "-s", "65536", "-i", "2"]) == 0
    cap = capsys.readouterr()
    seconds, kib = cap.out.strip().split("\t")
    assert float(seconds) > 0
    assert int(kib) == 2 * 64
    # the plan-cache counters go to stderr, off the one-line contract
    assert "plan-cache: hits=" in cap.err and "retraces=" in cap.err


def test_benchmark_decode_random(capsys):
    assert ecb.run(["-w", "decode", "-p", "jerasure", "-P", "k=4",
                    "-P", "m=2", "-s", "16384", "-i", "3",
                    "-e", "2"]) == 0
    assert "\t" in capsys.readouterr().out


def test_benchmark_decode_exhaustive(capsys):
    assert ecb.run(["-w", "decode", "-p", "jerasure", "-P", "k=2",
                    "-P", "m=2", "-s", "8192", "-E", "exhaustive",
                    "-e", "2"]) == 0


def test_benchmark_decode_erased_list(capsys):
    assert ecb.run(["-w", "decode", "-p", "isa", "-P", "k=4", "-P", "m=2",
                    "-s", "8192", "--erased", "0", "--erased", "3"]) == 0
    out = capsys.readouterr().out
    assert "(0)" in out and "(3)" in out  # display_chunks marks erased


# -- ceph-erasure-code-tool ------------------------------------------------

PROFILE = "plugin=jerasure,technique=reed_sol_van,k=4,m=2"


def test_ec_tool_plugin_exists():
    assert ect.run(["test-plugin-exists", "jerasure"]) == 0
    assert ect.run(["test-plugin-exists", "nonesuch"]) != 0


def test_ec_tool_validate_profile(capsys):
    assert ect.run(["validate-profile", PROFILE]) == 0
    out = capsys.readouterr().out
    assert "chunk_count=6" in out
    assert ect.run(["validate-profile", PROFILE, "data_chunk_count"]) == 0
    assert capsys.readouterr().out.strip() == "4"


def test_ec_tool_calc_chunk_size(capsys):
    assert ect.run(["calc-chunk-size", PROFILE, "4096"]) == 0
    assert int(capsys.readouterr().out) >= 1024


def test_ec_tool_encode_decode_round_trip(tmp_path):
    fname = str(tmp_path / "object")
    data = np.random.default_rng(0).integers(
        0, 256, 100_000, dtype=np.uint8).tobytes()
    with open(fname, "wb") as f:
        f.write(data)
    shards = ",".join(str(i) for i in range(6))
    assert ect.run(["encode", PROFILE, "4096", shards, fname]) == 0
    for i in range(6):
        assert os.path.exists(f"{fname}.{i}")
    # decode from a subset (drop shards 1 and 4)
    os.unlink(fname)
    assert ect.run(["decode", PROFILE, "4096", "0,2,3,5", fname]) == 0
    with open(fname, "rb") as f:
        restored = f.read()
    assert restored[:len(data)] == data


def test_ec_tool_usage(capsys):
    assert ect.run([]) == 1
    assert ect.run(["bogus-command"]) == 1


# -- non-regression corpus -------------------------------------------------


def test_non_regression_create_check(tmp_path):
    base = str(tmp_path)
    args = ["--plugin", "jerasure", "--base", base,
            "-P", "k=2", "-P", "m=2", "-P", "technique=reed_sol_van"]
    assert non_regression.run(args + ["--create"]) == 0
    dirs = os.listdir(base)
    assert len(dirs) == 1 and "plugin=jerasure" in dirs[0]
    archive = os.path.join(base, dirs[0])
    assert sorted(os.listdir(archive)) == ["0", "1", "2", "3", "content"]
    assert non_regression.run(args + ["--check"]) == 0


def test_non_regression_detects_corruption(tmp_path):
    base = str(tmp_path)
    args = ["--plugin", "jerasure", "--base", base, "-P", "k=2", "-P", "m=1"]
    assert non_regression.run(args + ["--create"]) == 0
    archive = os.path.join(base, os.listdir(base)[0])
    chunk = os.path.join(archive, "1")
    with open(chunk, "r+b") as f:
        f.seek(10)
        byte = f.read(1)
        f.seek(10)
        f.write(bytes([byte[0] ^ 0xFF]))
    assert non_regression.run(args + ["--check"]) == 1


# -- crushtool -------------------------------------------------------------

CRUSH_TEXT = """\
# begin crush map
tunable choose_local_tries 0
tunable choose_local_fallback_tries 0
tunable choose_total_tries 50
tunable chooseleaf_descend_once 1
tunable chooseleaf_vary_r 1
tunable chooseleaf_stable 1

# devices
device 0 osd.0 class hdd
device 1 osd.1 class ssd
device 2 osd.2 class hdd
device 3 osd.3 class ssd
device 4 osd.4 class hdd
device 5 osd.5 class hdd

# types
type 0 osd
type 1 host
type 11 root

# buckets
host host0 {
\tid -2
\talg straw2
\thash 0\t# rjenkins1
\titem osd.0 weight 1.00000
\titem osd.1 weight 1.00000
}
host host1 {
\tid -3
\talg straw2
\thash 0
\titem osd.2 weight 1.00000
\titem osd.3 weight 1.00000
}
host host2 {
\tid -4
\talg straw2
\thash 0
\titem osd.4 weight 1.00000
\titem osd.5 weight 2.00000
}
root default {
\tid -1
\talg straw2
\thash 0
\titem host0 weight 2.00000
\titem host1 weight 2.00000
\titem host2 weight 3.00000
}

# rules
rule replicated_rule {
\tid 0
\ttype replicated
\tmin_size 1
\tmax_size 10
\tstep take default
\tstep chooseleaf firstn 0 type host
\tstep emit
}
rule hdd_rule {
\tid 1
\ttype replicated
\tmin_size 1
\tmax_size 10
\tstep take default class hdd
\tstep chooseleaf firstn 0 type host
\tstep emit
}
# end crush map
"""


@pytest.fixture
def crush_text_file(tmp_path):
    path = str(tmp_path / "map.txt")
    with open(path, "w") as f:
        f.write(CRUSH_TEXT)
    return path


def test_crushtool_compile_decompile_round_trip(crush_text_file, tmp_path):
    compiled = str(tmp_path / "map.json")
    assert crushtool.run(["-c", crush_text_file, "-o", compiled]) == 0
    data = json.loads(open(compiled).read())
    assert len(data["buckets"]) >= 4
    decompiled = str(tmp_path / "map2.txt")
    assert crushtool.run(["-d", compiled, "-o", decompiled]) == 0
    text2 = open(decompiled).read()
    # recompile of the decompiled text parses to the same placements
    recompiled = str(tmp_path / "map3.json")
    with open(str(tmp_path / "map2b.txt"), "w") as f:
        f.write(text2)
    assert crushtool.run(["-c", decompiled, "-o", recompiled]) == 0


def test_crushtool_test_utilization(crush_text_file, capsys):
    assert crushtool.run(["-i", crush_text_file, "--test", "--num-rep", "3",
                          "--max-x", "255", "--show-utilization",
                          "--show-statistics"]) == 0
    out = capsys.readouterr().out
    assert "device 0:" in out
    assert "stored" in out and "expected" in out
    assert "result size == 3" in out


def test_crushtool_mappings_match_host_mapper(crush_text_file, capsys):
    """The --test path (TPU kernel or host) equals the exact host mapper."""
    from ceph_tpu.crush import mapper as m
    cmap = crushtool.load_map(crush_text_file)
    assert crushtool.run(["-i", crush_text_file, "--test", "--rule", "0",
                          "--num-rep", "3", "--max-x", "63",
                          "--show-mappings"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    weights = cmap.full_weight_vector()
    for line in out:
        # CRUSH rule 0 x X [a,b,c]
        parts = line.split()
        x = int(parts[4])
        got = [int(v) for v in parts[5].strip("[]").split(",") if v]
        want = [v for v in m.crush_do_rule(cmap, 0, x, 3, weights)
                if v >= 0]
        assert got == want, (x, got, want)


def test_crushtool_class_rule(crush_text_file, capsys):
    """Rule with `class hdd` places only on hdd devices (0,2,4,5)."""
    assert crushtool.run(["-i", crush_text_file, "--test", "--rule", "1",
                          "--num-rep", "2", "--max-x", "127",
                          "--show-mappings"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    hdd = {0, 2, 4, 5}
    for line in out:
        devs = [int(v) for v in line.split()[5].strip("[]").split(",") if v]
        assert set(devs) <= hdd, line


def test_crushtool_compare_self(crush_text_file, tmp_path, capsys):
    ref = str(tmp_path / "mappings.txt")
    assert crushtool.run(["-i", crush_text_file, "--test", "--rule", "0",
                          "--num-rep", "3", "--max-x", "127",
                          "--show-mappings"]) == 0
    with open(ref, "w") as f:
        f.write(capsys.readouterr().out)
    assert crushtool.run(["-i", crush_text_file, "--test", "--rule", "0",
                          "--num-rep", "3", "--max-x", "127",
                          "--compare", ref]) == 0
    assert "0 mismatches" in capsys.readouterr().out


def test_crushtool_bad_rule(crush_text_file, capsys):
    assert crushtool.run(["-i", crush_text_file, "--test",
                          "--rule", "9"]) == 1


def test_crushtool_predeclared_class_ids(tmp_path, capsys):
    """A map that pre-declares shadow ids (`id -N class c`) must still
    materialize the shadow hierarchy when a class rule runs (the reference
    always emits those lines on decompile)."""
    text = CRUSH_TEXT.replace(
        "host host0 {\n\tid -2",
        "host host0 {\n\tid -2\n\tid -12 class hdd")
    path = str(tmp_path / "declared.txt")
    with open(path, "w") as f:
        f.write(text)
    assert crushtool.run(["-i", path, "--test", "--rule", "1",
                          "--num-rep", "2", "--max-x", "63",
                          "--show-mappings"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 64
    hdd = {0, 2, 4, 5}
    for line in out:
        devs = [int(v) for v in line.split()[5].strip("[]").split(",") if v]
        assert devs and set(devs) <= hdd, line


def test_crushtool_choose_args_round_trip(tmp_path):
    text = CRUSH_TEXT + """
# choose_args
choose_args 0 {
  {
    bucket_id -1
    weight_set [
      [ 2.00000 2.00000 3.00000 ]
      [ 1.00000 2.00000 3.00000 ]
    ]
    ids [ -2 -3 -4 ]
  }
}
"""
    path = str(tmp_path / "ca.txt")
    with open(path, "w") as f:
        f.write(text)
    cmap = crushtool.load_map(path)
    assert -1 in cmap.choose_args
    assert cmap.choose_args[-1].weight_set[1] == [0x10000, 0x20000, 0x30000]
    assert cmap.choose_args[-1].ids == [-2, -3, -4]
    # decompile -> recompile preserves choose_args
    from ceph_tpu.crush import compiler as cc
    text2 = cc.decompile(cmap)
    cmap2 = cc.compile_text(text2)
    assert cmap2.choose_args[-1].weight_set == cmap.choose_args[-1].weight_set
    assert cmap2.choose_args[-1].ids == cmap.choose_args[-1].ids


def test_benchmark_exhaustive_with_erased(capsys):
    """--erased + -E exhaustive verifies against pristine chunks."""
    assert ecb.run(["-w", "decode", "-p", "jerasure", "-P", "k=2",
                    "-P", "m=2", "-s", "4096", "-E", "exhaustive",
                    "-e", "1", "--erased", "0"]) == 0


def test_ec_tool_incompatible_stripe_unit(tmp_path, capsys):
    fname = str(tmp_path / "f")
    with open(fname, "wb") as f:
        f.write(b"x" * 1000)
    rc = ect.run(["encode", "plugin=clay,k=4,m=2", "100",
                  "0,1,2,3,4,5", fname])
    assert rc == 1
    err = capsys.readouterr().err
    assert "incompatible" in err or "usage" in err


# -- rados bench zipf sampler (the skewed-read tier leg) --------------------


def test_zipf_indices_deterministic_and_skewed():
    from ceph_tpu.tools.rados import zipf_indices

    a = zipf_indices(1.2, 64, 10_000, seed=5)
    b = zipf_indices(1.2, 64, 10_000, seed=5)
    assert np.array_equal(a, b), "same seed must reproduce the stream"
    assert not np.array_equal(a, zipf_indices(1.2, 64, 10_000, seed=6))
    assert a.min() >= 0 and a.max() < 64
    # rank 0 dominates under theta=1.2 and the mass is monotone-ish
    counts = np.bincount(a, minlength=64)
    assert counts[0] == counts.max()
    assert counts[0] > 10_000 / 64 * 4, "head not hot enough"
    # theta=0 degenerates to uniform (no rank dominates 3x the mean)
    flat = np.bincount(zipf_indices(0.0, 64, 10_000, seed=5),
                       minlength=64)
    assert flat.max() < 3 * 10_000 / 64
