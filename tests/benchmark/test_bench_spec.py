"""BENCHMARK.json against the contract's shape, and the harness finding a
configuration, a traffic mix and a per-layer metric by name from files
alone, as a later PR adds them."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert bench["command"][1] == "benchmark/run.py"
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_names_units_and_references(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    configs = {c["name"]: c for c in bench["configs"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(cells)
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".py"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(ROOT, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
        assert any(w["config"] == c["name"] for w in cells.values())
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "traffic",
                                           w["traffic"] + ".json"))
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == \
        len(cells)


NEW_METRIC = '''
def read(w):
    return w["window"]["encode"]["requests"]
'''


def test_later_pr_adds_config_traffic_and_metric_by_files(tmp_path, bench):
    """Copy the benchmark, add a configuration, a mix and a metric as new
    files with new BENCHMARK.json entries, and load the new cell."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "benchmark/configs/rados_r6_82.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "rados_r6_42"
    cfg["ec_profile"].update(k="4", m="2")
    (root / "benchmark/configs/rados_r6_42.json").write_text(json.dumps(cfg))
    with open(os.path.join(ROOT, "benchmark/traffic/write_4m.json")) as f:
        mix = json.load(f)
    mix.update(name="write_1m", object_bytes=1 << 20)
    (root / "benchmark/traffic/write_1m.json").write_text(json.dumps(mix))
    (root / "benchmark/metrics/encode_requests.py").write_text(NEW_METRIC)
    b = json.loads(json.dumps(bench))
    b["configs"].append({"name": "rados_r6_42", "source": "x",
                         "file": "benchmark/configs/rados_r6_42.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "rados_r6_42.write_1m",
                           "config": "rados_r6_42", "traffic": "write_1m",
                           "chips": 1, "why": "x"})
    b["per_layer"].append({"name": "encode_requests", "unit": "objects",
                           "better": "higher", "source": "program_counter",
                           "layer": "encode service",
                           "moves": "goodput_mibs",
                           "workloads": ["rados_r6_42.write_1m"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    src = ("from benchmark import harness; "
           "s = harness.load_spec('rados_r6_42.write_1m'); "
           "w = {'window': {'encode': {'requests': 7}}}; "
           "print(s.config['ec_profile']['k'], s.traffic['object_bytes'], "
           "[m['name'] for m in s.per_layer], "
           "harness._metric('encode_requests').read(w), "
           "harness._driver(s).__name__)")
    r = subprocess.run([sys.executable, "-c", src], cwd=str(root),
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=str(root)))
    assert r.returncode == 0, r.stderr[-2000:]
    k, size, rest = r.stdout.split(" ", 2)
    assert (k, size) == ("4", str(1 << 20))
    assert rest.split() == ["['encode_requests']", "7",
                            "benchmark.drivers.rados"]


REPLICATED = {
    "name": "rados_rep3", "source": "x", "guarantee": "x",
    "osds": 12, "hosts": 12,
    "pools": [{"name": "rbench", "type": "replicated", "size": 3,
               "pg_num": 32}],
    "data_pool": "rbench", "objectstore": "memstore",
    "osd_config": {"osd_heartbeat_interval": 6.0},
    "mon_config": {}, "gateway": None, "assumed": [], "reduced": []}


def test_later_pr_adds_a_replicated_config_by_files(tmp_path, bench):
    """A configuration with no EC pool (Ceph's default pool type) and its
    mix, added as files alone, runs a rehearsal to a correct result that
    compared every copy."""
    root = tmp_path / "checkout"
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), root / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "benchmark/configs/rados_rep3.json").write_text(
        json.dumps(REPLICATED))
    with open(os.path.join(ROOT, "benchmark/traffic/write_4m.json")) as f:
        mix = json.load(f)
    mix.pop("expect_executor")
    mix["name"] = "write_4m_rep"
    (root / "benchmark/traffic/write_4m_rep.json").write_text(
        json.dumps(mix))
    b = json.loads(json.dumps(bench))
    b["configs"].append({"name": "rados_rep3", "source": "x",
                         "file": "benchmark/configs/rados_rep3.json",
                         "reduced": [], "why": "x"})
    b["workloads"].append({"name": "rados_rep3.write_4m_rep",
                           "config": "rados_rep3",
                           "traffic": "write_4m_rep", "chips": 1,
                           "why": "x"})
    for m in b["per_layer"]:
        if m["name"] in ("osd_queue_ms", "osd_subop_ms"):
            m["workloads"].append("rados_rep3.write_4m_rep")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    src = ("import json, sys; sys.path.insert(0, 'tests/benchmark'); "
           "import bench_rehearsal; "
           "out = bench_rehearsal.run('rados_rep3.write_4m_rep', "
           "trace=True); "
           "print(json.dumps([out['correct'], out['diag']"
           "['mismatches_by_kind'], sorted(out['metrics'])]))")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), ROOT]))
    r = subprocess.run([sys.executable, "-c", src], cwd=str(root),
                       capture_output=True, text=True, timeout=300,
                       env=env)
    assert r.returncode == 0, r.stderr[-2000:]
    correct, kinds, metrics = json.loads(r.stdout.strip().splitlines()[-1])
    assert correct is True
    assert kinds == {"replicas_wrong": 0, "reads_wrong": 0,
                     "window_empty": 0}
    assert metrics == ["osd_queue_ms", "osd_subop_ms"]


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "rados_r6_82.write_4m", "--seed", "1", "--seconds", "1",
         "--trace", "0", *args], cwd=str(cwd), capture_output=True,
        text=True, timeout=120, env=env)


def test_no_tpu_exits_nonzero_with_no_result():
    r = _run(ROOT)
    assert r.returncode != 0
    assert "correct" not in r.stdout
    assert "TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path, bench):
    """A directory with BENCHMARK.json and the files under paths only."""
    for p in bench["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = _run(tmp_path)
    assert r.returncode != 0
    assert "correct" not in r.stdout
