"""Run one cell of the benchmark once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.  ``--fault`` plants a fault from faults/ (the
control and the fault tests); the benchmark's own runs never pass it.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout's root, not benchmark/, heads the path: trace.py must not
# shadow the standard library's module of that name
sys.path[0] = ROOT
# JAX's persistent compile cache, at the fixed path jaxcache.enable()
# names, inside the checkout: set before JAX is imported.  No size cap:
# the capped cache evicts by access-time files, and one entry written
# without them (by an uncapped process in the same checkout) stops it
# writing anything at all
CACHE = os.path.join(ROOT, ".jax_cache")
os.makedirs(CACHE, exist_ok=True)
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    from benchmark import harness

    try:
        spec = harness.load_spec(args.workload)
    except harness.SpecError as e:
        print(f"# {e}", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    chips = int(spec.cell["chips"])
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"# needs {chips} TPU chip(s); JAX found {len(devs)}"
              f" {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    from ceph_tpu.common import jaxcache

    jaxcache.enable()
    out = harness.run_cell(spec, args.seed, args.seconds,
                           bool(args.trace), T_START, fault=args.fault)
    harness.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
