"""The benchmark's command: one run of one cell on the chips of this
machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It looks for the chips the cell asks for and exits non-zero, printing no
result, where JAX finds no TPU or too few of them. Otherwise it runs the
cell (``bench/harness.py``): earlier lines say what it did, the last
lines on standard error give each number compared beside its limit, and
the last line on standard output is the result object.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def require_chips(n: int):
    """The JAX devices, or exit non-zero when they are not ``n`` TPUs
    or more."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        sys.exit(f"bench: this cell needs {n} TPU chip(s); JAX finds "
                 f"{len(devices)} {devices[0].platform!r} device(s)")
    return devices


def main(argv=None) -> None:
    args = parse(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]   # not bench/ itself
    from bench import harness
    bench_json = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec = harness.load_spec(args.workload, bench_json)
    require_chips(spec["chips"])
    result = harness.run_cell(spec, args.seed, args.seconds,
                              bool(args.trace), T_START)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
