"""The control of ``correct``, and the readings that set its limits.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 --seconds <s> [--sound] [--gaps DIR]

The configuration states bfloat16; the step below it that would tempt a
later PR is int8, and the program has an int8 path of its own: the
kept-tile pack (``PruneRecipe.quant = "int8"``, served with
``ServeConfig.quant`` left to follow the plans). By default each seed
is served on that path through the whole harness, the reference using
the selection of the same prune without the int8 pack, and the command
exits non-zero where such a control run comes out correct. ``--sound``
serves the program as configured instead: the readings the limits have
to stay above. All seeds run in one process, one after another, each
with its own set-up.

One line per seed gives every number compared; ``--gaps DIR`` also
keeps each seed's per-token gaps there (``<seed>.npz``, one array per
checked request). The last line sums the readings up.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def int8_artifact(dense, cfg, recipe, art):
    """The same prune with the int8 pack: the served artifact."""
    from repro.core.pipeline import MosaicPipeline
    del art
    return MosaicPipeline(recipe.replace(quant="int8")).run(dense, cfg)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--sound", action="store_true",
                   help="serve the program as configured")
    p.add_argument("--gaps", type=Path, help="keep per-token gaps here")
    args = p.parse_args(argv)
    sys.path[0:1] = [str(ROOT / "src"), str(ROOT)]
    import numpy as np
    from bench import harness
    from bench.run import require_chips
    spec = harness.load_spec(args.workload, json.loads(
        (ROOT / "BENCHMARK.json").read_text()))
    require_chips(spec["chips"])
    readings = []
    for seed in args.seeds:
        hooks = {} if args.sound else {"artifact": int8_artifact}
        if args.gaps:
            args.gaps.mkdir(parents=True, exist_ok=True)
            hooks["gaps"] = lambda g, s=seed: np.savez(
                args.gaps / f"{s}.npz", *g)
        out = harness.run_cell(spec, seed, args.seconds, False,
                               time.monotonic(), hooks=hooks)
        readings.append({"seed": seed, "correct": out["correct"],
                         "compared": out["compared"],
                         "metrics": out["metrics"]})
        print(json.dumps(readings[-1]), flush=True)
    kind = "sound" if args.sound else "control"
    print(json.dumps({kind: args.workload, "readings": readings}))
    if not args.sound and any(r["correct"] for r in readings):
        sys.exit("control: a control run came out correct")


if __name__ == "__main__":
    main()
