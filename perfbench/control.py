"""Readings that set the check's limits, on the chip at a cell's size.

    python3 perfbench/control.py --workload <cell> --seeds 5 6 7 \\
        --seconds 10

For each seed: one run of the cell's timed path (short window, the
cell's own load), then the program's widest logit gap and the control's
-- the plain reference computed in float8 (e4m3, amax-scaled) in the
program's place, the next precision below the configuration's bfloat16
-- on the same sampled prompts and served tokens.  Prints one JSON line
per seed.  The benchmark's own runs never run the control.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--fault", default=None,
                    help="plant a fault (token | drop_half | retrieval)")
    args = ap.parse_args(argv)
    if len(args.seeds) > 1:
        # one process per seed: each holds the chip alone and frees all
        # of its state at exit (this parent never touches JAX)
        for seed in args.seeds:
            cmd = [sys.executable, __file__, "--workload", args.workload,
                   "--seeds", str(seed), "--seconds", str(args.seconds)]
            if args.fault:
                cmd += ["--fault", args.fault]
            subprocess.run(cmd, check=False)
        return 0
    from perfbench import harness
    seed = args.seeds[0]
    res = harness.run(ROOT, args.workload, seed, args.seconds, False, T0,
                      fault=args.fault, control="fp8")
    print(json.dumps({"seed": seed, "correct": res["correct"],
                      "program": {k: v["value"] for k, v in
                                  res["checked"].items()},
                      "control": res["control"],
                      "attempted": res["attempted"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
