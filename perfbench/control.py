"""The lower-precision control of a cell, read on the chip.

    python3 perfbench/control.py --workload ba100k-steady \\
        --seeds 11,12,13 --seconds 10 --precision f32

Sets the cell up once with the program at ``--precision`` (the control:
the nearest precision below the configuration's), then for each seed
serves the cell's own traffic for ``--seconds``, compares the sample a
run compares with the plain reference, and prints one JSON line per
seed: ``correct`` and each compared number beside its limit.  The
control has to come out not correct on every seed.  ``--precision``
left out runs the configuration's own precision, the sound readings.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import bench, cell, check  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--precision", default=None)
    args = ap.parse_args()
    cell.compile_cache()
    spec = bench.cell(bench.load(), args.workload)
    info = cell.device_info(int(spec["cell"]["chips"]))
    sys.path.insert(0, os.path.join(bench.CHECKOUT, "src"))
    ses = cell.Session(args.workload, t_start=T_START, info=info,
                       precision=args.precision)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = ses.window(ses.traffic, seed, args.seconds, False)
        sampled = check.sample(run.answered, run.batches,
                               int(run.traffic["check_sample"]), seed)
        verdict = check.compare(run.requests, sampled, ses.ov,
                                run.config, run.config["check"],
                                ses.reference)
        print(json.dumps({"seed": seed, "precision": args.precision,
                          "correct": verdict["ok"],
                          "sampled": verdict["sampled"],
                          "check": verdict["numbers"]}), flush=True)
    ses.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
