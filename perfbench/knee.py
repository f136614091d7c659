"""Find an open-loop cell's knee: the highest arrival rate it sustains.

    python3 perfbench/knee.py --workload ba100k-steady \\
        --rates 1.5,2,2.5,3,3.5 --seconds 30 --seed 1

Sets the cell up once, then serves its traffic at each rate in turn for
``--seconds`` and prints one JSON line per rate: requests due and
answered, the answered rate over the time to the last answer, p50 and
p90 latency from due time, and how much slower the last quarter of the
window's requests were than the first (a queue that grows all through
the window shows as a ratio well above 1).  Run it on the chip when a
cell is defined or a later benchmark change moves the knee; the cell's
traffic file then takes 0.8 of the highest sustained rate.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import bench, cell, traffic  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell.compile_cache()
    spec = bench.cell(bench.load(), args.workload)
    info = cell.device_info(int(spec["cell"]["chips"]))
    sys.path.insert(0, os.path.join(bench.CHECKOUT, "src"))
    ses = cell.Session(args.workload, t_start=T_START, info=info)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        tr = dict(ses.traffic, rate_per_s=rate)
        run = ses.window(tr, args.seed + i, args.seconds, False)
        lat = [r.latency_s for r in run.requests]
        q = max(1, len(lat) // 4)
        done = run.answered
        first, last = lat[:q], lat[-q:]
        print(json.dumps({
            "rate_per_s": rate, "due": len(lat), "answered": len(done),
            "answered_per_s": (len(done) / max(r.done_s for r in done)
                               if done else 0.0),
            "p50_s": traffic.percentile(lat, 50),
            "p90_s": traffic.percentile(lat, 90),
            "last_over_first_quarter": (
                sum(last) / sum(first) if all(map(math.isfinite, lat))
                else math.inf)}), flush=True)
    ses.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
