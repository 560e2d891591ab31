"""Sweep LeNet EASGD round timing over (per-worker batch, tau) on the live
backend; prints a JSON row per point (µs/round, samples/s/chip, MFU).

Used to pick the headline bench operating point (per-worker batch, tau).
"""

import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from bench import bench_jax  # noqa: E402


def main():
    batches = [int(b) for b in (sys.argv[1].split(",") if len(sys.argv) > 1
                                else ("256", "1024", "4096"))]
    taus = [int(t) for t in (sys.argv[2].split(",") if len(sys.argv) > 2
                             else ("1", "4", "16"))]
    for pwb in batches:
        for tau in taus:
            res = bench_jax(per_worker_batch=pwb, tau=tau)
            row = {
                "pwb": pwb,
                "tau": tau,
                "us_per_round": round(
                    1e6 * res["timed_seconds"] / res["timed_rounds"], 1
                ),
                "samples_per_sec_per_chip": round(
                    res["samples_per_sec_per_chip"], 1
                ),
                "mfu": res.get("mfu"),
            }
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
