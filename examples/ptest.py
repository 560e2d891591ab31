"""ptest — the bundled end-to-end MNIST example.

Reference parity (SURVEY.md §2 comp. 6, BASELINE.json:7): the reference's
``asyncsgd/ptest.lua`` was launched as ``mpirun -n 3 th ptest.lua`` and split
ranks into 2 pclients + 1 pserver training LeNet on MNIST. Here there is no
mpirun and no rank split: the worker "processes" are the devices of the TPU
slice (or a CPU-simulated mesh), and the algorithm is chosen by flag. All
flags come from :class:`mpit_tpu.utils.TrainConfig` (see
``examples/train.py`` for the preset-driven superset CLI).

Run on the simulated mesh:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
    python examples/ptest.py --algo easgd --epochs 3

Run on TPU hardware: python examples/ptest.py --algo easgd
The reference's literal shape: python examples/ptest.py --algo ps-easgd
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from mpit_tpu.utils.config import TrainConfig

    cfg = TrainConfig.from_args(description=__doc__)
    if cfg.preset is None and cfg.dataset != "mnist":
        raise SystemExit(
            "ptest is the MNIST example; use examples/train.py for other "
            "datasets"
        )

    from mpit_tpu.run import run

    r = run(cfg)
    if cfg.algo.startswith("ps-"):
        print(
            f"[ptest] {cfg.algo} ({r['clients']} pclients + {r['servers']} "
            f"pservers): test acc={r['accuracy']:.4f} "
            f"loss={r['final_loss']:.4f} wall={r['wall_s']:.1f}s "
            f"({r['samples_per_sec']:.0f} samples/sec) "
            f"server_counts={r['server_counts']}"
        )
    else:
        print(
            f"[ptest] {cfg.algo}: test acc={r['accuracy']:.4f} "
            f"loss={r['final_loss']:.4f} wall={r['wall_s']:.1f}s "
            f"({r['samples_per_sec']:.0f} samples/sec, "
            f"{r['samples_per_sec_per_chip']:.0f} per worker)"
        )


if __name__ == "__main__":
    main()
