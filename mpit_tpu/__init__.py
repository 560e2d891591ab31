"""mpit_tpu — a TPU-native distributed training framework.

A ground-up, jax/XLA-first rebuild of the capability surface of
``JiatianWu/mpiT`` (an MPI-for-Torch binding plus an asynchronous
parameter-server training harness; see SURVEY.md — the reference mount was
empty at survey time, so citations are to SURVEY.md/BASELINE.json rather than
reference file:line):

- ``mpit_tpu.comm``      — topology bootstrap + collectives. Replaces the
  reference's C MPI binding (SURVEY.md §2 comp. 1): ``MPI_Init/rank/size`` →
  TPU-slice discovery + ``jax.sharding.Mesh``; ``MPI_Allreduce/Bcast/Barrier``
  → ``jax.lax.psum``/friends over ICI.
- ``mpit_tpu.transport`` — tagged send/recv with ANY_SOURCE/ANY_TAG semantics
  for the host-async parameter-server protocol (the part of MPI that has no
  XLA analogue), over in-process queues or TCP sockets.
- ``mpit_tpu.goptim``    — distributed optimizers (EASGD/EAMSGD, Downpour)
  re-expressed as jit-compiled sharded update steps (SURVEY.md §2 comp. 5).
- ``mpit_tpu.parallel``  — trainers: sync allreduce DP (plus ZeRO-1
  sharded optimizer state and gradient accumulation), collective EASGD /
  Downpour, the host-async pserver/pclient fidelity mode
  (SURVEY.md §2 comps. 3, 4, 7), and the beyond-parity suite: sequence
  (ring or Ulysses), tensor (GSPMD), pipeline (GPipe/1F1B/interleaved),
  expert (top-k MoE), and the composed dp×tp×sp step.
- ``mpit_tpu.ops``       — pallas kernels (flash attention fwd+bwd,
  fused elastic update) and the sharded attention/MoE primitives.
- ``mpit_tpu.models``    — LeNet, VGG-small, AlexNet, ResNet-50, PTB
  LSTM (BASELINE.json configs 1–5), plus MLP and the transformer LM.
- ``mpit_tpu.data``      — dataset pipelines with deterministic synthetic
  fallbacks (no-network environments).
- ``mpit_tpu.utils``     — flat-parameter utilities (≡ Torch
  ``getParameters()``), config, logging, metrics, checkpointing.
"""

__version__ = "0.1.0"

from mpit_tpu.comm import (  # noqa: F401
    Topology,
    init,
    finalize,
    is_initialized,
    topology,
    rank,
    size,
    process_rank,
    process_count,
    allreduce,
    allgather,
    bcast,
    barrier,
    device_barrier,
    psum,
    pmean,
    pmax,
    pmin,
    reduce_scatter,
    SUM,
    PROD,
    MAX,
    MIN,
    AVG,
)
from mpit_tpu.utils.params import (  # noqa: F401
    flatten_params,
    unflatten_params,
    FlatParamSpec,
)
