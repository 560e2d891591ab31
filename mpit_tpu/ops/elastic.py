"""Fused EASGD elastic update as a pallas TPU kernel.

The exchange round's elementwise math (goptim.easgd_round, SURVEY.md §3(b-c)):

    new_x = x - α (x - c)            (client move toward center)
    new_c = c + α d                  (center move; d = psum of client diffs)

One kernel, three inputs, two outputs, one pass over HBM — the VPU does the
arithmetic while the bandwidth is the bound. Grid: 1-D over row-blocks of a
(rows, 128)-shaped view (lane dim fixed at 128, float32 sublane tiling;
/opt/skills/guides/pallas_guide.md). α is compile-time static (a config
constant), so it folds into the kernel.

`interpret=True` runs the same kernel on CPU (tests). The public wrapper
picks plain XLA elementwise ops off-TPU only when no kernel was asked for
(``use_pallas=None``); an explicit ``use_pallas=True`` runs the kernel —
compiled on TPU, interpreted on CPU — or raises.

Naming: "elastic" here is EASGD's elastic *force* — the update math.
Elastic *membership* (ranks joining/leaving/preempted mid-run) is
:mod:`mpit_tpu.parallel.elastic`, which shares nothing with this kernel
but the paper's adjective.

Measured (single v5e chip, 25M-element f32 operands, 2026-07): bit-exact
equality with the XLA path; XLA's own fusion was ~2.7x faster per call than
this kernel (grid/dispatch overhead dominates a pure-bandwidth op), which is
why ``use_pallas`` defaults to off everywhere — the kernel documents the
fusion floor and the pallas recipe, it is not the fast path today.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LANE = 128
BLOCK_ROWS = 512  # 512×128 f32 = 256 KiB per operand block in VMEM


def pallas_supported() -> bool:
    """True when the pallas TPU path can run natively here. A backend that
    fails to initialize raises — it must not read as "not a TPU"."""
    return jax.default_backend() == "tpu"


def pallas_interpret() -> bool:
    """``interpret=`` for a kernel that was asked for: compiled on TPU,
    interpreted on CPU (the tests), an error anywhere else."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"pallas TPU kernel requested on backend {backend!r}: it "
            "compiles for tpu and interprets on cpu only"
        )
    return backend == "cpu"


def _kernel(alpha, x_ref, c_ref, d_ref, newx_ref, newc_ref):
    x = x_ref[:]
    c = c_ref[:]
    newx_ref[:] = x - alpha * (x - c)
    newc_ref[:] = c + alpha * d_ref[:]


@functools.partial(jax.jit, static_argnames=("alpha", "interpret"))
def _elastic_pallas(x, c, d, alpha: float, interpret: bool):
    from jax.experimental import pallas as pl

    n = x.size
    block = BLOCK_ROWS * LANE
    padded = max(-(-n // block), 1) * block
    rows = padded // LANE

    def prep(a):
        a = a.reshape(-1)
        return jnp.pad(a, (0, padded - n)).reshape(rows, LANE)

    spec = pl.BlockSpec((BLOCK_ROWS, LANE), lambda i: (i, 0))
    out = jax.ShapeDtypeStruct((rows, LANE), x.dtype)
    new_x, new_c = pl.pallas_call(
        functools.partial(_kernel, alpha),
        grid=(rows // BLOCK_ROWS,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec],
        out_shape=[out, out],
        interpret=interpret,
    )(prep(x), prep(c), prep(d))
    return (
        new_x.reshape(-1)[:n].reshape(x.shape),
        new_c.reshape(-1)[:n].reshape(x.shape),
    )


def elastic_update(x, center, total_diff, alpha: float, use_pallas=None):
    """Fused elastic pair update; returns ``(new_x, new_center)``.

    Args:
      x, center, total_diff: same-shape arrays (any rank).
      alpha: elastic coupling (static).
      use_pallas: True = require the kernel (compiled on TPU, interpret
        mode on CPU, an error on any other backend), False = plain XLA,
        None = kernel on TPU, XLA elsewhere.
    """
    if use_pallas is None:
        use_pallas = pallas_supported()
    if use_pallas:
        return _elastic_pallas(
            jnp.asarray(x), jnp.asarray(center), jnp.asarray(total_diff),
            float(alpha), pallas_interpret(),
        )
    new_x = x - alpha * (x - center)
    new_c = center + alpha * total_diff
    return new_x, new_c
