"""Flash attention as a pallas TPU kernel (single-device sequence).

The within-device counterpart of ``ops/ring_attention.py``: the same
online-softmax recipe, but tiled into VMEM by a pallas kernel so the
(T, T) score matrix never round-trips HBM even on ONE device. XLA's
fusion keeps scores in registers for small T; for long sequences it
materializes (B, H, T, T) scores in HBM — this kernel caps that at a
(block_q, block_k) tile in VMEM.

Kernel structure (the canonical pallas flash shape,
/opt/skills/guides/pallas_guide.md):

- grid ``(B·H, T/block_q, T/block_k)`` — the k-block axis is innermost,
  so for each (head, q-block) the kernel visits k-blocks sequentially,
  carrying the online-softmax state (running max ``m``, normalizer
  ``l``, output accumulator) in VMEM scratch that persists across the
  innermost grid steps;
- scratch initializes at ``j == 0``, the output block writes once at
  the last ``j`` (revisiting one output block across sequential grid
  steps is the standard TPU accumulation pattern);
- causal masking uses GLOBAL positions from the block indices, and a
  fully-masked (block entirely above the diagonal) k-block skips its
  matmuls via ``pl.when``;
- scores/statistics accumulate in f32 regardless of input dtype (bf16
  inputs hit the MXU as bf16 — the recipe shared with ring attention).
  ``m``/``l`` live lane-broadcast in (block_q, 128) scratch (the TPU
  f32 tile's lane width).

The log-sum-exp residual follows the layout the official TPU kernels
use (``jax.experimental.pallas.ops.tpu.splash_attention``), because
Mosaic tiles the last two block dimensions as (8, 128): the forward
writes it lane-broadcast as ``(B·H, T, 128)`` and keeps lane 0; the dQ
kernel reads it as ``(B·H, 1, T)`` rows turned into a column, the
transposed dK/dV kernel as ``(B·H, 8, T)`` sublane-broadcast rows. A
``(1, block_q)`` block of a 2-D ``(B·H, T)`` array — the first version
of this file — is refused by the TPU lowering whenever ``B·H > 1``.

`interpret=True` runs the same kernel on CPU (the correctness tests).
The public wrapper picks plain XLA dense attention off-TPU only when no
kernel was asked for (``use_pallas=None``); once the kernel is selected
it runs — compiled on TPU, interpreted on CPU — or raises, and a ``T``
that does not tile is an error, never a silent dense pass. Default OFF
in the model (``attn_impl="xla"``): no timing against XLA's fused
attention exists yet (PERF.md), and the elastic-update kernel taught us
XLA's fusion can beat a pallas kernel (ops/elastic.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.elastic import pallas_interpret, pallas_supported
from mpit_tpu.ops.ring_attention import dense_attention

_NEG_INF = float("-inf")
_LANE = 128
_SUBLANE = 8


def _apply_causal(s, q_off, k_off, q_axis: int):
    """Mask score tile entries where k_pos > q_pos (global positions);
    ``q_axis`` names the tile dimension the query positions vary along
    (0 in the q-major kernels, 1 in the transposed dK/dV kernel). The
    ONE copy of the mask for forward and both backward kernels."""
    q_pos = q_off + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k_off + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1 - q_axis
    )
    return jnp.where(k_pos <= q_pos, s, _NEG_INF)


def _to2d(a):
    """(B, T, H, D) -> (B·H, T, D), the kernels' layout."""
    b, t, h, d = a.shape
    return a.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from2d(a, b: int, h: int, t: int, d: int):
    return a.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, block_q, block_k, n_k,
):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr[:], _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr[:])
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    # causal: a k-block strictly above the q-block's last row contributes
    # nothing — skip its matmuls entirely
    needed = (
        j * block_k <= i * block_q + block_q - 1 if causal else j >= 0
    )

    @pl.when(needed)
    def _update():
        q = q_ref[0]  # (block_q, D)
        k = k_ref[0]  # (block_k, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if causal:
            s = _apply_causal(s, i * block_q, j * block_k, 0)
        m_prev = m_scr[:][:, :1]  # (block_q, 1) of the broadcast store
        l_prev = l_scr[:][:, :1]
        block_max = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        # a still-fully-masked row has m = -inf; exp(s - m) would be nan —
        # substitute 0, every term it touches is exp(-inf - 0) = 0
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - safe_m)
        corr = jnp.where(
            jnp.isneginf(m_prev), 0.0, jnp.exp(m_prev - safe_m)
        )
        l_new = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc_scr[:] * corr + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc_new

    @pl.when(j == n_k - 1)
    def _finalize():
        l = l_scr[:][:, :1]
        out = acc_scr[:] / jnp.maximum(l, jnp.finfo(jnp.float32).tiny)
        o_ref[0] = out.astype(o_ref.dtype)
        m = m_scr[:][:, :1]
        # log-sum-exp per query row: P_ij = exp(s_ij - lse_i) in the
        # backward. A row with no unmasked key gets +inf (P row = 0).
        lse = jnp.where(
            l > 0.0, jnp.where(jnp.isneginf(m), 0.0, m) + jnp.log(
                jnp.maximum(l, jnp.finfo(jnp.float32).tiny)
            ),
            jnp.inf,
        )
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dq_ref, acc_scr,
    *, scale, causal, block_q, block_k, n_k,
):
    """dQ_i = scale · Σ_j dS_ij K_j with dS = P ∘ (dP − D); grid
    (B·H, q-block, k-block-innermost), accumulating in VMEM scratch."""
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    needed = (
        j * block_k <= i * block_q + block_q - 1 if causal else j >= 0
    )

    @pl.when(needed)
    def _update():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0][:, None]  # (1, bq) row block -> (bq, 1)
        dd = dd_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = _apply_causal(s, i * block_q, j * block_k, 0)
        p = jnp.exp(s - lse)  # rows with lse=+inf go to 0
        dp = jax.lax.dot_general(
            do, v.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd)
        acc_scr[:] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(j == n_k - 1)
    def _finalize():
        dq_ref[0] = acc_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, block_q, block_k, n_q,
):
    """dK_j = scale · Σ_i dSᵀ_ji Q_i and dV_j = Σ_i Pᵀ_ji dO_i; grid
    (B·H, k-block, q-block-innermost)."""
    j = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr[:])
        dv_scr[:] = jnp.zeros_like(dv_scr[:])

    # causal: a q-block entirely ABOVE this k-block contributes nothing
    needed = (
        i * block_q + block_q - 1 >= j * block_k if causal else i >= 0
    )

    @pl.when(needed)
    def _update():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0][:1, :]  # (8, bq) sublane-broadcast -> (1, bq)
        dd = dd_ref[0][:1, :]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bk, bq) = sᵀ
        if causal:
            st = _apply_causal(st, i * block_q, j * block_k, 1)
        pt = jnp.exp(st - lse)
        dv_scr[:] += jax.lax.dot_general(
            pt, do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v.astype(jnp.float32), do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dst = pt * (dpt - dd)
        dk_scr[:] += jax.lax.dot_general(
            dst, q.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale

    @pl.when(i == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def _flash_pallas_bwd(q, k, v, out, lse, ct, causal, block_q, block_k,
                      interpret):
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    q2, k2, v2 = _to2d(q), _to2d(k), _to2d(v)
    do2 = _to2d(ct)
    o2 = _to2d(out)
    # D_i = Σ_d dO_id · O_id — cheap elementwise+reduce, XLA's job
    dd = jnp.sum(
        do2.astype(jnp.float32) * o2.astype(jnp.float32), -1
    )  # (BH, T)
    n_q, n_k = t // block_q, t // block_k

    q_spec = lambda ax: pl.BlockSpec(
        (1, block_q, d), lambda bh, a, b_: (bh, a if ax == 1 else b_, 0)
    )
    # per-row residuals as lane-major rows: one row for the q-major dQ
    # kernel, sublane-broadcast for the transposed dK/dV kernel
    row_spec = lambda ax, rows: pl.BlockSpec(
        (1, rows, block_q), lambda bh, a, b_: (bh, 0, a if ax == 1 else b_)
    )
    rows_of = lambda a, rows: jnp.broadcast_to(
        a[:, None, :], (b * h, rows, t)
    )
    kv_spec = lambda ax: pl.BlockSpec(
        (1, block_k, d), lambda bh, a, b_: (bh, a if ax == 1 else b_, 0)
    )

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, n_k=n_k,
        ),
        grid=(b * h, n_q, n_k),
        in_specs=[
            q_spec(1), kv_spec(2), kv_spec(2), q_spec(1),
            row_spec(1, 1), row_spec(1, 1),
        ],
        out_specs=q_spec(1),
        out_shape=jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q2, k2, v2, do2, rows_of(lse, 1), rows_of(dd, 1))

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, n_q=n_q,
        ),
        grid=(b * h, n_k, n_q),
        in_specs=[
            q_spec(2), kv_spec(1), kv_spec(1), q_spec(2),
            row_spec(2, _SUBLANE), row_spec(2, _SUBLANE),
        ],
        out_specs=[kv_spec(1), kv_spec(1)],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), k.dtype),
            jax.ShapeDtypeStruct((b * h, t, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q2, k2, v2, do2, rows_of(lse, _SUBLANE), rows_of(dd, _SUBLANE))

    return (
        _from2d(dq, b, h, t, d),
        _from2d(dk, b, h, t, d),
        _from2d(dv, b, h, t, d),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, block_q, block_k, interpret):
    """Differentiable flash attention: pallas kernels both directions.

    ``pallas_call`` has no automatic VJP; the backward here is the
    standard FlashAttention recipe — recompute P from the saved
    log-sum-exp, never materializing more than a (block, block) score
    tile: a dQ kernel (q-blocks outer, k-blocks inner) and a fused
    dK/dV kernel (k-blocks outer, q-blocks inner), with the D = rowsum
    (dO ∘ O) vector computed by XLA outside.
    """
    return _flash_pallas(q, k, v, causal, block_q, block_k, interpret)[0]


def _flash_fwd(q, k, v, causal, block_q, block_k, interpret):
    out, lse = _flash_pallas(q, k, v, causal, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, block_q, block_k, interpret, res, ct):
    q, k, v, out, lse = res
    return _flash_pallas_bwd(
        q, k, v, out, lse, ct, causal, block_q, block_k, interpret
    )


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "block_q", "block_k", "interpret"),
)
def _flash_pallas(q, k, v, causal, block_q, block_k, interpret):
    b, t, h, d = q.shape
    scale = 1.0 / (d ** 0.5)
    q2, k2, v2 = _to2d(q), _to2d(k), _to2d(v)
    n_q, n_k = t // block_q, t // block_k

    q_spec = pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda bh, i, j: (bh, j, 0))
    out, lse = pl.pallas_call(
        functools.partial(
            _kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, n_k=n_k,
        ),
        grid=(b * h, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, _LANE), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, t, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, t, _LANE), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),  # running max m
            pltpu.VMEM((block_q, _LANE), jnp.float32),  # normalizer l
            pltpu.VMEM((block_q, d), jnp.float32),      # output acc
        ],
        interpret=interpret,
    )(q2, k2, v2)
    return _from2d(out, b, h, t, d), lse[..., 0]


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int = 128,
    block_k: int = 128,
    use_pallas=None,
) -> jax.Array:
    """Tiled exact attention, ``(B, T, H, D) -> (B, T, H, D)``.

    ``use_pallas``: True = require the kernel (compiled on TPU, interpret
    mode on CPU), False = XLA dense attention, None = kernel on TPU, XLA
    elsewhere.

    Fully trainable: the custom VJP runs the standard FlashAttention
    backward as pallas kernels too (P recomputed from the saved
    log-sum-exp; dQ and fused dK/dV passes), so no (T, T) score matrix
    materializes in either direction.

    Blocks clamp to ``T`` for short sequences. Once the kernel is
    selected, a ``T`` the blocks do not tile raises ``ValueError`` — it
    never becomes a dense pass: a block must divide ``T`` and be
    sublane-aligned (a multiple of 8), and compiled for the chip it must
    also be lane-aligned (a multiple of 128) or span ``T``, because the
    backward reads per-row residuals as ``(…, block_q)`` lane-major rows.
    """
    if use_pallas is None:
        use_pallas = pallas_supported()
    if not use_pallas:
        return dense_attention(q, k, v, causal=causal)
    interpret = pallas_interpret()
    t = q.shape[1]
    block_q = min(block_q, t)
    block_k = min(block_k, t)
    for name, blk in (("block_q", block_q), ("block_k", block_k)):
        if t % blk or blk % 8:
            raise ValueError(
                f"flash_attention: T={t} does not tile with {name}={blk} "
                f"(q shape {q.shape}): the block must divide T and be a "
                "multiple of 8"
            )
        if not interpret and blk % _LANE and blk != t:
            raise ValueError(
                f"flash_attention: {name}={blk} at T={t} (q shape "
                f"{q.shape}) cannot compile for TPU: a block must be a "
                f"multiple of {_LANE} or span T"
            )
    return _flash(q, k, v, causal, block_q, block_k, interpret)
