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
- the tiles come from the shape (``choose_blocks``), for each of the
  three kernels: a grid step has a fixed cost and every fold of the
  online softmax works on one-lane columns, so at T = 1,024 the
  128 x 128 tiles this file began with took three times the time of the
  chosen ones (PERF.md section 6, PR 26, has the sweep);
- causal masking uses GLOBAL positions from the block indices. A
  fully-masked tile (entirely above the diagonal) skips its arithmetic
  via ``pl.when`` AND its fetch: the inner axis' index maps clamp to
  the nearest live block, so a skipped step names the block already
  resident and the pipeline issues no copy;
- every product reaches the MXU in the inputs' dtype (``p``, ``dS`` and
  ``dO`` are cast to it, as jax's own TPU kernel does) and leaves it as
  f32; scores, running max, normalizer, log-sum-exp, ``D`` and every
  accumulator stay f32. With f32 inputs nothing is cast.
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

Residuals are named. The backward kernels read ``(q, k, v, out, lse)``;
under a ``jax.checkpoint`` with no policy all five are recomputed on the
way back, and recomputing ``out`` and ``lse`` is the forward kernel, the
dearest of the three for what it does, run a second time (34.8 ms of the
Laguna cell's 467 ms step at 8,192 tokens, PERF.md section 6, PR 28). So
the forward rule passes ``out`` and ``lse`` through ``checkpoint_name``
(``flash_out``, ``flash_lse``) and the public wrapper does the same for
``q``, ``k`` and ``v`` as they enter the kernel (``flash_qkv``), as jax's
own TPU kernel does under ``residual_checkpoint_name``: a caller's
``save_only_these_names`` policy (``models/transformer._REMAT_KEEPS``)
then keeps them, the recomputed forward call is dead code and so is what
only fed it. Outside a ``jax.checkpoint`` a name lowers to nothing.

In a trace the three kernels run under the ``profiling.scope``s
``flash_fwd``, ``flash_dq`` and ``flash_dkv``, and the transposes into
and out of the kernels' layout with the ``D`` reduction under
``flash_layout``.

`interpret=True` runs the same kernel on CPU (the correctness tests).
The public wrapper picks plain XLA dense attention off-TPU only when no
kernel was asked for (``use_pallas=None``); once the kernel is selected
it runs — compiled on TPU, interpreted on CPU — or raises, and a ``T``
that does not tile is an error, never a silent dense pass. Default OFF
in the model (``attn_impl="xla"``); PERF.md section 6 (PR 26) has its
timing against XLA's dense attention at GPT-2-small's shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mpit_tpu.ops.elastic import pallas_interpret, pallas_supported
from mpit_tpu.ops.ring_attention import dense_attention
from mpit_tpu.utils import profiling

_NEG_INF = float("-inf")
_LANE = 128
_SUBLANE = 8
#: what a kernel may take of VMEM (the chip's compiler is told so), and
#: the part of it the tile chooser's estimate has to stay under
_VMEM_LIMIT = 48 * 1024 * 1024
_VMEM_BUDGET = _VMEM_LIMIT * 2 // 3
#: the largest tile side the chooser takes for each kernel, and how many
#: f32 temporaries of the score tile's size the kernel holds (PERF.md
#: section 6, PR 26: the sweep at T = 1,024, D = 64). The forward pays
#: for every fold of the online softmax in per-row work on one-lane
#: columns, as dear as a 128-lane tile's, so it wants one k-step where T
#: allows; the backward kernels only accumulate, and there the causal
#: skip of 512-tiles (3 of 4 live) beats the smaller step count.
_KERNELS = {"fwd": (1024, 3), "dq": (512, 5), "dkv": (512, 5)}
#: from this length on the backward kernels take the forward's cap too:
#: with eight tiles a side or more a 1,024-tile skips nearly as much of the
#: causal mask as a 512-tile (9 of 16 tiles live against 17 of 32) in a
#: quarter of the steps (PERF.md section 6, PR 27: T = 8,192, D = 128)
_LONG_T = 8192
_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=_VMEM_LIMIT,
)


def vmem_estimate(
    kernel: str, block_q: int, block_k: int, d: int, itemsize: int
) -> int:
    """An upper estimate of the VMEM ``kernel`` ("fwd", "dq" or "dkv")
    holds at these tiles: at most four double-buffered tiles a side, in
    and out; the per-row residuals and statistics, lane-broadcast at
    worst; two f32 accumulators; and the kernel's f32 temporaries of the
    score tile's size (scores, probabilities and their low-precision
    copy; dP and dS in the backward)."""
    tiles = 2 * 4 * (block_q + block_k) * d * itemsize
    rows = 4 * block_q * _LANE * 4
    scratch = 2 * max(block_q, block_k) * d * 4
    return tiles + rows + scratch + _KERNELS[kernel][1] * block_q * block_k * 4


def choose_blocks(
    t: int, d: int, dtype, window: int | None = None
) -> tuple[tuple[int, int], ...]:
    """``(block_q, block_k)`` for the forward, dQ and dK/dV kernels, in
    that order, at sequence length ``t``: for each the largest square
    tile up to its cap that divides ``t``, is a multiple of 128 (or
    spans ``t``, where ``t`` has no such divisor) and keeps
    ``vmem_estimate`` under the budget. Under a ``window`` no tile is
    larger than the window (a larger one is mostly masked; at window 512
    a 512 x 512 tile won the sweep for all three kernels, PERF.md section
    6, PR 27)."""
    itemsize = jnp.dtype(dtype).itemsize

    def side(kernel):
        cap = _KERNELS["fwd" if t >= _LONG_T else kernel][0]
        cap = min(t, cap)
        if window is not None:
            cap = min(cap, max(_LANE, window))
        fits = [
            b for b in range(_LANE, cap + 1, _LANE)
            if t % b == 0
            and vmem_estimate(kernel, b, b, d, itemsize) <= _VMEM_BUDGET
        ]
        return max(fits) if fits else t

    return tuple((side(kernel),) * 2 for kernel in _KERNELS)


def _last_live_k(i, block_q: int, block_k: int):
    """Under the causal mask, the last k-block q-block ``i`` sees."""
    return (i * block_q + block_q - 1) // block_k


def _first_live_q(j, block_q: int, block_k: int):
    """Under the causal mask, the first q-block that sees k-block ``j``."""
    return (j * block_k) // block_q


def _first_live_k(i, block_q: int, block_k: int, window: int):
    """Under a window, the k-block of the earliest key that q-block
    ``i``'s first row sees (key ``q - window + 1``)."""
    return jnp.maximum(i * block_q - window + 1, 0) // block_k


def _last_live_q(j, block_q: int, block_k: int, window: int, n_q: int):
    """Under a window, the q-block of the latest query that sees k-block
    ``j``'s last key (query ``k + window - 1``)."""
    return jnp.minimum(
        (j * block_k + block_k + window - 2) // block_q, n_q - 1
    )


def window_steps(t: int, block_q: int, block_k: int, window: int):
    """Inner grid extents under a window: the most k-blocks any q-block
    sees and the most q-blocks that see any k-block. The windowed grids
    walk only these, from each outer block's first live inner block, so
    a tile outside the window costs neither arithmetic, fetch nor grid
    step."""
    n_q, n_k = t // block_q, t // block_k
    k_steps = max(
        (i * block_q + block_q - 1) // block_k
        - max(i * block_q - window + 1, 0) // block_k + 1
        for i in range(n_q)
    )
    q_steps = max(
        min((j * block_k + block_k + window - 2) // block_q, n_q - 1)
        - (j * block_k) // block_q + 1
        for j in range(n_k)
    )
    return k_steps, q_steps


def _k_block(i, j, block_q: int, block_k: int, window):
    """The k-block step ``j`` of q-block ``i`` stands for: ``j`` itself,
    or under a window the ``j``-th from the first live one."""
    if window is None:
        return j
    return _first_live_k(i, block_q, block_k, window) + j


def _q_block(j, i, block_q: int, block_k: int, window):
    """The mirror for the q-innermost dK/dV grid."""
    if window is None:
        return i
    return _first_live_q(j, block_q, block_k) + i


def _inner_k(i, j, causal: bool, block_q: int, block_k: int, window=None):
    """The k-block to hold at step ``(i, j)`` of a k-innermost grid: the
    step's own, or under the causal mask the last live one, so that a
    masked step names the resident block and nothing is fetched for it."""
    if not causal:
        return j
    return jnp.minimum(
        _k_block(i, j, block_q, block_k, window),
        _last_live_k(i, block_q, block_k),
    )


def _inner_q(j, i, causal: bool, block_q: int, block_k: int, window=None,
             n_q: int = 0):
    """The mirror for the q-innermost dK/dV grid. Without a window its
    masked steps come first and name the first live q-block, which then
    is resident; under a window they come last and name the last."""
    if not causal:
        return i
    if window is None:
        return jnp.maximum(i, _first_live_q(j, block_q, block_k))
    return jnp.minimum(
        _q_block(j, i, block_q, block_k, window),
        _last_live_q(j, block_q, block_k, window, n_q),
    )


def _apply_causal(s, q_off, k_off, q_axis: int, window=None):
    """Mask score tile entries where k_pos > q_pos (global positions)
    and, under a window, where k_pos <= q_pos - window; ``q_axis`` names
    the tile dimension the query positions vary along (0 in the q-major
    kernels, 1 in the transposed dK/dV kernel). The ONE copy of the mask
    for forward and both backward kernels."""
    ahead = jax.lax.broadcasted_iota(
        jnp.int32, s.shape, q_axis
    ) - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    seen = ahead >= k_off - q_off
    if window is not None:
        seen &= ahead < k_off - q_off + window
    return jnp.where(seen, s, _NEG_INF)


def _when_live(live, update):
    """Run a tile's arithmetic: always, or under the causal mask only
    where the tile has an unmasked entry."""
    if live is None:
        update()
    else:
        pl.when(live)(update)


def _to2d(a):
    """(B, T, H, D) -> (B·H, T, D), the kernels' layout."""
    b, t, h, d = a.shape
    return a.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _from2d(a, b: int, h: int, t: int, d: int):
    return a.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, block_q, block_k, n_k, window=None,
):
    i = pl.program_id(1)
    j = pl.program_id(2)
    kb = _k_block(i, j, block_q, block_k, window)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr[:], _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr[:])
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    def _update():
        q = q_ref[0]  # (block_q, D)
        k = k_ref[0]  # (block_k, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (block_q, block_k)
        if causal:
            s = _apply_causal(s, i * block_q, kb * block_k, 0, window)
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
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
        acc_scr[:] = acc_new

    # causal: a k-block strictly above the q-block's last row contributes
    # nothing — skip its matmuls entirely
    _when_live(
        kb <= _last_live_k(i, block_q, block_k) if causal else None, _update
    )

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
    *, scale, causal, block_q, block_k, n_k, window=None,
):
    """dQ_i = scale · Σ_j dS_ij K_j with dS = P ∘ (dP − D); grid
    (B·H, q-block, k-block-innermost), accumulating in VMEM scratch."""
    i = pl.program_id(1)
    j = pl.program_id(2)
    kb = _k_block(i, j, block_q, block_k, window)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr[:])

    def _update():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, 0][:, None]  # (1, bq) row block -> (bq, 1)
        dd = dd_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        if causal:
            s = _apply_causal(s, i * block_q, kb * block_k, 0, window)
        p = jnp.exp(s - lse)  # rows with lse=+inf go to 0
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - dd)
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _when_live(
        kb <= _last_live_k(i, block_q, block_k) if causal else None, _update
    )

    @pl.when(j == n_k - 1)
    def _finalize():
        dq_ref[0] = (acc_scr[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, dd_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, scale, causal, block_q, block_k, n_q, window=None,
    group=1, t_blocks=0,
):
    """dK_j = scale · Σ_i dSᵀ_ji Q_i and dV_j = Σ_i Pᵀ_ji dO_i; grid
    (B·H_kv, k-block, q-block-innermost). ``n_q`` is the q-steps one
    query head takes; with grouped KV heads the innermost axis walks
    them once for each of the ``group`` query heads that read this KV
    head, and the sums run over all of them in the same scratch."""
    j = pl.program_id(1)
    step = pl.program_id(2)
    i = step if group == 1 else step % n_q
    qb = _q_block(j, i, block_q, block_k, window)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr[:])
        dv_scr[:] = jnp.zeros_like(dv_scr[:])

    def _update():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:1, :]  # (8, bq) sublane-broadcast -> (1, bq)
        dd = dd_ref[0][:1, :]
        st = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale  # (bk, bq) = sᵀ
        if causal:
            st = _apply_causal(st, qb * block_q, j * block_k, 1, window)
        pt = jnp.exp(st - lse)
        dv_scr[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dpt = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dst = pt * (dpt - dd)
        dk_scr[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    # causal: a q-block entirely ABOVE this k-block contributes nothing;
    # under a window neither does one wholly past it
    if not causal:
        live = None
    elif window is None:
        live = i >= _first_live_q(j, block_q, block_k)
    else:
        live = qb <= _last_live_q(j, block_q, block_k, window, t_blocks)
    _when_live(live, _update)

    @pl.when(step == group * n_q - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _kv_head(b, group: int):
    """Row of the ``(B·H_kv, T, D)`` arrays that query row ``b`` of
    ``(B·H, T, D)`` reads: query head ``h`` reads KV head
    ``h // group``, and ``H = group · H_kv`` makes that ``b // group``."""
    return b if group == 1 else b // group


def _k_inner_specs(d, causal, block_q, block_k, window=None, group=1):
    """Q and K/V block specs of a ``(B·H, q-block, k-block)`` grid."""
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec(
        (1, block_k, d),
        lambda b, i, j: (
            _kv_head(b, group),
            _inner_k(i, j, causal, block_q, block_k, window), 0,
        ),
    )
    return q_spec, kv_spec


def _k_steps(t, block_q, block_k, window):
    return t // block_k if window is None else window_steps(
        t, block_q, block_k, window)[0]


def _fwd_call(q2, k2, v2, causal, block_q, block_k, interpret, window=None):
    """Forward kernel on the (B·H, T, D) layout -> (out, lane-broadcast
    log-sum-exp). ``k2``/``v2`` may hold fewer (grouped) heads."""
    bh, t, d = q2.shape
    group = bh // k2.shape[0]
    q_spec, kv_spec = _k_inner_specs(d, causal, block_q, block_k, window, group)
    n_k = _k_steps(t, block_q, block_k, window)
    scope = "flash_fwd" if window is None else "flash_window_fwd"
    with profiling.scope(scope):
        return pl.pallas_call(
            functools.partial(
                _kernel, scale=1.0 / (d ** 0.5), causal=causal,
                block_q=block_q, block_k=block_k, n_k=n_k, window=window,
            ),
            grid=(bh, t // block_q, n_k),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=[
                q_spec,
                pl.BlockSpec((1, block_q, _LANE), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, t, d), q2.dtype),
                jax.ShapeDtypeStruct((bh, t, _LANE), jnp.float32),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _LANE), jnp.float32),  # running max m
                pltpu.VMEM((block_q, _LANE), jnp.float32),  # normalizer l
                pltpu.VMEM((block_q, d), jnp.float32),      # output acc
            ],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(q2, k2, v2)


def _rows(a, rows: int):
    """(B·H, T) per-row residual -> (B·H, rows, T) lane-major rows."""
    return jnp.broadcast_to(a[:, None, :], (a.shape[0], rows, a.shape[1]))


def _dq_call(q2, k2, v2, do2, lse, dd, causal, block_q, block_k, interpret,
             window=None):
    bh, t, d = q2.shape
    group = bh // k2.shape[0]
    q_spec, kv_spec = _k_inner_specs(d, causal, block_q, block_k, window, group)
    n_k = _k_steps(t, block_q, block_k, window)
    # per-row residuals as one lane-major row for the q-major kernel
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    scope = "flash_dq" if window is None else "flash_window_dq"
    with profiling.scope(scope):
        return pl.pallas_call(
            functools.partial(
                _dq_kernel, scale=1.0 / (d ** 0.5), causal=causal,
                block_q=block_q, block_k=block_k, n_k=n_k, window=window,
            ),
            grid=(bh, t // block_q, n_k),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=q_spec,
            out_shape=jax.ShapeDtypeStruct((bh, t, d), q2.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(q2, k2, v2, do2, _rows(lse, 1), _rows(dd, 1))


def _dkv_call(q2, k2, v2, do2, lse, dd, causal, block_q, block_k, interpret,
              window=None):
    bh, t, d = q2.shape
    group = bh // k2.shape[0]
    n_q = t // block_q if window is None else window_steps(
        t, block_q, block_k, window)[1]
    # step -> (query head of the group, q-step of that head)
    head = lambda b, s: b if group == 1 else b * group + s // n_q
    inner = lambda j, s: _inner_q(
        j, s if group == 1 else s % n_q, causal, block_q, block_k, window,
        t // block_q)
    q_spec = pl.BlockSpec(
        (1, block_q, d), lambda b, j, s: (head(b, s), inner(j, s), 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b, j, s: (b, j, 0))
    # sublane-broadcast rows for the transposed kernel
    row_spec = pl.BlockSpec(
        (1, _SUBLANE, block_q), lambda b, j, s: (head(b, s), 0, inner(j, s))
    )
    scope = "flash_dkv" if window is None else "flash_window_dkv"
    with profiling.scope(scope):
        return pl.pallas_call(
            functools.partial(
                _dkv_kernel, scale=1.0 / (d ** 0.5), causal=causal,
                block_q=block_q, block_k=block_k, n_q=n_q, window=window,
                group=group, t_blocks=t // block_q,
            ),
            grid=(k2.shape[0], t // block_k, group * n_q),
            in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
            out_specs=[kv_spec, kv_spec],
            out_shape=[
                jax.ShapeDtypeStruct(k2.shape, k2.dtype),
                jax.ShapeDtypeStruct(v2.shape, v2.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
        )(q2, k2, v2, do2, _rows(lse, _SUBLANE), _rows(dd, _SUBLANE))


@functools.partial(
    jax.jit, static_argnames=("causal", "blocks", "interpret", "window")
)
def _flash_pallas_bwd(q, k, v, out, lse, ct, causal, blocks, interpret,
                      window=None):
    b, t, h, d = q.shape
    with profiling.scope("flash_layout"):
        q2, k2, v2, do2, o2 = (_to2d(a) for a in (q, k, v, ct, out))
        # D_i = Σ_d dO_id · O_id — cheap elementwise+reduce, XLA's job
        dd = jnp.sum(
            do2.astype(jnp.float32) * o2.astype(jnp.float32), -1
        )  # (BH, T)
    _, dq_blocks, dkv_blocks = blocks
    dq = _dq_call(
        q2, k2, v2, do2, lse, dd, causal, *dq_blocks, interpret, window)
    dk, dv = _dkv_call(
        q2, k2, v2, do2, lse, dd, causal, *dkv_blocks, interpret, window
    )
    with profiling.scope("flash_layout"):
        return (_from2d(dq, b, h, t, d),
                *(_from2d(a, b, k.shape[2], t, d) for a in (dk, dv)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, blocks, interpret, window=None):
    """Differentiable flash attention: pallas kernels both directions.
    ``blocks`` holds the forward, dQ and dK/dV kernels' tiles.

    ``pallas_call`` has no automatic VJP; the backward here is the
    standard FlashAttention recipe — recompute P from the saved
    log-sum-exp, never materializing more than a (block, block) score
    tile: a dQ kernel (q-blocks outer, k-blocks inner) and a fused
    dK/dV kernel (k-blocks outer, q-blocks inner), with the D = rowsum
    (dO ∘ O) vector computed by XLA outside.
    """
    return _flash_pallas(q, k, v, causal, blocks, interpret, window)[0]


def _flash_fwd(q, k, v, causal, blocks, interpret, window=None):
    out, lse = _flash_pallas(q, k, v, causal, blocks, interpret, window)
    # named outside the jitted call, where a jax.checkpoint round the
    # caller sees them (module text, "Residuals are named")
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, blocks, interpret, window, res, ct):
    q, k, v, out, lse = res
    return _flash_pallas_bwd(
        q, k, v, out, lse, ct, causal, blocks, interpret, window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(
    jax.jit, static_argnames=("causal", "blocks", "interpret", "window")
)
def _flash_pallas(q, k, v, causal, blocks, interpret, window=None):
    b, t, h, d = q.shape
    with profiling.scope("flash_layout"):
        q2, k2, v2 = _to2d(q), _to2d(k), _to2d(v)
    out, lse = _fwd_call(q2, k2, v2, causal, *blocks[0], interpret, window)
    with profiling.scope("flash_layout"):
        return _from2d(out, b, h, t, d), lse[..., 0]


def masked_dense_attention(q, k, v, window=None):
    """Causal dense attention with grouped KV heads and an optional
    window, f32 scores: the XLA branch beside the kernels for shapes
    :func:`dense_attention` does not take (it materializes the
    ``(B, H, T, T)`` scores, so it is for small ``T``)."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) / (d ** 0.5)
    ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    seen = ahead >= 0
    if window is not None:
        seen &= ahead < window
    p = jax.nn.softmax(jnp.where(seen, s, _NEG_INF), axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    block_q: int | None = None,
    block_k: int | None = None,
    use_pallas=None,
    window: int | None = None,
) -> jax.Array:
    """Tiled exact attention, ``(B, T, H, D) -> (B, T, H, D)``.

    ``use_pallas``: True = require the kernel (compiled on TPU, interpret
    mode on CPU), False = XLA dense attention, None = kernel on TPU, XLA
    elsewhere.

    ``k`` and ``v`` may hold fewer heads than ``q`` (grouped KV heads,
    ``H`` a multiple of ``H_kv``): query head ``h`` reads KV head
    ``h // (H / H_kv)`` through the kernels' index maps, so nothing is
    repeated in HBM, and dK/dV are summed over the group in the dK/dV
    kernel's scratch. ``window`` (causal only): key ``j`` is visible to
    query ``i`` iff ``i - window < j <= i``; tiles outside the window
    are not in the grid at all. ``window=None`` and equal head counts
    give the kernels as they were.

    Fully trainable: the custom VJP runs the standard FlashAttention
    backward as pallas kernels too (P recomputed from the saved
    log-sum-exp; dQ and fused dK/dV passes), so no (T, T) score matrix
    materializes in either direction.

    ``block_q``/``block_k``: ``None`` = chosen from ``(T, D, dtype,
    window)`` by ``choose_blocks``, for each of the three kernels; a
    given one wins for all three and clamps to ``T``. Once the kernel is
    selected, a ``T`` the blocks do not tile raises ``ValueError`` — it
    never becomes a dense pass: a block must divide ``T`` and be
    sublane-aligned (a multiple of 8), and compiled for the chip it must
    also be lane-aligned (a multiple of 128) or span ``T``, because the
    backward reads per-row residuals as ``(…, block_q)`` lane-major rows.
    """
    if window is not None and not causal:
        raise ValueError("flash_attention: a window needs causal=True")
    if q.shape[2] % k.shape[2] or k.shape != v.shape:
        raise ValueError(
            f"flash_attention: {q.shape[2]} query heads over KV of shape "
            f"{k.shape} / {v.shape}"
        )
    if use_pallas is None:
        use_pallas = pallas_supported()
    if not use_pallas:
        if window is None and q.shape[2] == k.shape[2]:
            return dense_attention(q, k, v, causal=causal)
        return masked_dense_attention(q, k, v, window)
    interpret = pallas_interpret()
    t, d = q.shape[1], q.shape[3]
    blocks = tuple(
        (bq if block_q is None else min(block_q, t),
         bk if block_k is None else min(block_k, t))
        for bq, bk in choose_blocks(t, d, q.dtype, window)
    )
    for name, blk in (
        pair for tiles in blocks for pair in zip(("block_q", "block_k"), tiles)
    ):
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
    q, k, v = (checkpoint_name(a, "flash_qkv") for a in (q, k, v))
    return _flash(q, k, v, causal, blocks, interpret, window)
