"""quant — the shared scalar quantization kernels, host and XLA paths.

One contract, two execution paths. The PS wire path
(:mod:`mpit_tpu.transport.wire`) quantizes numpy buffers on the host
before framing; the collective path (:mod:`mpit_tpu.comm.collectives`)
quantizes inside a jit'd ``shard_map`` program so the bytes that cross
the ICI/DCN links are the quantized codes, not float32. Both paths MUST
produce bit-identical codes and scales for the same input — the error-
feedback math (docs/WIRE.md) treats ``dequantize(quantize(x))`` as one
deterministic function, and a host/device disagreement would make the
residual wrong by exactly the disagreement. The equivalence is pinned in
``tests/test_wire.py`` (numpy-vs-jnp bit-equality for both modes).

Kernels (EQuARX-style, PAPERS.md arXiv:2506.17615):

- ``bf16``: round-to-nearest-even high halves of the float32 bits —
  pure bit arithmetic, scale-free, 2x byte drop. Relative error at most
  2^-8 for magnitudes up to bfloat16's largest finite value
  (``BF16_MAX``, 3.3895314e38) and on to half a step past it; from
  there (``BF16_MAX + 2^119``, 3.3961775e38) a finite float32 is no
  nearer to that value than to 2^128 and rounds to infinity, as IEEE 754
  says and as ``astype(jnp.bfloat16)`` does. A NaN stays a NaN;
- ``int8``: symmetric per-block absmax scaling, codes in [-127, 127],
  ``scale = absmax / 127`` computed in float32 on BOTH paths (a float64
  host division would double-round against XLA's f32), 4x byte drop.
  The scale is held inside ``[_INT8_SCALE_MIN, _INT8_SCALE_MAX]``, which
  only the two ends of the float32 range reach: where the quotient
  rounds up so far that its 127-fold overflows (absmax within a few ulps
  of float32's largest) it is one ulp less, so a finite input never
  reconstructs to inf; where it is subnormal, or underflows to zero, it
  is the smallest normal float32, so no block divides by zero and a
  backend that flushes subnormals computes the same scale from a normal
  absmax (a block of nothing but subnormals it reads as all zero: scale
  1, codes 0). The half-step bound ``|x - deq(q(x))| <= scale / 2``
  holds at both ends.

This module imports numpy only at module scope; jax is imported lazily
inside the jnp kernels so the host wire path (and the stdlib-only
reader tools that sit behind it) never pays a jax import.
"""

from __future__ import annotations

import dataclasses
import sys

import numpy as np

_F32_SIZE = 4

QUANT_MODES = ("off", "bf16", "int8")

# on-wire bytes per quantized element (raw float32 = 4)
MODE_ITEMSIZE = {"off": 4, "bf16": 2, "int8": 1}

# bfloat16's largest finite value: round-to-nearest-even carries a
# magnitude half a step (2^119) or more above it to infinity
BF16_MAX = np.float32(3.3895314e38)

# the largest int8 scale whose 127-fold is finite in float32:
# FLT_MAX / 127 rounds up and 127 times it overflows; one ulp less does not
_INT8_SCALE_MAX = np.nextafter(
    np.finfo(np.float32).max / np.float32(127.0), np.float32(0)
)
# the smallest: a subnormal absmax / 127 is subnormal or zero (XLA flushes
# it to zero on every backend), and a zero scale divides by zero
_INT8_SCALE_MIN = np.finfo(np.float32).tiny


def _bf16_codes(xp, a, u):
    """bfloat16 codes (still 32 bits wide) of float32 ``a`` with bits
    ``u``, on either face (``xp`` is numpy or jax.numpy):
    round-to-nearest-even on the dropped mantissa half; the + carries
    into the exponent correctly for halfway cases. A NaN keeps its high
    half with the quiet bit set: the carry would take its payload through
    the sign bit to a zero, or leave a payload that sits in the low half
    as an infinity."""
    return xp.where(
        xp.isnan(a),
        (u >> 16) | 0x0040,
        (u + 0x7FFF + ((u >> 16) & 1)) >> 16,
    )


def _int8_scale(xp, amax):
    """The int8 scale of a block (or of each row) whose finite absmax is
    ``amax``, on either face. An f32 division, not float64-then-cast:
    both faces divide in f32 and must agree to the bit. All-zero block:
    the scale is moot, pick 1."""
    return xp.where(
        amax > 0,
        xp.clip(
            amax / xp.float32(127.0), _INT8_SCALE_MIN, _INT8_SCALE_MAX
        ),
        xp.float32(1.0),
    ).astype(xp.float32)


@dataclasses.dataclass(frozen=True)
class QuantArray:
    """A quantized float32 chunk in transit.

    ``mode`` is ``"bf16"`` (``data`` = uint16 high halves) or ``"int8"``
    (``data`` = symmetric codes in [-127, 127], ``scale`` = absmax/127).
    Pickles fine, so quantized exchange also works over the inproc
    broker and with pickle-only peers — quantization is a protocol-layer
    choice, independent of the framing."""

    mode: str
    scale: float
    data: np.ndarray

    @property
    def nbytes(self) -> int:
        """On-wire payload size (the telemetry byte counters read this
        via the same ``nbytes`` duck-type as real ndarrays): quantized
        buffer plus the header-resident scale."""
        return int(self.data.nbytes) + _F32_SIZE


# -- host (numpy) path ----------------------------------------------------


def _rt_numerics_checker():
    """The RT104 numerics sanitizer, IF some other code armed it.

    This module must stay importable with only numpy (lint.sh gate 6
    pins it jax- and analysis-free), so we never import the analysis
    package here: ``sys.modules`` is peeked for an already-imported
    ``analysis.runtime`` — exactly the processes that armed the checker
    (``MPIT_RT_NUMERICS=1`` ranks, ``checking(numerics=True)`` tests)
    have it loaded. Costs one dict lookup per quantize when unarmed."""
    rt = sys.modules.get("mpit_tpu.analysis.runtime")
    if rt is None:
        return None
    checker = rt.active_checker()
    if checker is not None and getattr(checker, "numerics", False):
        return checker
    return None


def quantize(arr: np.ndarray, mode: str) -> QuantArray:
    """Pack a float32 array into a :class:`QuantArray` (copies — the
    quantized buffer is new; the input is never aliased)."""
    a = np.ascontiguousarray(arr, dtype=np.float32)
    if mode == "bf16":
        data = _bf16_codes(np, a, a.view(np.uint32)).astype(np.uint16)
        checker = _rt_numerics_checker()
        if checker is not None:
            checker.on_quantize("quantize", a, mode, None, data)
        return QuantArray("bf16", 1.0, data)
    if mode == "int8":
        # NaN/Inf never drive the block scale (an all-NaN chunk used to
        # poison amax and cast NaN to int8 — undefined codes); the scale
        # comes from the finite elements only, so it stays finite
        finite = np.isfinite(a)
        amax = (
            np.float32(np.max(np.where(finite, np.abs(a), np.float32(0))))
            if a.size
            else np.float32(0)
        )
        scale = _int8_scale(np, amax)
        codes = np.clip(np.rint(a / scale), -127, 127)
        # ±Inf saturates to ±127 via the clip; NaN pins to code 0, so a
        # poisoned element dequantizes to 0 instead of garbage
        data = np.where(np.isnan(a), np.float32(0), codes).astype(np.int8)
        checker = _rt_numerics_checker()
        if checker is not None:
            checker.on_quantize("quantize", a, mode, scale, data)
        return QuantArray("int8", float(scale), data)
    raise ValueError(f"unknown quantization mode {mode!r}")


def dequantize(q: QuantArray) -> np.ndarray:
    """float32 reconstruction of a :class:`QuantArray`."""
    if q.mode == "bf16":
        data = np.ascontiguousarray(q.data, dtype=np.uint16)
        return (data.astype(np.uint32) << 16).view(np.float32)
    if q.mode == "int8":
        checker = _rt_numerics_checker()
        if checker is not None:
            checker.on_dequantize("dequantize", q.scale, q.mode)
        data = np.asarray(q.data, dtype=np.int8)
        return data.astype(np.float32) * np.float32(q.scale)
    raise ValueError(f"unknown quantization mode {q.mode!r}")


def quantize_rows(a: np.ndarray, mode: str):
    """Host twin of :func:`quantize_rows_jnp`: blockwise quantization of
    a 2-D float32 array, one absmax scale per row. Returns
    ``(codes (B, n), scales (B, 1))``, bit-identical to the jnp face on
    the same input (pinned in tests/test_wire.py) — the reference the
    RT104 sanitizer and the property suite probe without a jax import."""
    a = np.ascontiguousarray(a, dtype=np.float32)
    if a.ndim != 2:
        raise ValueError(f"quantize_rows wants a 2-D array, got {a.shape}")
    if mode == "bf16":
        return quantize(a, "bf16").data, np.ones(
            (a.shape[0], 1), np.float32
        )
    if mode == "int8":
        finite = np.isfinite(a)
        amax = np.max(
            np.where(finite, np.abs(a), np.float32(0)),
            axis=1,
            keepdims=True,
        ).astype(np.float32) if a.size else np.zeros(
            (a.shape[0], 1), np.float32
        )
        scales = _int8_scale(np, amax)
        codes = np.clip(np.rint(a / scales), -127, 127)
        codes = np.where(np.isnan(a), np.float32(0), codes).astype(np.int8)
        checker = _rt_numerics_checker()
        if checker is not None:
            checker.on_quantize("quantize_rows", a, mode, scales, codes)
        return codes, scales
    raise ValueError(f"unknown quantization mode {mode!r}")


def dequantize_rows(codes: np.ndarray, scales, mode: str) -> np.ndarray:
    """Host twin of :func:`dequantize_rows_jnp` (scales broadcast over
    rows; ignored for bf16)."""
    if mode == "bf16":
        return dequantize(QuantArray("bf16", 1.0, codes))
    if mode == "int8":
        checker = _rt_numerics_checker()
        if checker is not None:
            checker.on_dequantize("dequantize_rows", scales, mode)
        data = np.asarray(codes, dtype=np.int8)
        return data.astype(np.float32) * np.asarray(scales, np.float32)
    raise ValueError(f"unknown quantization mode {mode!r}")


# -- device (jnp) path ----------------------------------------------------
#
# The jnp twins return (codes, scales) pairs instead of QuantArray —
# inside a traced program the scale is an array, and the collective path
# needs PER-BLOCK scales (one per destination row of the reduce-scatter)
# that a scalar-field dataclass cannot carry. ``quantize_jnp`` is the
# whole-array special case (scale shape ``()``); ``quantize_rows_jnp``
# quantizes each row of a 2-D array independently (scales ``(rows, 1)``).


def _jnp():
    import jax.numpy as jnp
    from jax import lax

    return jnp, lax


def quantize_jnp(x, mode: str):
    """jit-safe twin of :func:`quantize`: ``(codes, scale)`` for one
    array with ONE scale (f32 scalar; fixed 1.0 for bf16). Codes and
    scale are bit-identical to the numpy path on the same input."""
    jnp, lax = _jnp()
    a = jnp.asarray(x, jnp.float32)
    if mode == "bf16":
        u = lax.bitcast_convert_type(a, jnp.uint32)
        return _bf16_codes(jnp, a, u).astype(jnp.uint16), jnp.float32(1.0)
    if mode == "int8":
        # same NaN/Inf guards as the host path (scale from finite
        # elements only; Inf saturates, NaN pins to code 0) — the two
        # faces must stay bit-identical on ANY input, not just clean ones
        amax = (
            jnp.max(jnp.where(jnp.isfinite(a), jnp.abs(a), 0.0))
            if a.size
            else jnp.float32(0)
        )
        scale = _int8_scale(jnp, amax)
        codes = jnp.clip(jnp.rint(a / scale), -127, 127)
        codes = jnp.where(jnp.isnan(a), 0.0, codes).astype(jnp.int8)
        return codes, scale
    raise ValueError(f"unknown quantization mode {mode!r}")


def dequantize_jnp(codes, scale, mode: str):
    """float32 reconstruction of a jnp ``(codes, scale)`` pair."""
    jnp, lax = _jnp()
    if mode == "bf16":
        u = codes.astype(jnp.uint32) << 16
        return lax.bitcast_convert_type(u, jnp.float32)
    if mode == "int8":
        return codes.astype(jnp.float32) * jnp.asarray(scale, jnp.float32)
    raise ValueError(f"unknown quantization mode {mode!r}")


def quantize_rows_jnp(x, mode: str):
    """Blockwise quantization of a 2-D array: each row gets its own
    absmax scale (the reduce-scatter layout — row j is the block bound
    for worker j). Returns ``(codes (B, n), scales (B, 1))``; bf16
    scales are ones (carried for shape uniformity, never sent)."""
    jnp, lax = _jnp()
    a = jnp.asarray(x, jnp.float32)
    if mode == "bf16":
        codes, _ = quantize_jnp(a, "bf16")
        return codes, jnp.ones((a.shape[0], 1), jnp.float32)
    if mode == "int8":
        amax = jnp.max(
            jnp.where(jnp.isfinite(a), jnp.abs(a), 0.0),
            axis=1,
            keepdims=True,
        )
        scale = _int8_scale(jnp, amax)
        codes = jnp.clip(jnp.rint(a / scale), -127, 127)
        codes = jnp.where(jnp.isnan(a), 0.0, codes).astype(jnp.int8)
        return codes, scale
    raise ValueError(f"unknown quantization mode {mode!r}")


def dequantize_rows_jnp(codes, scales, mode: str):
    """float32 reconstruction of a blockwise pair (scales broadcast
    over rows)."""
    jnp, _ = _jnp()
    if mode == "bf16":
        return dequantize_jnp(codes, None, "bf16")
    if mode == "int8":
        return codes.astype(jnp.float32) * jnp.asarray(scales, jnp.float32)
    raise ValueError(f"unknown quantization mode {mode!r}")
