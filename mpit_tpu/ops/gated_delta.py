"""The gated delta rule over a sequence, in chunks (the WY / UT transform).

Sources: Yang, Kautz, Hatamizadeh, "Gated Delta Networks: Improving Mamba2
with Delta Rule", arXiv:2412.06464 (the recurrence and its chunked form);
OLMo 2, arXiv:2501.00656 (the block round it, ``models/transformer.py``).

The linear-attention mixer this serves (``Block._linear_attention_mixer``),
input ``u`` ``(B, T, d)``, ``H`` heads, keys of ``d_k``, values of ``d_v``:

1. ``q = SiLU(conv(u W_q))``, ``k = SiLU(conv(u W_k))``, ``v = SiLU(conv(u
   W_v))``: three depthwise causal convolutions, no bias; reshaped to heads.
2. ``q <- q / |q|_2 · d_k^-1/2``, ``k <- k / |k|_2`` a head and step (L2,
   epsilon 1e-6, float32).
3. ``beta_t = sigmoid(u W_b)`` a head, times 2 where negative eigenvalues are
   allowed (``beta`` in (0, 2): ``I - beta k k^T`` has an eigenvalue in (-1,
   1)); ``g_t = -exp(A_log) · softplus(u W_a + dt_bias)`` a head, the log
   decay, never positive; float32.
4. A state ``S`` in ``R^{d_v x d_k}`` a head, ``S_0 = 0``::

       S' = exp(g_t) S_{t-1}
       S_t = S' + beta_t (v_t - S' k_t) k_t^T        o_t = S_t q_t

5. ``y = RMSNorm_head(o) · w_norm · SiLU(u W_gate)``, then ``W_o``.

This module is item 4: :func:`gated_delta` takes ``q``, ``k`` (after item 2),
``v``, ``g`` and ``beta`` and returns ``o``. Unlike Mamba-2's recurrence
(``ops/ssd.py``: a scalar decay and a rank-one ADDITION) the update first
READS the state (``S' k_t``), so a chunk is not masked products alone: it
needs a triangular solve. In chunks of ``C`` steps, with ``gamma_i`` the sum of
``g`` over the chunk's steps up to and including ``i``, rows a step, and the
state held transposed (``S^T``, ``d_k x d_v``):

- ``A = tril_strict(diag(beta) (K K^T ⊙ exp(gamma_i - gamma_j)))``;
- ``T = (I + A)^-1 diag(beta)``; ``W = T (K ⊙ exp(gamma))``, ``U = T V``;
- with the entering state: ``V_new = U - W S^T``; ``O = (Q ⊙ exp(gamma)) S^T +
  tril(Q K^T ⊙ exp(gamma_i - gamma_j)) V_new``;
- ``S^T_out = exp(gamma_C) S^T + (K ⊙ exp(gamma_C - gamma))^T V_new``.

(Unrolled, ``S_t = exp(gamma_t) S + sum_{j<=t} exp(gamma_t - gamma_j) u_j
k_j^T`` with ``u_t = beta_t (v_t - S'_t k_t)``; putting ``S'_t`` in gives ``(I
+ A) V_new = diag(beta) (V - (K ⊙ exp(gamma)) S^T)``, the rows of ``V_new``
being the ``u_t``.) Only ``V_new`` and the state depend on the chunk before,
so a ``lax.scan`` over the chunks carries the state and makes two products a
step; everything else is one batched product over a segment's chunks
(``SEGMENT``: an outer scan takes the sequence a segment at a time under a
``jax.checkpoint``, which bounds what the backward holds).

**The inverse.** ``I + A`` is unit lower triangular. Its inverse is taken by
halves: the inverse of ``[[L11, 0], [L21, L22]]`` is ``[[L11^-1, 0], [-L22^-1
L21 L11^-1, L22^-1]]``, from blocks of 1 up to the chunk, ``log2 C`` levels of
two batched products each (twelve for ``C = 64``), float32 at
``Precision.HIGHEST``. Every intermediate is a block of the true inverse, whose
size the recurrence bounds (each ``I - beta k k^T`` is a contraction), where the
product form ``(I - A)(I + A^2)(I + A^4)...`` passes through powers of ``A``
that grow as ``C(63, n) |A_ij|^n`` before they vanish and a row-by-row
substitution is ``C`` dependent steps a chunk. ``C`` must be a power of two.

**The repo's rule for decays** (``ops/ssd.py``) holds: every exponent is a sum
or a masked difference that is never positive (``gamma_i``, ``gamma_i -
gamma_j`` for ``j <= i``, ``gamma_C - gamma_j``), the mask applied BEFORE the
exponential, nothing is ever divided by a decay, so nothing overflows however
strong a chunk's decay; ``log_decay_min``, the most negative ``gamma_C`` met,
says whether a form factorised as ``exp(gamma_i) · exp(-gamma_j)`` would
(below about -88 in float32). Decays, their sums, the inverse and the carried
state are float32; the other products take their operands in ``v``'s dtype
and accumulate in float32.

Plain ``jax.numpy``, differentiated by jax: no kernel. A Pallas kernel is a
later change's, read against ``delta_roofline_pct`` (``benchmark/lib/
delta_kernels.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


def unit_lower_inverse(lower):
    """The inverse of ``I + lower`` for a strictly lower triangular
    ``lower`` ``(..., C, C)``, ``C`` a power of two, by halves (float32)."""
    c = lower.shape[-1]
    lead = lower.shape[:-2]
    # the diagonal blocks' inverses, size s: (..., C / s, s, s)
    inv = jnp.ones((*lead, c, 1, 1), jnp.float32)
    s = 1
    while s < c:
        n = c // (2 * s)
        pairs = jnp.moveaxis(jnp.diagonal(
            lower.reshape(*lead, n, 2 * s, n, 2 * s), axis1=-4, axis2=-2),
            -1, -3)  # the diagonal blocks of size 2 s: (..., n, 2 s, 2 s)
        inv = inv.reshape(*lead, n, 2, s, s)
        upper, under = inv[..., 0, :, :], inv[..., 1, :, :]
        corner = -jnp.matmul(
            jnp.matmul(under, pairs[..., s:, :s], precision=_HIGHEST),
            upper, precision=_HIGHEST)
        inv = jnp.concatenate([
            jnp.concatenate([upper, jnp.zeros_like(upper)], axis=-1),
            jnp.concatenate([corner, under], axis=-1)], axis=-2)
        s *= 2
    return inv.reshape(*lead, c, c)


#: chunks a segment: the chunked form is taken a segment at a time under a
#: ``jax.checkpoint``, so that its backward keeps the state between segments
#: and rebuilds a segment's ``C x C`` matrices, ``W``, ``U`` and chunk states
#: instead of holding all of them (3 GB a layer at 8,192 tokens, 30 heads)
SEGMENT = 16


def _segment(state, q, k, v, g, beta):
    """The chunked form over one segment: ``q``, ``k`` ``(B, n, H, C, d_k)``,
    ``v`` ``(B, n, H, C, d_v)``, ``g``, ``beta`` ``(B, n, H, C)`` float32,
    ``state`` ``(B, H, d_k, d_v)`` float32 entering. Returns the state
    leaving and ``o`` ``(B, n, H, C, d_v)`` in ``v``'s dtype."""
    f32, dtype = jnp.float32, v.dtype
    c = q.shape[-2]
    mm = lambda spec, a, b: jnp.einsum(
        spec, a.astype(dtype), b.astype(dtype), preferred_element_type=f32)
    gamma = jnp.cumsum(g, axis=-1)
    last = gamma[..., -1]  # a chunk's whole log decay
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    seg = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.exp(jnp.where(i >= j, seg, -jnp.inf))  # j <= i, else 0
    from_start = jnp.exp(gamma)[..., None]
    to_end = jnp.exp(last[..., None] - gamma)[..., None]

    kk = mm("zchid,zchjd->zchij", k, k)
    a = jnp.where(i > j, beta[..., None] * kk * decay, 0.0)
    solve = unit_lower_inverse(a) * beta[..., None, :]  # (I + A)^-1 diag(beta)
    w = mm("zchij,zchjd->zchid", solve, k.astype(f32) * from_start)
    u = mm("zchij,zchjd->zchid", solve, v)
    within = mm("zchid,zchjd->zchij", q, k) * decay  # tril(Q K^T ⊙ decay)
    k_end = (k.astype(f32) * to_end).astype(dtype)

    def step(state, at):
        w_c, u_c, k_c, log_decay = at
        v_new = u_c - mm("zhid,zhde->zhie", w_c, state)
        made = mm("zhid,zhie->zhde", k_c, v_new)
        return jnp.exp(log_decay)[..., None, None] * state + made, (
            state, v_new.astype(dtype))

    chunks = lambda arr: jnp.moveaxis(arr, 1, 0)
    state, (entering, v_new) = lax.scan(
        step, state,
        (chunks(w.astype(dtype)), chunks(u), chunks(k_end), chunks(last)))
    entering, v_new = jnp.moveaxis(entering, 0, 1), jnp.moveaxis(v_new, 0, 1)
    o = (mm("zchid,zchde->zchie", q.astype(f32) * from_start, entering)
         + mm("zchij,zchjd->zchid", within, v_new))
    return state, o.astype(dtype)


def gated_delta(q, k, v, g, beta, *, chunk: int):
    """``q``, ``k``: ``(B, T, H, d_k)`` (normalised by the caller); ``v``:
    ``(B, T, H, d_v)``; ``g``: ``(B, T, H)`` float32 log decays, never
    positive; ``beta``: ``(B, T, H)`` float32. Returns ``o`` ``(B, T, H,
    d_v)`` in ``v``'s dtype and ``log_decay_min``, a float32 scalar. Any
    ``T``: it is padded to whole segments of ``SEGMENT`` chunks (one
    segment where it is shorter) with steps of ``g = 0`` and ``beta = 0``,
    which neither decay nor write the state."""
    f32 = jnp.float32
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]
    c = chunk
    if c < 1 or c & (c - 1):
        raise ValueError(f"gated_delta: chunk {c} is not a power of two")
    n = min(SEGMENT, -(-t // c))  # chunks a segment
    pad = -t % (n * c)
    g, beta = g.astype(f32), beta.astype(f32)
    if pad:
        q, k, v, g, beta = (
            jnp.pad(a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            for a in (q, k, v, g, beta))
    segments = (t + pad) // (n * c)
    # (segment, batch, chunk, head, step, ...): a chunk's C x C masks have
    # steps on both of their minor dimensions
    cut = lambda a: jnp.moveaxis(
        a.reshape(bsz, segments, n, c, h, *a.shape[3:]), (1, 3), (0, 4))
    body = jax.checkpoint(lambda state, at: _segment(state, *at))
    _, o = lax.scan(body, jnp.zeros((bsz, h, dk, dv), f32),
                    tuple(cut(a) for a in (q, k, v, g, beta)))
    o = jnp.moveaxis(o, (0, 4), (1, 3)).reshape(bsz, t + pad, h, dv)[:, :t]
    low = jnp.min(jnp.sum(g.reshape(bsz, -1, c, h), axis=2))
    return o, lax.stop_gradient(low)
