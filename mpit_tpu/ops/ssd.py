"""The Mamba-2 recurrence over a sequence, in chunks (state-space duality).

For each head ``h`` with a state ``S`` in ``R^{P x N}``, ``S_0 = 0``::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

``A`` is a negative scalar a head, ``dt`` a positive scalar a head and step,
``B_t``, ``C_t`` in ``R^N`` are shared by the ``H / G`` heads of a group. Taken
step by step this is ``T`` dependent updates of a ``P x N`` state; the chunked
form (Dao and Gu, "Transformers are SSMs", 2024, section 6) makes it matrix
products. With ``a_t = dt_t A`` and ``cum_i`` the sum of ``a`` over a chunk's
steps up to and including ``i``:

- inside a chunk, ``y_i = sum_{j <= i} L_ij (C_i . B_j) dt_j x_j`` with
  ``L_ij = exp(cum_i - cum_j)``: a masked ``Q x Q`` product a head and chunk;
- a chunk's closing state from nothing, ``sum_j exp(cum_last - cum_j)
  dt_j x_j B_j^T``;
- the states carried chunk to chunk, ``S_in' = exp(cum_last) S_in + closing``;
- ``C_i`` times the carried state, decayed to step ``i``: ``exp(cum_i) S_in
  C_i``, added in.

Every decay is the exponential of a difference that is never positive
(``cum_i - cum_j`` for ``j <= i``), so nothing overflows whatever the chunk's
total decay; ``log_decay_min``, the most negative ``cum_last`` met, says
whether a form factorised as ``exp(cum_i) · exp(-cum_j)`` would (below about
-88 in float32). Decays and their sums are float32; the products take their
operands in ``x``'s dtype and accumulate in float32. Differentiated by jax.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def ssd(x, dt, a, b, c, d, *, chunk: int, carry_state: bool = True):
    """``x``: ``(B, T, H, P)``; ``dt``: ``(B, T, H)`` float32, after its
    softplus; ``a``: ``(H,)`` float32, negative; ``b``, ``c``: ``(B, T, G,
    N)``; ``d``: ``(H,)``. Returns ``y`` ``(B, T, H, P)`` in ``x``'s dtype
    and ``log_decay_min``, a float32 scalar. Any ``T``: the last chunk is
    padded with steps of ``dt = 0``, which neither decay nor feed the state.

    ``carry_state=False`` leaves the states where they are made (every chunk
    starts from 0): a fault, for the control that the comparison deciding a
    cell's ``correct`` has to refuse (``scripts/nemotron_controls.py``)."""
    f32, dtype = jnp.float32, x.dtype
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g  # heads a group
    q = chunk
    pad = -t % q
    if pad:
        padded = lambda v: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        x, dt, b, c = padded(x), padded(dt), padded(b), padded(c)
    nc = (t + pad) // q
    mm = lambda spec, u, v: jnp.einsum(
        spec, u.astype(dtype), v.astype(dtype), preferred_element_type=f32)

    # (batch, chunk, step, group, head of the group, ...); the decays with
    # the step last, so that a chunk's Q x Q masks have steps on both of
    # their minor dimensions
    xc = x.reshape(bsz, nc, q, g, r, p)
    bc, cc = b.reshape(bsz, nc, q, g, n), c.reshape(bsz, nc, q, g, n)
    dtc = dt.astype(f32).reshape(bsz, nc, q, g, r)
    xdt = (xc.astype(f32) * dtc[..., None]).astype(dtype)
    cum = jnp.cumsum(
        jnp.moveaxis(dtc, 2, -1) * a.astype(f32).reshape(g, r, 1), axis=-1)
    last = cum[..., -1]  # (B, nc, g, r): a chunk's whole log-decay

    # inside a chunk: (L o C B^T)(dt x)
    i, j = jnp.arange(q)[:, None], jnp.arange(q)[None, :]
    seg = cum[..., :, None] - cum[..., None, :]  # (B, nc, g, r, i, j)
    decay = jnp.exp(jnp.where(i >= j, seg, -jnp.inf))
    cb = mm("zcign,zcjgn->zcgij", cc, bc)
    y = mm("zcgrij,zcjgrp->zcigrp", cb[:, :, :, None] * decay, xdt)

    # a chunk's closing state from nothing, then the carry
    to_end = jnp.moveaxis(jnp.exp(last[..., None] - cum), -1, 2)
    closing = mm("zcjgrp,zcjgn->zcgrpn",
                 xdt.astype(f32) * to_end[..., None], bc)

    def step(state, chunk_in):
        made, log_decay = chunk_in
        return jnp.exp(log_decay)[..., None, None] * state + made, state

    if carry_state:
        _, entering = lax.scan(
            step, jnp.zeros((bsz, g, r, p, n), f32),
            (jnp.moveaxis(closing, 1, 0), jnp.moveaxis(last, 1, 0)))
        entering = jnp.moveaxis(entering, 0, 1)  # (B, nc, g, r, p, n)
        y = y + mm("zcign,zcgrpn->zcigrp", cc, entering) * jnp.moveaxis(
            jnp.exp(cum), -1, 2)[..., None]

    y = y + xc.astype(f32) * d.astype(f32).reshape(g, r)[..., None]
    y = y.reshape(bsz, nc * q, h, p)[:, :t].astype(dtype)
    return y, lax.stop_gradient(jnp.min(last))
