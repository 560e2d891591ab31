"""Compressed convolutional attention (CCA): what lies between the
projections into the latent and the attention itself.

Source: Figliolia et al. (Zyphra), "Compressed Convolutional Attention:
Efficient Attention in a Compressed Latent Space", arXiv:2510.04476; the
layer as the ``zaya`` family's ``config.json`` sizes it (``models/arch.py``).
The query and key projections land in a latent of ``H_q`` and ``H_kv`` heads
of ``d_h``, narrower than the model, and are not projected up again. With
``q0`` ``(B, T, H_q, d_h)``, ``k0`` ``(B, T, H_kv, d_h)`` and the two halves
of the value projection ``v_now`` and ``v_back`` ``(B, T, H_kv d_h / 2)``:

1. value shift: ``v_t = [v_now_t ; v_back_{t-1}]`` a head, ``v_back_{-1} =
   0``: half of each value head's channels look one token back;
2. convolutional mixing of ``u = [q0 ; k0]`` (``H_q + H_kv`` heads), causal
   along time: ``c_t = sum_j a_j * u_{t-j}`` over ``time0`` taps, depthwise
   (``a`` ``(C, time0)``, ``C = (H_q + H_kv) d_h``), then ``z_t = sum_j
   c_{t-j} B_j`` over ``time1`` taps, each head's ``d_h`` channels mixed by
   its own ``d_h x d_h`` matrix (``B`` ``(H, d_h, d_h, time1)``); no bias,
   no activation between;
3. q-k mean, query head ``i`` of group ``g`` (``H_q / H_kv`` query heads a
   key head): ``q_i = z_i + (q0_i + k0_g) / 2`` and ``k_g = z_g + (mean_{i
   in g} q0_i + k0_g) / 2``;
4. L2 norms with a temperature: ``q_i <- sqrt(d_h) tau_i q_i / |q_i|``,
   ``k_g <- sqrt(d_h) k_g / |k_g|``, ``tau`` ``(H_q,)`` learned.

The forms of 1, 3 and 4 and the absence of a bias or an activation in 2 are
assumed (the config fixes only the tap counts; ``benchmark/configs/
zaya1-8b.json`` lists each assumption). Everything is ``jax.numpy`` and
differentiated by jax; the per-head products take their operands in the
inputs' dtype and accumulate in float32, the rest is float32. Every step
reads a token and those before it, never after.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def shift(a, steps: int = 1, axis: int = 1):
    """``a_{t - steps}`` along ``axis`` (time), zeros before the first
    token."""
    if steps == 0:
        return a
    pad = [(0, 0)] * a.ndim
    pad[axis] = (steps, 0)
    return jax.lax.slice_in_dim(jnp.pad(a, pad), 0, a.shape[axis], axis=axis)


def value_shift(v_now, v_back, kv_heads: int):
    """``(B, T, H_kv d_h / 2)`` twice -> ``v`` ``(B, T, H_kv, d_h)``: each
    head's first half from this token, its second from the one before."""
    b, t, _ = v_now.shape
    heads = lambda a: a.reshape(b, t, kv_heads, -1)
    return jnp.concatenate([heads(v_now), heads(shift(v_back))], axis=-1)


def conv_mix(u, taps0, taps1):
    """``u`` ``(B, T, H, d_h)`` -> ``z``, the same shape, in float32: the
    depthwise causal convolution ``taps0`` ``(H d_h, time0)``, then the
    per-head one ``taps1`` ``(H, d_h, d_h, time1)``."""
    b, t, h, d = u.shape
    a = taps0.reshape(h, d, -1).astype(F32)
    u = u.astype(F32)
    c = sum(shift(u, j) * a[..., j] for j in range(a.shape[-1]))
    # heads leading: the CPU's dot has no bfloat16 product batched inside
    c = jnp.moveaxis(c.astype(taps1.dtype), 2, 0)
    z = sum(
        jnp.einsum("hbtd,hde->hbte", shift(c, j, axis=2), taps1[..., j],
                   preferred_element_type=F32)
        for j in range(taps1.shape[-1]))
    return jnp.moveaxis(z, 0, 2)


def qk_mean(zq, zk, q0, k0):
    """Step 3: each query head gets the mean of itself and its key head,
    each key head the mean of its query heads' mean and itself."""
    b, t, h, d = q0.shape
    h_kv = k0.shape[2]
    q0, k0 = q0.astype(F32), k0.astype(F32)
    q = zq + (q0 + jnp.repeat(k0, h // h_kv, axis=2)) / 2
    k = zk + (q0.reshape(b, t, h_kv, h // h_kv, d).mean(3) + k0) / 2
    return q, k


def unit(a):
    """``a / sqrt(|a|² + 1e-6)`` over the last axis, in float32."""
    a = a.astype(F32)
    return a * jax.lax.rsqrt(jnp.sum(jnp.square(a), -1, keepdims=True) + 1e-6)


def cca_mix(q0, k0, v_now, v_back, taps0, taps1, temperature):
    """Steps 1 to 4: ``(q, k, v)`` for grouped causal attention, ``q``
    ``(B, T, H_q, d_h)``, ``k`` and ``v`` ``(B, T, H_kv, d_h)``, in
    float32, ``q`` and ``k`` at norm ``sqrt(d_h)`` (``q`` times its
    head's ``temperature``) before any rotary."""
    h, d = q0.shape[2], q0.shape[3]
    v = value_shift(v_now, v_back, k0.shape[2])
    z = conv_mix(jnp.concatenate([q0, k0], axis=2), taps0, taps1)
    q, k = qk_mean(z[:, :, :h], z[:, :, h:], q0, k0)
    scale = jnp.sqrt(jnp.float32(d))
    q = unit(q) * (scale * temperature.astype(F32))[:, None]
    return q, unit(k) * scale, v.astype(F32)
