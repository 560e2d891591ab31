"""Plain reference for the ``olmo_hybrid`` family ``TrainConfig.arch`` describes
(``layer_types`` of ``linear_attention`` and ``full_attention``: gated-delta-rule
and causal-attention mixers, each followed by a dense SwiGLU).

The layer equations written out in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: the gated delta rule step by step
over the sequence (never in chunks, no triangular solve), a dense causal mask,
no flax, no kernel, no rematerialisation policy of the program's. It shares no
code with ``models/transformer.py``, ``models/arch.py`` or ``ops/``; what it
shares is the parameter tree's names, so that both can be handed the same
weights:

    Embed_0/embedding (V, d)        final_norm (d,)        head (V, d)
    linear: Block_l/linattn_norm (d,)  lin_q, lin_k (d, H d_k)
       lin_v, lin_gate (d, H d_v)  lin_a, lin_b (d, H)  lin_o (H d_v, d)
       conv_q, conv_k (H d_k, K)  conv_v (H d_v, K)  A_log, dt_bias (H,)
       gate_norm (d_v,)
    full: Block_l/attn_norm (d,)  wq, wk, wv (d, H hd)  wo (H hd, d)
       q_norm, k_norm (H hd,)
    both: Block_l/ffn_norm (d,)  w_gate, w_up (d, f)  w_down (f, d)

``arch`` is the source's ``config.json`` as a dict, with ``num_hidden_layers``
the layers present (the first that many of ``layer_types``).

Equations (sources: Yang, Kautz, Hatamizadeh, "Gated Delta Networks",
arXiv:2412.06464, for the mixer; OLMo 2, arXiv:2501.00656, for the block).
RMSNorm is ``y / sqrt(mean(y²) + rms_norm_eps) * w``. Every layer:
``x <- x + RMSNorm(Mixer(x))``, then ``x <- x + RMSNorm(SwiGLU(x))``: the norm
on each sublayer's OUTPUT (OLMo 2's reordered norm; assumed: the config alone
does not say), ``SwiGLU(x) = W_down (SiLU(W_gate x) * W_up x)``.

- ``linear_attention`` (``H`` heads, keys of ``d_k``, values of ``d_v``):
  ``q = SiLU(conv(x W_q))``, ``k = SiLU(conv(x W_k))``, ``v = SiLU(conv(x
  W_v))``, ``conv`` depthwise and causal with kernel ``K`` and no bias,
  ``out_t = sum_j w[:, j] in_{t - (K-1) + j}``; a head and step, ``q <- q /
  sqrt(|q|² + 1e-6) / sqrt(d_k)``, ``k <- k / sqrt(|k|² + 1e-6)``; ``beta =
  sigmoid(x W_b)``, times 2 where ``linear_allow_neg_eigval``; ``g =
  -exp(A_log) softplus(x W_a + dt_bias)``; a state ``S`` of ``d_v x d_k`` a
  head, ``S_0 = 0``: ``S' = exp(g_t) S_{t-1}``, ``S_t = S' + beta_t (v_t - S'
  k_t) k_tᵀ``, ``o_t = S_t q_t``; ``y = RMSNorm(o; gate_norm)`` over each
  head's ``d_v`` channels, THEN times ``SiLU(x W_gate)``; ``W_o``.
- ``full_attention``: ``q = x Wq``, ``k = x Wk``, each through an RMSNorm over
  its whole width (``q_norm``, ``k_norm``: OLMo 2's QK norm, before the split
  into heads), ``v = x Wv``; ``H`` heads of ``hd = d / H`` on as many KV heads
  (grouped where ``num_key_value_heads`` is smaller); scores ``q kᵀ /
  sqrt(hd)``, key ``j`` visible to query ``i`` iff ``j <= i``; softmax; ``Wo``.
  No rotary and no other position signal: ``rope_parameters.rope_theta`` is
  null (the linear-attention layers carry position).
- Loss: mean over positions of the cross-entropy of ``final_norm(x) Wheadᵀ``.

``operand_dtype`` rounds both operands of every product to that dtype first
(the matrix products, and the recurrence's ``q``, ``k`` and ``v``; the products
still accumulate in float32): what the same equations give in a lower
precision, which a comparison's tolerances must tell apart from the system.

``loss_and_grad_by_layer`` is the same loss and gradient taken a layer at a
time, so that the published widths fit a chip's memory: each layer's input is
kept, each layer's vector-Jacobian product is its own program, attention runs
a query head at a time (the same head function, under ``jax.lax.map``), and
the recurrence's ``T`` steps are taken in blocks whose inner steps are
computed again on the way back (the same step function; no arithmetic
changes). It takes and ignores ``experts_held``, ``expert_offset`` and
``choices`` (the signature ``benchmark/drivers`` call: the model has no
expert) and returns None for every layer's own choices.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32

#: steps of the recurrence a block, where its steps are recomputed backward
SCAN_BLOCK = 128


def _round(a, operand_dtype):
    return a if operand_dtype is None else a.astype(operand_dtype).astype(F32)


def _mm(a, b, operand_dtype=None):
    return jnp.matmul(_round(a, operand_dtype), _round(b, operand_dtype))


def _eps(arch):
    return arch.get("rms_norm_eps", 1e-6)


def rms_norm(y, scale, eps):
    return y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * scale


def layer_kind(arch: dict, l: int) -> str:
    return arch["layer_types"][l]


# -- linear_attention: the gated delta rule ------------------------------------

def recurrence(q, k, v, g, beta, blocks=False, delta_term=True):
    """``q``, ``k``: ``(B, T, H, d_k)``; ``v``: ``(B, T, H, d_v)``; ``g``,
    ``beta``: ``(B, T, H)``. ``o``: ``(B, T, H, d_v)``, one step of the
    sequence at a time. ``delta_term=False`` leaves the read of the state out
    of the update (plain gated linear attention: a fault, for a control)."""
    bsz, t, h, dk = q.shape
    dv = v.shape[-1]

    def step(state, at):  # state: (B, H, d_v, d_k)
        q_t, k_t, v_t, g_t, beta_t = at
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.sum(state * k_t[..., None, :], axis=-1) if delta_term else 0.0
        state = state + (beta_t[..., None] * (v_t - read))[..., None] * k_t[
            ..., None, :]
        return state, jnp.sum(state * q_t[..., None, :], axis=-1)

    steps = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    state = jnp.zeros((bsz, h, dv, dk), F32)
    if not blocks or t % SCAN_BLOCK:
        return jnp.moveaxis(jax.lax.scan(step, state, steps)[1], 0, 1)
    # the same steps, SCAN_BLOCK at a time; a block's steps are computed
    # again on the way back, so only the states between blocks are kept
    block = jax.checkpoint(lambda s, at: jax.lax.scan(step, s, at))
    cut = lambda a: a.reshape(t // SCAN_BLOCK, SCAN_BLOCK, *a.shape[1:])
    _, o = jax.lax.scan(block, state, tuple(cut(a) for a in steps))
    return jnp.moveaxis(o.reshape(t, bsz, h, dv), 0, 1)


def conv_silu(x, w):
    """Causal depthwise convolution without bias (``K - 1`` zeros to the
    left), then SiLU. ``x``: ``(B, T, C)``; ``w``: ``(C, K)``."""
    t, taps = x.shape[1], w.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for tap in range(taps):
        out = out + padded[:, tap:tap + t] * w[:, tap]
    return jax.nn.silu(out)


def _unit(a):
    return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)


def linear_attention(p, x, arch, operand_dtype=None, blocks=False,
                     delta_term=True):
    bsz, t, _ = x.shape
    h = arch["linear_num_key_heads"]
    dk, dv = arch["linear_key_head_dim"], arch["linear_value_head_dim"]
    mm = functools.partial(_mm, operand_dtype=operand_dtype)
    q = conv_silu(mm(x, p["lin_q"]), p["conv_q"]).reshape(bsz, t, h, dk)
    k = conv_silu(mm(x, p["lin_k"]), p["conv_k"]).reshape(bsz, t, h, dk)
    v = conv_silu(mm(x, p["lin_v"]), p["conv_v"]).reshape(bsz, t, h, dv)
    q, k = _unit(q) / math.sqrt(dk), _unit(k)
    beta = jax.nn.sigmoid(mm(x, p["lin_b"])) * (
        2.0 if arch.get("linear_allow_neg_eigval") else 1.0)
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(mm(x, p["lin_a"])
                                               + p["dt_bias"])
    o = recurrence(_round(q, operand_dtype), _round(k, operand_dtype),
                   _round(v, operand_dtype), g, beta, blocks, delta_term)
    gate = jax.nn.silu(mm(x, p["lin_gate"]))  # norm first, then the gate
    y = rms_norm(o, p["gate_norm"], _eps(arch)).reshape(bsz, t, h * dv) * gate
    return mm(y, p["lin_o"])


# -- full_attention ------------------------------------------------------------

def _one_head(q, k, v, seen, operand_dtype):
    """``q, k, v``: ``(B, T, hd)`` of one query head and its KV head."""
    scores = _mm(q, jnp.swapaxes(k, 1, 2), operand_dtype) / math.sqrt(
        q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return _mm(probs, v, operand_dtype)


def attention(p, x, arch, operand_dtype=None, head_at_a_time=False):
    bsz, t, d = x.shape
    heads, kv_heads = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = arch.get("head_dim") or d // heads
    mm = functools.partial(_mm, operand_dtype=operand_dtype)
    q = rms_norm(mm(x, p["wq"]), p["q_norm"], _eps(arch))
    k = rms_norm(mm(x, p["wk"]), p["k_norm"], _eps(arch))
    q = q.reshape(bsz, t, heads, hd)
    k = k.reshape(bsz, t, kv_heads, hd)
    v = mm(x, p["wv"]).reshape(bsz, t, kv_heads, hd)
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    group = heads // kv_heads
    if head_at_a_time:
        # the same head function, one head live at a time (by_layer)
        head = jax.checkpoint(
            lambda h: _one_head(q[:, :, h], k[:, :, h // group],
                                v[:, :, h // group], seen, operand_dtype))
        out = jnp.moveaxis(jax.lax.map(head, jnp.arange(heads)), 0, 2)
    else:
        out = jnp.stack(
            [_one_head(q[:, :, h], k[:, :, h // group], v[:, :, h // group],
                       seen, operand_dtype) for h in range(heads)], axis=2)
    return mm(out.reshape(bsz, t, heads * hd), p["wo"])


# -- the feed-forward, the block, the model ------------------------------------

def swiglu(p, x, operand_dtype=None):
    mm = functools.partial(_mm, operand_dtype=operand_dtype)
    return mm(jax.nn.silu(mm(x, p["w_gate"])) * mm(x, p["w_up"]), p["w_down"])


def layer(p, x, arch, kind, operand_dtype=None, at_a_time=False,
          delta_term=True):
    """One block: the mixer ``kind`` names, then the SwiGLU, each through its
    norm on the way OUT. ``at_a_time``: heads and blocks of the recurrence
    one at a time (``loss_and_grad_by_layer``)."""
    if kind == "linear_attention":
        x = x + rms_norm(
            linear_attention(p, x, arch, operand_dtype, blocks=at_a_time,
                             delta_term=delta_term),
            p["linattn_norm"], _eps(arch))
    elif kind == "full_attention":
        x = x + rms_norm(attention(p, x, arch, operand_dtype, at_a_time),
                         p["attn_norm"], _eps(arch))
    else:
        raise ValueError(
            f"layer type {kind!r}: have linear_attention, full_attention")
    return x + rms_norm(swiglu(p, x, operand_dtype), p["ffn_norm"], _eps(arch))


def head_loss(params, x, targets, arch, operand_dtype=None):
    y = rms_norm(x, params["final_norm"], _eps(arch))
    table = (params["Embed_0"]["embedding"]
             if arch.get("tie_word_embeddings") else params["head"])
    logits = _mm(y, table.T, operand_dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked), logits


def forward(params, tokens, arch, operand_dtype=None, delta_term=True):
    """Hidden states before the final norm, ``(B, T, d)``."""
    with jax.default_matmul_precision("highest"):
        x = params["Embed_0"]["embedding"][tokens]
        for l in range(arch["num_hidden_layers"]):
            x = layer(params[f"Block_{l}"], x, arch, layer_kind(arch, l),
                      operand_dtype, delta_term=delta_term)
        return x


def logits(params, tokens, arch, **kw):
    with jax.default_matmul_precision("highest"):
        x = forward(params, tokens, arch, **kw)
        return head_loss(params, x, jnp.zeros_like(tokens), arch,
                         kw.get("operand_dtype"))[1]


def loss(params, tokens, targets, arch, **kw):
    with jax.default_matmul_precision("highest"):
        x = forward(params, tokens, arch, **kw)
        return head_loss(params, x, targets, arch, kw.get("operand_dtype"))[0]


def loss_and_grad(params, tokens, targets, arch, **kw):
    return jax.value_and_grad(loss)(params, tokens, targets, arch, **kw)


# -- the same, a layer at a time ---------------------------------------------

def loss_and_grad_by_layer(params, tokens, targets, arch, experts_held=None,
                           expert_offset=0, choices=None, operand_dtype=None,
                           to_host=False):
    """``loss_and_grad`` with bounded memory: forward keeping each layer's
    input, then each layer's vector-Jacobian product as its own jitted
    program (one a layer kind). ``to_host`` moves each layer's gradient to
    the host as it is made. Returns ``(loss, grads, own_choices)``, the last
    None a layer: the model has no expert, and ``experts_held``,
    ``expert_offset`` and ``choices`` are read by nothing."""
    n = arch["num_hidden_layers"]
    fetch = jax.device_get if to_host else (lambda tree: tree)
    kinds = [layer_kind(arch, l) for l in range(n)]

    def run(p, x, kind):
        return layer(p, x, arch, kind, operand_dtype, at_a_time=True)

    @functools.partial(jax.jit, static_argnums=(2,))
    def run_layer(p, x, kind):
        with jax.default_matmul_precision("highest"):
            return run(p, x, kind)

    @functools.partial(jax.jit, static_argnums=(3,))
    def pull_layer(p, x, dx_out, kind):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda p_, x_: run(p_, x_, kind), p, x)
            return vjp(dx_out)

    @jax.jit
    def top(params_top, x, targets):
        with jax.default_matmul_precision("highest"):
            value, vjp, _ = jax.vjp(
                lambda p_, x_: head_loss(p_, x_, targets, arch, operand_dtype),
                params_top, x, has_aux=True)
            return value, vjp(jnp.ones((), F32))

    table = params["Embed_0"]["embedding"]
    inputs = [table[tokens]]
    for l in range(n):
        inputs.append(run_layer(params[f"Block_{l}"], inputs[-1], kinds[l]))
    top_params = {name: v for name, v in params.items()
                  if not name.startswith("Block_")}
    value, (top_grads, dx) = top(top_params, inputs.pop(), targets)
    grads = dict(fetch(top_grads))
    for l in reversed(range(n)):
        dp, dx = pull_layer(params[f"Block_{l}"], inputs.pop(), dx, kinds[l])
        grads[f"Block_{l}"] = fetch(dp)
    embed_grad = jnp.zeros_like(table).at[tokens].add(dx)
    grads["Embed_0"] = {"embedding": fetch(
        embed_grad + grads["Embed_0"]["embedding"])}
    return value, grads, [None] * n
