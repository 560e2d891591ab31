"""The benchmark's copy of ``mpit_tpu/models/reference_zaya.py``: the plain
reference the cell ``zaya1_sync_1chip_8k`` is checked against. Kept here so
that the comparison that decides ``correct`` lives with the benchmark and does
not move when the program's file does; ``tests/benchmark`` holds the two to
the same numbers on a seed. Everything below this paragraph is that file's
text, docstring included.

Plain reference for the ``zaya`` family ``TrainConfig.arch`` describes
(``layer_types`` of ``hybrid``: compressed convolutional attention, then a
top-k sparse-expert layer routed by an MLP whose state passes from layer to
layer).

The layer equations written out in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: the value shift on the normed
input itself, both convolutions tap by tap, a dense causal mask, a Python
loop over experts computed on every token, no flax, no kernel, no sorting, no
rematerialisation policy of the program's. It shares no code with
``models/transformer.py``, ``models/arch.py`` or ``ops/``; what it shares is
the parameter tree's names, so that both can be handed the same weights:

    Embed_0/embedding (V, d)   final_norm (d,)   head (V, d) where untied
    Block_l/cca_norm, ffn_norm (d,)
       cca_q (d, H_q d_h)  cca_k (d, H_kv d_h)  cca_o (H_q d_h, d)
       cca_v_now, cca_v_back (d, H_kv d_h / 2)
       cca_conv0 ((H_q + H_kv) d_h, K0)  cca_conv1 (H_q + H_kv, d_h, d_h, K1)
       cca_temperature (H_q,)
       cca_res_scale, cca_out_scale, ffn_res_scale, ffn_out_scale (d,)
       router_down (d, R)  router_w1, router_w2 (R, R)  router_w3 (R, E)
       router_eda ()
       moe_w_gate, moe_w_up (E_held, d, m)  moe_w_down (E_held, m, d)

``arch`` is the source's ``config.json`` as a dict, with ``num_hidden_layers``
the layers present. One chip's share of the experts (the deployment's
experts over its chips) is given as ``experts_held`` and
``expert_offset``: the router scores all ``E`` experts and takes its top-k over all of them, and only the
chosen experts in ``[offset, offset + held)`` add to the result. With
``experts_held=None`` every expert is held.

Equations (sources: Figliolia et al., "Compressed Convolutional Attention",
arXiv:2510.04476, for the attention; Zyphra's ZAYA1 technical report,
November 2025, for the router and the residual scaling; the forms the config
does not fix are assumed, as ``benchmark/configs/zaya1-8b.json`` lists).
RMSNorm is ``y / sqrt(mean(y²) + rms_norm_eps) * w``. Every layer, ``x`` of
width ``d``, ``H_q`` query and ``H_kv`` key heads of ``d_h``, ``g(i) = i //
(H_q / H_kv)`` the key head of query head ``i``:

- CCA: ``h = RMSNorm(x; cca_norm)``; ``q0 = h W_q``, ``k0 = h W_k`` (the
  latent; nothing projects them up); ``v_t = [h_t W_v_now ; h_{t-1}
  W_v_back]`` a head, ``h_{-1} = 0``; ``u = [q0 ; k0]`` (``H_q + H_kv``
  heads), ``c_t = sum_j a[:, j] u_{t-j}`` (``K0`` taps, a channel each),
  ``z_t = sum_j c_{t-j} B[:, :, :, j]`` (``K1`` taps, a ``d_h x d_h`` matrix
  a head), zeros before the first token; ``q_i = z_i + (q0_i + k0_g(i)) /
  2``, ``k_g = z_g + (mean_{g(i) = g} q0_i + k0_g) / 2``; ``q_i <- sqrt(d_h)
  tau_i q_i / sqrt(|q_i|² + 1e-6)``, ``k_g <- sqrt(d_h) k_g / sqrt(|k_g|² +
  1e-6)``; rotary on the first ``partial_rotary_factor · d_h`` channels of
  each head (rotate-half pairs ``i`` and ``i + r/2``, angle ``p ·
  rope_theta^(-2i/r)``); scores ``q_i k_g(i)ᵀ / sqrt(d_h)``, key ``j``
  visible to query ``p`` iff ``j <= p``; softmax; ``o W_o``; ``x <-
  cca_res_scale x + cca_out_scale (o W_o)``.
- Experts: ``h = RMSNorm(x; ffn_norm)``; ``r_l = h W_down + gamma_l r_{l-1}``
  (``router_eda``; ``r_{-1} = 0``); ``logits = W_3 gelu(W_2 gelu(W_1 r_l))``,
  exact GELU; ``p = softmax(logits)`` over all ``E``; the top-k of ``p``;
  weights the chosen ``p`` themselves, not renormalised; ``sum_e w_e
  SwiGLU_e(h)``, ``SwiGLU(h) = W_down (SiLU(h W_gate) * h W_up)``; ``x <-
  ffn_res_scale x + ffn_out_scale out``. With ``moe_routing_no_grad`` the
  weights ``w`` are constants of the backward pass.
- The first ``x`` is the token's row of the embedding times
  ``embedding_multiplier`` (this repo's key; 1 where absent); the loss is
  the mean over positions of the cross-entropy of ``final_norm(x)
  Wheadᵀ`` (``Whead`` the embedding where tied, not multiplied), plus
  ``router_aux_loss_coef`` times the mean over layers of ``E · sum_e f_e
  P_e``: ``f_e`` the share of the layer's (token, choice) pairs that chose
  ``e`` over the tokens, ``P_e`` the mean over tokens of ``p_e``.

``choices`` (a list, one ``(tokens, k)`` int array a layer) replaces the
reference's own top-k by given ones; the weights are still the reference's
probabilities at those indices. A comparison with a system that computes in
bfloat16 uses it, because the top-k of near-tied scores is discrete.

``operand_dtype`` rounds both operands of every product outside the router to
that dtype first (the projections, the per-head convolution, the attention's
two products, the experts, the head; the products still accumulate in
float32): what the same equations give in a lower precision, which a
comparison's tolerances must tell apart from the system.

``loss_and_grad_by_layer`` is the same loss and gradient taken a layer at a
time, so that the published widths fit a chip's memory: each layer's input
and router state are kept, each layer's vector-Jacobian product is its own
program, attention runs a query head at a time and, inside it, a block of
queries at a time, and the experts run one at a time (the same head, block
and expert functions, under ``jax.lax.map``; no arithmetic changes).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32

#: queries a block where attention is taken a block at a time
QUERY_BLOCK = 1024


def _round(a, operand_dtype):
    return a if operand_dtype is None else a.astype(operand_dtype).astype(F32)


def _mm(a, b, operand_dtype=None):
    return jnp.matmul(_round(a, operand_dtype), _round(b, operand_dtype))


def _eps(arch):
    return arch.get("rms_norm_eps", 1e-5)


def rms_norm(y, scale, eps):
    return y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * scale


def back(a, steps):
    """``a_{t - steps}`` along axis 1 (time), zeros before the start."""
    if steps == 0:
        return a
    zeros = jnp.zeros((a.shape[0], steps, *a.shape[2:]), a.dtype)
    return jnp.concatenate([zeros, a[:, :-steps]], axis=1)


def _unit(a):
    return a / jnp.sqrt(jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)


# -- compressed convolutional attention -----------------------------------------

def rotary(a, arch):
    """Rotate-half rotary on the first ``partial_rotary_factor · d_h``
    channels of ``a`` ``(B, T, H, d_h)``; the angles are made in float64."""
    rope = arch["rope_parameters"]["hybrid"]
    t, d = a.shape[1], a.shape[-1]
    r = int(d * rope.get("partial_rotary_factor", 1.0))
    inv_freq = 1.0 / rope["rope_theta"] ** (np.arange(0, r, 2) / r)
    angles = np.outer(np.arange(t, dtype=np.float64), inv_freq)
    cos = jnp.asarray(np.cos(angles), F32)[None, :, None, :]
    sin = jnp.asarray(np.sin(angles), F32)[None, :, None, :]
    first, second, rest = a[..., :r // 2], a[..., r // 2:r], a[..., r:]
    return jnp.concatenate([first * cos - second * sin,
                            second * cos + first * sin, rest], axis=-1)


def _one_head(q, k, v, start, operand_dtype):
    """Queries ``start ..`` of one head ``(B, Q, d_h)`` over all keys and
    values ``(B, T, d_h)``."""
    scores = _mm(q, jnp.swapaxes(k, 1, 2), operand_dtype) / math.sqrt(
        q.shape[-1])
    rows = start + jnp.arange(q.shape[1])[:, None]
    seen = jnp.arange(k.shape[1])[None, :] <= rows
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return _mm(probs, v, operand_dtype)


def attention(q, k, v, operand_dtype=None, at_a_time=False):
    """``q`` ``(B, T, H_q, d_h)``; ``k``, ``v`` ``(B, T, H_kv, d_h)``."""
    bsz, t, heads, d = q.shape
    group = heads // k.shape[2]
    if not at_a_time:
        return jnp.stack(
            [_one_head(q[:, :, i], k[:, :, i // group], v[:, :, i // group],
                       0, operand_dtype) for i in range(heads)], axis=2)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    def head(i):
        q_i = jnp.take(q, i, axis=2)
        k_g, v_g = (jnp.take(a, i // group, axis=2) for a in (k, v))
        blocks = q_i.reshape(bsz, t // block, block, d)
        out = jax.lax.map(
            lambda j: _one_head(jnp.take(blocks, j, axis=1), k_g, v_g,
                                j * block, operand_dtype),
            jnp.arange(t // block))
        return jnp.moveaxis(out, 0, 1).reshape(bsz, t, d)

    out = jax.lax.map(jax.checkpoint(head), jnp.arange(heads))
    return jnp.moveaxis(out, 0, 2)


def cca(p, x, arch, operand_dtype=None, at_a_time=False):
    """The CCA sublayer's output ``x``."""
    bsz, t, _ = x.shape
    heads, kv_heads = arch["num_attention_heads"], arch["num_key_value_heads"]
    d = arch["head_dim"]
    mm = functools.partial(_mm, operand_dtype=operand_dtype)
    h = rms_norm(x, p["cca_norm"], _eps(arch))
    q0 = mm(h, p["cca_q"]).reshape(bsz, t, heads, d)
    k0 = mm(h, p["cca_k"]).reshape(bsz, t, kv_heads, d)
    v = jnp.concatenate([
        mm(h, p["cca_v_now"]).reshape(bsz, t, kv_heads, d // 2),
        mm(back(h, 1), p["cca_v_back"]).reshape(bsz, t, kv_heads, d // 2),
    ], axis=-1)
    u = jnp.concatenate([q0, k0], axis=2)
    taps0 = p["cca_conv0"].reshape(heads + kv_heads, d, -1)
    c = jnp.zeros_like(u)
    for j in range(taps0.shape[-1]):
        c = c + taps0[..., j] * back(u, j)
    z = jnp.zeros_like(u)
    for j in range(p["cca_conv1"].shape[-1]):
        z = z + jnp.einsum(
            "bthd,hde->bthe", _round(back(c, j), operand_dtype),
            _round(p["cca_conv1"][..., j], operand_dtype))
    group = heads // kv_heads
    q = jnp.stack([z[:, :, i] + (q0[:, :, i] + k0[:, :, i // group]) / 2
                   for i in range(heads)], axis=2)
    k = jnp.stack([
        z[:, :, heads + g]
        + (q0[:, :, g * group:(g + 1) * group].mean(2) + k0[:, :, g]) / 2
        for g in range(kv_heads)], axis=2)
    q = math.sqrt(d) * p["cca_temperature"][:, None] * _unit(q)
    k = math.sqrt(d) * _unit(k)
    o = attention(rotary(q, arch), rotary(k, arch), v, operand_dtype,
                  at_a_time)
    y = mm(o.reshape(bsz, t, heads * d), p["cca_o"])
    return p["cca_res_scale"] * x + p["cca_out_scale"] * y


# -- the expert sublayer ---------------------------------------------------------

def router_logits(p, h, r_before):
    """The router's state ``r_l`` and its logits, never rounded (float32 in
    the system)."""
    r = h @ p["router_down"] + p["router_eda"] * r_before
    hidden = jax.nn.gelu(r @ p["router_w1"], approximate=False)
    hidden = jax.nn.gelu(hidden @ p["router_w2"], approximate=False)
    return r, hidden @ p["router_w3"]


def swiglu(h, w_gate, w_up, w_down, operand_dtype=None):
    mm = functools.partial(_mm, operand_dtype=operand_dtype)
    return mm(jax.nn.silu(mm(h, w_gate)) * mm(h, w_up), w_down)


def own_choice(probs, k):
    return jax.lax.top_k(probs, k)[1]


def balance(probs, choice):
    """``E · sum_e f_e P_e``: ``f_e`` the share of tokens that chose ``e``
    (``k`` under uniform routing), ``P_e`` the mean probability."""
    experts = probs.shape[-1]
    tokens = choice.size // choice.shape[-1]
    share = jnp.bincount(choice.reshape(-1), length=experts) / tokens
    return experts * jnp.sum(share * probs.reshape(-1, experts).mean(0))


def experts(p, x, r_before, arch, experts_held=None, expert_offset=0,
            choice=None, operand_dtype=None, at_a_time=False):
    """The expert sublayer: ``(x, r_l, balance term, own top-k)``."""
    h = rms_norm(x, p["ffn_norm"], _eps(arch))
    r, logits = router_logits(p, h, r_before)
    probs = jax.nn.softmax(logits, axis=-1)
    k = arch["num_experts_per_tok"]
    own = own_choice(probs, k)
    choice = own if choice is None else jnp.asarray(choice).reshape(own.shape)
    weights = jnp.take_along_axis(probs, choice, axis=-1)  # not renormalised
    if arch.get("moe_routing_no_grad"):  # constants of the backward pass
        weights = jax.lax.stop_gradient(weights)
    held = p["moe_w_up"].shape[0] if experts_held is None else experts_held

    def expert(e, w_gate, w_up, w_down):
        w_e = jnp.where(choice == expert_offset + e, weights, 0.0).sum(-1)
        return w_e[..., None] * swiglu(h, w_gate, w_up, w_down, operand_dtype)

    stacked = [p[name][:held] for name in ("moe_w_gate", "moe_w_up",
                                           "moe_w_down")]
    if at_a_time:
        out = jax.lax.map(lambda ew: jax.checkpoint(expert)(*ew),
                          (jnp.arange(held), *stacked)).sum(0)
    else:
        out = jnp.zeros_like(x)
        for e in range(held):
            out = out + expert(e, *(w[e] for w in stacked))
    x = p["ffn_res_scale"] * x + p["ffn_out_scale"] * out
    return x, r, balance(probs, choice), own


# -- the model -----------------------------------------------------------------

def layer(p, x, r_before, arch, experts_held=None, expert_offset=0,
          choice=None, operand_dtype=None, at_a_time=False):
    """One layer: ``(x, r_l, balance term, own top-k)``."""
    x = cca(p, x, arch, operand_dtype, at_a_time)
    return experts(p, x, r_before, arch, experts_held, expert_offset, choice,
                   operand_dtype, at_a_time)


def head_loss(params, x, targets, arch, operand_dtype=None):
    y = rms_norm(x, params["final_norm"], _eps(arch))
    table = (params["Embed_0"]["embedding"]
             if arch.get("tie_word_embeddings") else params["head"])
    logits = _mm(y, table.T, operand_dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked), logits


def _choice(choices, l):
    return None if choices is None else choices[l]


def _embed(params, tokens, arch):
    return params["Embed_0"]["embedding"][tokens] * arch.get(
        "embedding_multiplier", 1.0)


def _state0(tokens, arch):
    return jnp.zeros((*tokens.shape, arch["router_hidden_size"]), F32)


def forward(params, tokens, arch, experts_held=None, expert_offset=0,
            choices=None, operand_dtype=None, with_balance=False):
    """Hidden states before the final norm, ``(B, T, d)``; ``with_balance``,
    also the layers' load-balancing terms."""
    with jax.default_matmul_precision("highest"):
        x = _embed(params, tokens, arch)
        r, terms = _state0(tokens, arch), []
        for l in range(arch["num_hidden_layers"]):
            x, r, term, _ = layer(params[f"Block_{l}"], x, r, arch,
                                  experts_held, expert_offset,
                                  _choice(choices, l), operand_dtype)
            terms.append(term)
        return (x, terms) if with_balance else x


def _aux(arch, terms):
    """What the load-balancing terms add to the loss."""
    coef = arch.get("router_aux_loss_coef", 0.0)
    return coef * sum(terms) / len(terms) if coef and terms else 0.0


def logits(params, tokens, arch, **kw):
    with jax.default_matmul_precision("highest"):
        x = forward(params, tokens, arch, **kw)
        return head_loss(params, x, jnp.zeros_like(tokens), arch,
                         kw.get("operand_dtype"))[1]


def loss(params, tokens, targets, arch, **kw):
    with jax.default_matmul_precision("highest"):
        x, terms = forward(params, tokens, arch, with_balance=True, **kw)
        return head_loss(params, x, targets, arch,
                         kw.get("operand_dtype"))[0] + _aux(arch, terms)


def loss_and_grad(params, tokens, targets, arch, **kw):
    return jax.value_and_grad(loss)(params, tokens, targets, arch, **kw)


# -- the same, a layer at a time ---------------------------------------------

def loss_and_grad_by_layer(params, tokens, targets, arch, experts_held=None,
                           expert_offset=0, choices=None, operand_dtype=None,
                           to_host=False):
    """``loss_and_grad`` with bounded memory: forward keeping each layer's
    input and router state, then each layer's vector-Jacobian product as its
    own jitted program. Also returns the reference's own top-k a layer (taken
    on the way, on the hidden states the given routing makes). ``to_host``
    moves each layer's gradient to the host as it is made. Returns ``(loss,
    grads, own_choices)``."""
    n = arch["num_hidden_layers"]
    fetch = jax.device_get if to_host else (lambda tree: tree)
    k = arch["num_experts_per_tok"]
    # d loss / d (a layer's load-balancing term)
    per_term = arch.get("router_aux_loss_coef", 0.0) / n

    def run(p, x, r, choice):
        return layer(p, x, r, arch, experts_held, expert_offset, choice,
                     operand_dtype, at_a_time=True)

    @jax.jit
    def run_layer(p, x, r, choice):
        with jax.default_matmul_precision("highest"):
            return run(p, x, r, choice)

    @jax.jit
    def pull_layer(p, x, r, choice, dx_out, dr_out):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda p_, x_, r_: run(p_, x_, r_, choice)[:3],
                             p, x, r)
            return vjp((dx_out, dr_out, jnp.asarray(per_term, F32)))

    @jax.jit
    def top(params_top, x, targets):
        with jax.default_matmul_precision("highest"):
            value, vjp, _ = jax.vjp(
                lambda p_, x_: head_loss(p_, x_, targets, arch, operand_dtype),
                params_top, x, has_aux=True)
            return value, vjp(jnp.ones((), F32))

    def chosen(l):
        given = _choice(choices, l)
        return None if given is None else jnp.asarray(given).reshape(
            *tokens.shape, k)

    table = params["Embed_0"]["embedding"]
    inputs, states = [_embed(params, tokens, arch)], [_state0(tokens, arch)]
    own, terms = [], []
    for l in range(n):
        x, r, term, mine = run_layer(params[f"Block_{l}"], inputs[-1],
                                     states[-1], chosen(l))
        inputs.append(x)
        states.append(r)
        terms.append(term)
        own.append(mine)
    top_params = {name: v for name, v in params.items()
                  if not name.startswith("Block_")}
    value, (top_grads, dx) = top(top_params, inputs.pop(), targets)
    value = value + _aux(arch, terms)
    grads = dict(fetch(top_grads))
    dr = jnp.zeros_like(states.pop())  # the last state is read by nothing
    for l in reversed(range(n)):
        dp, dx, dr = pull_layer(params[f"Block_{l}"], inputs.pop(),
                                states.pop(), chosen(l), dx, dr)
        grads[f"Block_{l}"] = fetch(dp)
    embed_grad = jnp.zeros_like(table).at[tokens].add(
        dx * arch.get("embedding_multiplier", 1.0))
    grads["Embed_0"] = {"embedding": fetch(
        embed_grad + grads["Embed_0"]["embedding"])}
    return value, grads, own
