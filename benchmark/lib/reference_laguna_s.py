"""The benchmark's copy of ``mpit_tpu/models/reference_lm.py``: the plain
reference the cell ``laguna_s21_sync_1chip_8k`` is checked against. Kept here
so that the comparison that decides ``correct`` lives with the benchmark and
does not move when the program's file does; ``tests/benchmark`` holds the two
to the same numbers on a seed. Everything below this paragraph is that file's
text, docstring included.

Plain reference for the architectures ``TrainConfig.arch`` describes.

The layer equations of a decoder with RMSNorm, rotary positions (plain,
partial and YaRN-scaled), grouped KV heads whose query-head count differs by
layer, sliding-window and full causal attention, a per-head sigmoid output
gate, SwiGLU feed-forwards and sparse experts (softmax scores, top-k
renormalised and scaled, a shared expert), written out in ``jax.numpy`` and
float32 under ``jax.default_matmul_precision("highest")``: dense masks, a
Python loop over experts, no flax, no kernel, no sorting, no
rematerialisation. It shares no code with ``models/transformer.py``,
``models/arch.py`` or ``ops/``; what it shares is the parameter tree's
names, so that both can be handed the same weights:

    Embed_0/embedding (V, d)            final_norm (d,)      head (V, d)
    Block_l/attn_norm, ffn_norm (d,)    wq (d, H_l·hd)  wk, wv (d, H_kv·hd)
    Block_l/wg (d, H_l)  wo (H_l·hd, d)
    dense:  w_gate, w_up (d, f)  w_down (f, d)
    sparse: moe_router (d, E)  moe_w_gate, moe_w_up (E_held, d, m)
            moe_w_down (E_held, m, d)  shared_w_gate, shared_w_up (d, s)
            shared_w_down (s, d)

``arch`` is the source's ``config.json`` as a dict, with
``num_hidden_layers`` the layers present. One chip's share of the experts
(the model-configs guide, section 4) is given as ``experts_held`` and
``expert_offset``: the router scores all ``E`` experts and takes its top-k
over all of them, and only the chosen experts in ``[offset, offset + held)``
add to the result, which is what that chip computes; the shared expert is
added once. With ``experts_held=None`` every expert is held.

Equations, for the normed input ``y`` of a sub-layer (``x`` the residual):

- ``h = x + Attn(RMSNorm(x))``, ``x' = h + FFN(RMSNorm(h))``; RMSNorm is
  ``y / sqrt(mean(y²) + eps) * scale``.
- Attention: ``q = y Wq`` as ``H_l`` heads, ``k, v`` as ``H_kv`` heads, query
  head ``h`` reads KV head ``h // (H_l / H_kv)``; rotary on the first
  ``partial_rotary_factor · hd`` dims of ``q`` and ``k``; scores
  ``q kᵀ / sqrt(hd)``, key ``j`` visible to query ``i`` iff ``j <= i`` and,
  on a sliding layer, ``i - window < j``; softmax; the head's output times
  ``sigmoid(y Wg)[:, h]``; then ``Wo``.
- Dense FFN: ``W_down(silu(W_gate y) * (W_up y))``.
- Sparse FFN: ``s = softmax(y W_r)``; the ``k`` largest; ``w = s_top /
  sum(s_top) * scaling``; ``Shared(y) + sum_e w_e E_e(y)``, every expert a
  SwiGLU. With ``moe_routing_no_grad`` in ``arch`` the weights ``w`` are
  constants of the backward pass.
- Loss: mean over positions of the cross-entropy of ``final_norm(x) Wheadᵀ``,
  plus ``router_aux_loss_coef`` (0 where ``arch`` has none) times the mean
  over the sparse layers of the load-balancing term ``E · sum_e f_e P_e``:
  ``f_e`` the share of the layer's tokens that chose expert ``e``, ``P_e``
  the mean of ``s_e`` over its tokens, over all ``E`` experts the router
  scores (``k`` under uniform routing; transformers'
  ``load_balancing_loss_func``, taken a layer).

``choices`` (a list, one ``(tokens, k)`` int array or None a layer) replaces
the reference's own top-k indices by given ones; the weights are still the
reference's scores at those indices. A comparison with a system that computes
in bfloat16 uses it, because the top-k of near-tied scores is discrete.

``operand_dtype`` rounds both operands of every matrix product to that dtype
first (the products still accumulate in float32): what the same equations
give in a lower precision, which a comparison's tolerances must tell apart
from the system.

``loss_and_grad_by_layer`` is the same loss and gradient taken a layer at a
time, so that the published widths fit a chip's memory: each layer's input
is kept, each layer's vector-Jacobian product is its own program, and
attention runs a query head at a time and the experts one at a time (the
same head and expert functions, under ``jax.lax.map``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _mm(a, b, operand_dtype=None):
    if operand_dtype is not None:
        a = a.astype(operand_dtype).astype(F32)
        b = b.astype(operand_dtype).astype(F32)
    return jnp.matmul(a, b)


def rms_norm(y, scale, eps):
    return y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * scale


# -- rotary positions --------------------------------------------------------

def rotary_cos_sin(params: dict, head_dim: int, length: int):
    """``cos`` and ``sin`` of shape ``(length, rotary_dim)`` for one entry
    of ``rope_parameters``, after transformers' ``_compute_default_rope_
    parameters`` and ``_compute_yarn_parameters``."""
    dim = int(head_dim * params.get("partial_rotary_factor", 1.0))
    base = float(params["rope_theta"])
    exponents = np.arange(0, dim, 2, dtype=np.float64) / dim
    inv_freq = 1.0 / base ** exponents
    attention_factor = 1.0
    if params.get("rope_type", "default") == "yarn":
        factor = float(params["factor"])
        original = params["original_max_position_embeddings"]
        attention_factor = params.get("attention_factor")
        if attention_factor is None:
            attention_factor = 0.1 * math.log(factor) + 1.0

        def correction_dim(rotations):
            return (dim * math.log(original / (rotations * 2 * math.pi))
                    / (2 * math.log(base)))

        low = max(math.floor(correction_dim(params.get("beta_fast", 32))), 0)
        high = min(math.ceil(correction_dim(params.get("beta_slow", 1))),
                   dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
        extrapolation = 1.0 - ramp  # share of the published frequency
        inv_freq = (inv_freq / factor * (1 - extrapolation)
                    + inv_freq * extrapolation)
    angles = np.outer(np.arange(length, dtype=np.float64), inv_freq)
    angles = np.concatenate([angles, angles], axis=1)
    return (jnp.asarray(np.cos(angles) * attention_factor, F32),
            jnp.asarray(np.sin(angles) * attention_factor, F32))


def rotate(x, cos, sin):
    """``x``: ``(B, T, H, hd)``; the first ``cos.shape[1]`` dims of each
    head turn (dim ``i`` pairs with dim ``i + rotary/2``)."""
    r = cos.shape[1]
    xr, rest = x[..., :r], x[..., r:]
    half = jnp.concatenate([-xr[..., r // 2:], xr[..., : r // 2]], axis=-1)
    xr = xr * cos[None, :, None, :] + half * sin[None, :, None, :]
    return jnp.concatenate([xr, rest], axis=-1)


# -- the layer ---------------------------------------------------------------

def _layer_kind(arch: dict, l: int):
    kinds = arch.get("layer_types")
    kind = kinds[l] if kinds else "full_attention"
    per_layer = arch.get("num_attention_heads_per_layer")
    heads = per_layer[l] if per_layer else arch["num_attention_heads"]
    ffns = arch.get("mlp_layer_types")
    return kind, int(heads), (ffns[l] if ffns else "dense")


def _visible(t: int, window):
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    return seen


def _one_head(q, k, v, seen, operand_dtype):
    """``q, k, v``: ``(B, T, hd)`` of one query head and its KV head."""
    scores = _mm(q, jnp.swapaxes(k, 1, 2), operand_dtype) / math.sqrt(
        q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return _mm(probs, v, operand_dtype)


def attention(p, y, arch, l, operand_dtype=None, head_at_a_time=False):
    kind, heads, _ = _layer_kind(arch, l)
    b, t, _ = y.shape
    hd, kv_heads = arch["head_dim"], arch["num_key_value_heads"]
    mm = functools.partial(_mm, operand_dtype=operand_dtype)
    q = mm(y, p["wq"]).reshape(b, t, heads, hd)
    k = mm(y, p["wk"]).reshape(b, t, kv_heads, hd)
    v = mm(y, p["wv"]).reshape(b, t, kv_heads, hd)
    ropes = arch["rope_parameters"]
    cos, sin = rotary_cos_sin(ropes.get(kind, ropes), hd, t)
    q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    window = arch["sliding_window"] if kind == "sliding_attention" else None
    seen = _visible(t, window)
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
    if arch.get("gating") == "per-head":
        out = out * jax.nn.sigmoid(mm(y, p["wg"]))[..., None]
    return mm(out.reshape(b, t, heads * hd), p["wo"])


def swiglu(y, w_gate, w_up, w_down, operand_dtype=None):
    mm = functools.partial(_mm, operand_dtype=operand_dtype)
    return mm(jax.nn.silu(mm(y, w_gate)) * mm(y, w_up), w_down)


def router_scores(p, y):
    return jax.nn.softmax(jnp.matmul(y, p["moe_router"]), axis=-1)


def balance(scores, choice):
    """``E · sum_e f_e P_e`` of one layer's scores ``(..., E)`` and chosen
    experts ``(..., k)``; the gradient passes through ``P`` alone."""
    experts = scores.shape[-1]
    tokens = choice.size // choice.shape[-1]
    share = jnp.bincount(choice.reshape(-1), length=experts) / tokens
    return experts * jnp.sum(share * scores.reshape(-1, experts).mean(0))


def sparse_ffn(p, y, arch, experts_held=None, expert_offset=0, choice=None,
               operand_dtype=None, expert_at_a_time=False,
               with_balance=False):
    """``y``: ``(B, T, d)``. Returns the held experts' part plus the shared
    expert (and, ``with_balance``, the layer's load-balancing term). The
    router is never rounded (it is float32 in the system)."""
    scores = router_scores(p, y)
    k = arch["num_experts_per_tok"]
    if choice is None:
        _, choice = jax.lax.top_k(scores, k)
    else:
        choice = jnp.asarray(choice).reshape(*y.shape[:-1], k)
    top = jnp.take_along_axis(scores, choice, axis=-1)
    weights = top / top.sum(-1, keepdims=True) * arch.get(
        "moe_routed_scaling_factor", 1.0)
    if arch.get("moe_routing_no_grad"):  # constants of the backward pass
        weights = jax.lax.stop_gradient(weights)
    held = p["moe_w_gate"].shape[0] if experts_held is None else experts_held
    def expert(e, w_gate, w_up, w_down):
        w_e = jnp.where(choice == expert_offset + e, weights, 0.0).sum(-1)
        return w_e[..., None] * swiglu(y, w_gate, w_up, w_down, operand_dtype)

    stacked = [p[name][:held] for name in
               ("moe_w_gate", "moe_w_up", "moe_w_down")]
    if expert_at_a_time:
        # the same expert function, one expert live at a time (by_layer)
        out = jax.lax.map(lambda ew: jax.checkpoint(expert)(*ew),
                          (jnp.arange(held), *stacked)).sum(0)
    else:
        out = jnp.zeros_like(y)
        for e in range(held):
            out = out + expert(e, *(w[e] for w in stacked))
    if arch.get("shared_expert_intermediate_size"):
        out = out + swiglu(y, p["shared_w_gate"], p["shared_w_up"],
                           p["shared_w_down"], operand_dtype)
    return (out, balance(scores, choice)) if with_balance else out


def layer(p, x, arch, l, experts_held=None, expert_offset=0, choice=None,
          operand_dtype=None, head_at_a_time=False, with_balance=False):
    """The layer's output; ``with_balance``, also its load-balancing term
    (None on a dense layer)."""
    eps = arch.get("rms_norm_eps", 1e-6)
    h = x + attention(p, rms_norm(x, p["attn_norm"], eps), arch, l,
                      operand_dtype, head_at_a_time)
    y = rms_norm(h, p["ffn_norm"], eps)
    if _layer_kind(arch, l)[2] == "dense":
        out = h + swiglu(y, p["w_gate"], p["w_up"], p["w_down"],
                         operand_dtype)
        return (out, None) if with_balance else out
    ffn, term = sparse_ffn(p, y, arch, experts_held, expert_offset, choice,
                           operand_dtype, expert_at_a_time=head_at_a_time,
                           with_balance=True)
    return (h + ffn, term) if with_balance else h + ffn


def head_loss(params, x, targets, arch, operand_dtype=None):
    y = rms_norm(x, params["final_norm"], arch.get("rms_norm_eps", 1e-6))
    table = (params["Embed_0"]["embedding"]
             if arch.get("tie_word_embeddings") else params["head"])
    logits = _mm(y, table.T, operand_dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, targets[..., None], axis=-1)
    return -jnp.mean(picked), logits


def _choice(choices, l):
    return None if choices is None else choices[l]


def forward(params, tokens, arch, experts_held=None, expert_offset=0,
            choices=None, operand_dtype=None, with_balance=False):
    """Hidden states before the final norm, ``(B, T, d)``; ``with_balance``,
    also the sparse layers' load-balancing terms."""
    with jax.default_matmul_precision("highest"):
        x = params["Embed_0"]["embedding"][tokens]
        terms = []
        for l in range(arch["num_hidden_layers"]):
            x, term = layer(params[f"Block_{l}"], x, arch, l, experts_held,
                            expert_offset, _choice(choices, l), operand_dtype,
                            with_balance=True)
            terms += [] if term is None else [term]
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
    input, then each layer's vector-Jacobian product as its own jitted
    program, attention a head at a time. Also returns the reference's own
    top-k a sparse layer (taken on the way, on the hidden states this
    routing gives). ``to_host`` moves each layer's gradient to the host as
    it is made. Returns ``(loss, grads, own_choices)``."""
    n = arch["num_hidden_layers"]
    fetch = jax.device_get if to_host else (lambda tree: tree)
    # layers of one kind (attention type, heads, feed-forward) are one
    # program: the first of the kind stands for all of them
    kinds = [_layer_kind(arch, l) for l in range(n)]
    first_of = [kinds.index(kind) for kind in kinds]
    k = arch["num_experts_per_tok"]

    sparse = [kind[2] == "sparse" for kind in kinds]
    # d loss / d (a sparse layer's load-balancing term)
    per_term = arch.get("router_aux_loss_coef", 0.0) / max(sum(sparse), 1)

    def run(p, x, choice, l):
        """The layer's output and its load-balancing term (0 if dense)."""
        out, term = layer(p, x, arch, l, experts_held, expert_offset, choice,
                          operand_dtype, head_at_a_time=True,
                          with_balance=True)
        return out, jnp.zeros((), F32) if term is None else term

    @functools.partial(jax.jit, static_argnums=(3,))
    def run_layer(p, x, choice, l):
        """The layer's output, its load-balancing term and, on a sparse
        layer, the reference's own top-k on this input."""
        with jax.default_matmul_precision("highest"):
            if kinds[l][2] != "sparse":
                return (*run(p, x, None, l), None)
            eps = arch.get("rms_norm_eps", 1e-6)
            h = x + attention(p, rms_norm(x, p["attn_norm"], eps), arch, l,
                              head_at_a_time=True)
            scores = router_scores(p, rms_norm(h, p["ffn_norm"], eps))
            return (*run(p, x, choice, l), jax.lax.top_k(scores, k)[1])

    @functools.partial(jax.jit, static_argnums=(4,))
    def pull_layer(p, x, choice, dx_out, l):
        with jax.default_matmul_precision("highest"):
            _, vjp = jax.vjp(lambda p_, x_: run(p_, x_, choice, l), p, x)
            return vjp((dx_out, jnp.asarray(per_term, F32)))

    @jax.jit
    def top(params_top, x, targets):
        with jax.default_matmul_precision("highest"):
            value, vjp, _ = jax.vjp(
                lambda p_, x_: head_loss(p_, x_, targets, arch, operand_dtype),
                params_top, x, has_aux=True)
            return value, vjp(jnp.ones((), F32))

    def chosen(l):
        """The layer's top-k to use: given, or (None) the reference's own."""
        given = _choice(choices, l)
        if given is None or kinds[l][2] != "sparse":
            return None
        return jnp.asarray(given).reshape(*tokens.shape, k)

    table = params["Embed_0"]["embedding"]
    inputs = [table[tokens]]
    own, terms = [], []
    for l in range(n):
        out, term, mine = run_layer(params[f"Block_{l}"], inputs[-1],
                                    chosen(l), first_of[l])
        own.append(mine)
        terms += [term] if sparse[l] else []
        inputs.append(out)
    top_params = {name: v for name, v in params.items()
                  if not name.startswith("Block_")}
    value, (top_grads, dx) = top(top_params, inputs.pop(), targets)
    value = value + _aux(arch, terms)
    grads = dict(fetch(top_grads))
    for l in reversed(range(n)):
        dp, dx = pull_layer(params[f"Block_{l}"], inputs.pop(), chosen(l),
                            dx, first_of[l])
        grads[f"Block_{l}"] = fetch(dp)
    embed_grad = jnp.zeros_like(table).at[tokens].add(dx)
    grads["Embed_0"] = {"embedding": fetch(
        embed_grad + grads["Embed_0"]["embedding"])}
    return value, grads, own

