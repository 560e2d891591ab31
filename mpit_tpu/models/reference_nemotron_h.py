"""Plain reference for the ``nemotron_h`` family ``TrainConfig.arch`` describes
(``hybrid_override_pattern``: Mamba-2, sparse-expert and attention layers,
each layer ONE mixer).

The layer equations written out in ``jax.numpy`` and float32 under
``jax.default_matmul_precision("highest")``: the Mamba-2 recurrence step by
step over the sequence (never in chunks), a dense causal mask, a Python loop
over experts, no flax, no kernel, no sorting, no rematerialisation policy of
the program's. It shares no code with ``models/transformer.py``,
``models/arch.py`` or ``ops/``; what it shares is the parameter tree's names,
so that both can be handed the same weights:

    Embed_0/embedding (V, d)        final_norm (d,)        head (V, d)
    M: Block_l/ssm_norm (d,)  in_proj (d, 2 d_in + 2 G N + H)
       conv_w (d_in + 2 G N, K)  conv_b (d_in + 2 G N,)  dt_bias, A_log, D (H,)
       gate_norm (d_in,)  out_proj (d_in, d)
    *: Block_l/attn_norm (d,)  wq (d, H_q hd)  wk, wv (d, H_kv hd)
       wo (H_q hd, d)
    E: Block_l/ffn_norm (d,)  moe_router (d, E)  moe_bias (E,)
       moe_w_up (E_held, d, m)  moe_w_down (E_held, m, d)
       shared_w_up (d, s)  shared_w_down (s, d)

``arch`` is the source's ``config.json`` as a dict, with ``num_hidden_layers``
the layers present (the first that many letters of
``hybrid_override_pattern``). One chip's share of the experts (the
model-configs guide, section 4) is given as ``experts_held`` and
``expert_offset``: the router scores all ``E`` experts and takes its top-k
over all of them, and only the chosen experts in ``[offset, offset + held)``
add to the result; the shared expert is added once. With
``experts_held=None`` every expert is held.

Equations. Every layer: ``x <- x + Mixer(RMSNorm(x; w, layer_norm_epsilon))``;
RMSNorm is ``y / sqrt(mean(y²) + eps) * w``. With ``u`` the normed input:

- ``M`` (``H`` heads of ``P`` channels, ``d_in = H P``; ``G`` groups, state
  ``N``): ``[z | xBC | dt] = u W_in`` of widths ``d_in | d_in + 2 G N | H``;
  ``xBC <- SiLU(conv(xBC))``, ``conv`` depthwise and causal with kernel ``K``,
  ``out_t = b + sum_k w[:, k] in_{t - (K-1) + k}``; ``[x | B | C] = xBC``;
  ``dt <- softplus(dt + dt_bias)``, ``A = -exp(A_log)``; for head ``h`` of
  group ``h // (H / G)``, ``S_0 = 0``, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
  B_tᵀ``, ``y_t = S_t C_t + D x_t``; ``y <- RMSNorm(y * SiLU(z))`` over each
  group's ``d_in / G`` channels, times ``gate_norm``; ``W_out``.
- ``*``: ``q = u Wq`` as ``H_q`` heads, ``k, v`` as ``H_kv`` heads, query head
  ``h`` reads KV head ``h // (H_q / H_kv)``; scores ``q kᵀ / sqrt(hd)``, key
  ``j`` visible to query ``i`` iff ``j <= i``; softmax; ``Wo``. No rotary and
  no other position signal: the family's published modelling code applies
  none in its attention layers (the Mamba-2 layers carry position) and the
  config's ``rope_theta`` is read by nothing there. (Assumed; the config
  alone does not say.)
- ``E``: ``s = sigmoid(u W_r)`` over all ``E``; the chosen set is the top-k of
  ``s + moe_bias`` (``e_score_correction_bias``); ``w = routed_scaling_factor
  · s_chosen / (sum s_chosen + 1e-20)``, from ``s`` without the bias;
  ``Shared(u) + sum_e w_e E_e(u)``, every expert (the shared one too) the
  ungated ``W_down relu(W_up u)²``. With ``moe_routing_no_grad`` in ``arch``
  the weights ``w`` are constants of the backward pass. (Departure: the
  family moves ``e_score_correction_bias`` by each expert's load outside
  the gradient; here it is a leaf no gradient reaches, held at its value.)
- Loss: mean over positions of the cross-entropy of ``final_norm(x) Wheadᵀ``,
  plus ``router_aux_loss_coef`` (0 where ``arch`` has none; not a key of the
  published config) times the mean over the ``E`` layers of ``E · sum_e f_e
  P_e``: ``f_e`` the share of the layer's tokens that chose expert ``e``,
  ``P_e`` the mean over tokens of ``s_e / sum_e' s_e'``.
  (``rescale_prenorm_residual`` is an initialisation rule and is not applied:
  the weights come from the caller.)

``choices`` (a list, one ``(tokens, k)`` int array or None a layer) replaces
the reference's own top-k indices by given ones; the weights are still the
reference's scores at those indices. A comparison with a system that computes
in bfloat16 uses it, because the top-k of near-tied scores is discrete.

``operand_dtype`` rounds both operands of every product to that dtype first
(the matrix products, and the recurrence's ``x``, ``B`` and ``C``; the
products still accumulate in float32): what the same equations give in a
lower precision, which a comparison's tolerances must tell apart from the
system.

``loss_and_grad_by_layer`` is the same loss and gradient taken a layer at a
time, so that the published widths fit a chip's memory: each layer's input is
kept, each layer's vector-Jacobian product is its own program, attention
runs a query head at a time and the experts one at a time (the same head and
expert functions, under ``jax.lax.map``), and the recurrence's ``T`` steps are
taken in blocks whose inner steps are computed again on the way back (the
same step function; no arithmetic changes).
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


def rms_norm(y, scale, eps):
    return y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps) * scale


def _eps(arch):
    return arch.get("layer_norm_epsilon", 1e-5)


def layer_kind(arch: dict, l: int) -> str:
    return arch["hybrid_override_pattern"][l]


# -- M: Mamba-2 ---------------------------------------------------------------

def recurrence(x, dt, a, b, c, d, blocks=False):
    """``x``: ``(B, T, H, P)``; ``dt``: ``(B, T, H)``; ``a``, ``d``: ``(H,)``;
    ``b``, ``c``: ``(B, T, H, N)`` (each head's group's). ``y``: ``(B, T, H,
    P)``, one step of the sequence at a time."""
    bsz, t, h, p = x.shape

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        y_t = jnp.sum(state * c_t[..., None, :], axis=-1) + d[:, None] * x_t
        return state, y_t

    steps = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c))
    state = jnp.zeros((bsz, h, p, b.shape[-1]), F32)
    if not blocks or t % SCAN_BLOCK:
        return jnp.moveaxis(jax.lax.scan(step, state, steps)[1], 0, 1)
    # the same steps, SCAN_BLOCK at a time; a block's steps are computed
    # again on the way back, so only the states between blocks are kept
    block = jax.checkpoint(lambda s, at: jax.lax.scan(step, s, at))
    cut = lambda v: v.reshape(t // SCAN_BLOCK, SCAN_BLOCK, *v.shape[1:])
    _, y = jax.lax.scan(block, state, tuple(cut(v) for v in steps))
    return jnp.moveaxis(y.reshape(t, bsz, h, p), 0, 1)


def mamba(p, u, arch, operand_dtype=None, blocks=False):
    bsz, t, _ = u.shape
    h, hp = arch["mamba_num_heads"], arch["mamba_head_dim"]
    g, n, k = arch["n_groups"], arch["ssm_state_size"], arch["conv_kernel"]
    inner = h * hp
    zxbcdt = _mm(u, p["in_proj"], operand_dtype)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner:2 * inner + 2 * g * n]
    dt = jax.nn.softplus(zxbcdt[..., 2 * inner + 2 * g * n:] + p["dt_bias"])
    # causal depthwise convolution: K - 1 zeros to the left
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = p["conv_b"]
    for tap in range(k):
        conv = conv + padded[:, tap:tap + t] * p["conv_w"][:, tap]
    xbc = jax.nn.silu(conv)
    x = xbc[..., :inner].reshape(bsz, t, h, hp)
    per_head = lambda v: jnp.repeat(  # head h reads group h // (H / G)
        v.reshape(bsz, t, g, n), h // g, axis=2)
    b = per_head(xbc[..., inner:inner + g * n])
    c = per_head(xbc[..., inner + g * n:])
    y = recurrence(_round(x, operand_dtype), dt, -jnp.exp(p["A_log"]),
                   _round(b, operand_dtype), _round(c, operand_dtype),
                   p["D"], blocks)
    gated = (y.reshape(bsz, t, inner) * jax.nn.silu(z)).reshape(
        bsz, t, g, inner // g)  # gate first, then the norm a group
    gated = gated / jnp.sqrt(
        jnp.mean(gated * gated, axis=-1, keepdims=True) + _eps(arch))
    return _mm(gated.reshape(bsz, t, inner) * p["gate_norm"], p["out_proj"],
               operand_dtype)


# -- *: attention -------------------------------------------------------------

def _one_head(q, k, v, seen, operand_dtype):
    """``q, k, v``: ``(B, T, hd)`` of one query head and its KV head."""
    scores = _mm(q, jnp.swapaxes(k, 1, 2), operand_dtype) / math.sqrt(
        q.shape[-1])
    probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return _mm(probs, v, operand_dtype)


def attention(p, u, arch, operand_dtype=None, head_at_a_time=False):
    bsz, t, _ = u.shape
    heads, kv_heads = arch["num_attention_heads"], arch["num_key_value_heads"]
    hd = arch["head_dim"]
    mm = functools.partial(_mm, operand_dtype=operand_dtype)
    q = mm(u, p["wq"]).reshape(bsz, t, heads, hd)
    k = mm(u, p["wk"]).reshape(bsz, t, kv_heads, hd)
    v = mm(u, p["wv"]).reshape(bsz, t, kv_heads, hd)
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


# -- E: experts ---------------------------------------------------------------

def relu2_mlp(u, w_up, w_down, operand_dtype=None):
    hidden = jnp.maximum(_mm(u, w_up, operand_dtype), 0.0)
    return _mm(hidden * hidden, w_down, operand_dtype)


def router_scores(p, u):
    return jax.nn.sigmoid(jnp.matmul(u, p["moe_router"]))


def own_choice(p, scores, k):
    """The top-k of ``score + bias``."""
    return jax.lax.top_k(scores + p["moe_bias"], k)[1]


def balance(scores, choice):
    """``E · sum_e f_e P_e`` of one layer's scores ``(..., E)`` and chosen
    experts ``(..., k)``, ``P`` from the scores as shares that sum to 1 a
    token; the gradient passes through ``P`` alone."""
    experts = scores.shape[-1]
    tokens = choice.size // choice.shape[-1]
    share = jnp.bincount(choice.reshape(-1), length=experts) / tokens
    probs = scores / scores.sum(-1, keepdims=True)
    return experts * jnp.sum(share * probs.reshape(-1, experts).mean(0))


def sparse_ffn(p, u, arch, experts_held=None, expert_offset=0, choice=None,
               operand_dtype=None, expert_at_a_time=False,
               with_balance=False):
    """``u``: ``(B, T, d)``. Returns the held experts' part plus the shared
    expert (and, ``with_balance``, the layer's load-balancing term). The
    router is never rounded (it is float32 in the system)."""
    scores = router_scores(p, u)
    k = arch["num_experts_per_tok"]
    if choice is None:
        choice = own_choice(p, scores, k)
    else:
        choice = jnp.asarray(choice).reshape(*u.shape[:-1], k)
    top = jnp.take_along_axis(scores, choice, axis=-1)  # without the bias
    weights = top / (top.sum(-1, keepdims=True) + 1e-20) * arch.get(
        "routed_scaling_factor", 1.0)
    if arch.get("moe_routing_no_grad"):  # constants of the backward pass
        weights = jax.lax.stop_gradient(weights)
    held = p["moe_w_up"].shape[0] if experts_held is None else experts_held

    def expert(e, w_up, w_down):
        w_e = jnp.where(choice == expert_offset + e, weights, 0.0).sum(-1)
        return w_e[..., None] * relu2_mlp(u, w_up, w_down, operand_dtype)

    stacked = [p[name][:held] for name in ("moe_w_up", "moe_w_down")]
    if expert_at_a_time:
        # the same expert function, one expert live at a time (by_layer)
        out = jax.lax.map(lambda ew: jax.checkpoint(expert)(*ew),
                          (jnp.arange(held), *stacked)).sum(0)
    else:
        out = jnp.zeros_like(u)
        for e in range(held):
            out = out + expert(e, *(w[e] for w in stacked))
    if arch.get("moe_shared_expert_intermediate_size"):
        out = out + relu2_mlp(u, p["shared_w_up"], p["shared_w_down"],
                              operand_dtype)
    return (out, balance(scores, choice)) if with_balance else out


# -- the model ----------------------------------------------------------------

def layer(p, x, arch, l, experts_held=None, expert_offset=0, choice=None,
          operand_dtype=None, at_a_time=False, with_balance=False):
    """The layer's output; ``with_balance``, also its load-balancing term
    (None but on an ``E`` layer). ``at_a_time``: heads, experts and blocks of
    the recurrence one at a time (``loss_and_grad_by_layer``)."""
    kind, term = layer_kind(arch, l), None
    if kind == "M":
        out = x + mamba(p, rms_norm(x, p["ssm_norm"], _eps(arch)), arch,
                        operand_dtype, blocks=at_a_time)
    elif kind == "*":
        out = x + attention(p, rms_norm(x, p["attn_norm"], _eps(arch)), arch,
                            operand_dtype, at_a_time)
    elif kind == "E":
        ffn, term = sparse_ffn(
            p, rms_norm(x, p["ffn_norm"], _eps(arch)), arch, experts_held,
            expert_offset, choice, operand_dtype, expert_at_a_time=at_a_time,
            with_balance=True)
        out = x + ffn
    else:
        raise ValueError(f"layer kind {kind!r}: have M, E, *")
    return (out, term) if with_balance else out


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


def forward(params, tokens, arch, experts_held=None, expert_offset=0,
            choices=None, operand_dtype=None, with_balance=False):
    """Hidden states before the final norm, ``(B, T, d)``; ``with_balance``,
    also the ``E`` layers' load-balancing terms."""
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
    program. Also returns the reference's own top-k an ``E`` layer (taken on
    the way, on the hidden states this routing gives; None on the others).
    ``to_host`` moves each layer's gradient to the host as it is made.
    Returns ``(loss, grads, own_choices)``."""
    n = arch["num_hidden_layers"]
    fetch = jax.device_get if to_host else (lambda tree: tree)
    # layers of one kind are one program: the first of the kind stands for
    # all of them
    kinds = [layer_kind(arch, l) for l in range(n)]
    first_of = [kinds.index(kind) for kind in kinds]
    k = arch["num_experts_per_tok"]
    sparse = [kind == "E" for kind in kinds]
    # d loss / d (an E layer's load-balancing term)
    per_term = arch.get("router_aux_loss_coef", 0.0) / max(sum(sparse), 1)

    def run(p, x, choice, l):
        """The layer's output and its load-balancing term (0 but on E)."""
        out, term = layer(p, x, arch, l, experts_held, expert_offset, choice,
                          operand_dtype, at_a_time=True, with_balance=True)
        return out, jnp.zeros((), F32) if term is None else term

    @functools.partial(jax.jit, static_argnums=(3,))
    def run_layer(p, x, choice, l):
        """The layer's output, its load-balancing term and, on an ``E``
        layer, the reference's own top-k on this input."""
        with jax.default_matmul_precision("highest"):
            if kinds[l] != "E":
                return (*run(p, x, None, l), None)
            scores = router_scores(p, rms_norm(x, p["ffn_norm"], _eps(arch)))
            return (*run(p, x, choice, l), own_choice(p, scores, k))

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
        if given is None or not sparse[l]:
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
