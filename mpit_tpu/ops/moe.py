"""Mixture-of-experts FFN with expert parallelism (GShard dispatch).

The last letter of the parallelism suite (dp / sp / tp / pp / ep): experts
shard across an ``ep`` mesh axis — each device owns ``E/ep`` expert FFNs
and a shard of the token batch — and tokens travel to their expert's
device and back with ``lax.all_to_all``, the TPU collective built for
exactly this exchange.

Algorithm (Mesh-TensorFlow / GShard, top-k routing with capacity):

1. router scores each LOCAL token over all ``E`` experts; top-k experts +
   softmax gates per token (k=1 keeps the raw top-1 probability as the
   gate — the Switch rule; k>1 renormalizes the selected gates to sum to
   one — the GShard rule);
2. per (expert, capacity-slot) one-hot **dispatch** mask and gate-weighted
   **combine** tensor are built locally — tokens beyond an expert's
   capacity ``C`` are dropped (the standard overflow rule; capacity_factor
   sizes ``C``). Queueing is choice-major: every token's FIRST choice
   claims its slot before any token's second choice (GShard's priority
   rule — overflow sheds the lower-priority assignments first);
3. ``einsum`` with the dispatch mask packs tokens into an ``(E, C, D)``
   buffer; ``all_to_all`` over ep regroups it so each device holds its own
   experts' slots from EVERY peer: ``(E/ep, ep·C, D)``;
4. the local expert FFNs run batched (one ``vmap`` over local experts —
   a single fat matmul pair on the MXU);
5. the reverse ``all_to_all`` returns processed slots, and the combine
   einsum scatters them back to token positions, gate-scaled.

With ``capacity_factor`` large enough that nothing drops, the result is
EXACTLY ``gate(token) · FFN_{expert(token)}(token)`` — pinned against a
per-token dense reference in tests/test_moe.py.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from mpit_tpu.utils import profiling


def init_moe_params(rng, d_model: int, d_ff: int, num_experts: int) -> dict:
    """Router + stacked expert FFN weights (E on the leading axis —
    shard it ``P("ep")`` for expert parallelism)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale = 1.0 / np.sqrt(d_model)
    return {
        "router": jax.random.normal(k1, (d_model, num_experts)) * scale,
        "w_up": jax.random.normal(k2, (num_experts, d_model, d_ff)) * scale,
        "b_up": jnp.zeros((num_experts, d_ff)),
        "w_down": jax.random.normal(k3, (num_experts, d_ff, d_model))
        / np.sqrt(d_ff),
        "b_down": jnp.zeros((num_experts, d_model)),
    }


def _expert_ffn(w_up, b_up, w_down, b_down, x):
    """One expert's FFN — the ONE definition both the sharded path and the
    dense reference run (their equivalence proof depends on it)."""
    return jax.nn.gelu(x @ w_up + b_up) @ w_down + b_down


def _routing(h2, router, num_experts: int, capacity: int, top_k: int = 1):
    """(tokens, D) → dispatch (T, E, C) one-hot, combine (T, E, C), and
    LOCAL routing statistics (for the balance/z losses and drop metric)."""
    if not 1 <= top_k <= num_experts:
        raise ValueError(
            f"top_k={top_k} must be in [1, num_experts={num_experts}]"
        )
    t = h2.shape[0]
    logits = (h2 @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = lax.top_k(probs, top_k)  # (T, k), distinct
    if top_k > 1:
        # GShard: selected gates renormalize to sum to one; the k=1 path
        # keeps the raw probability (Switch) so adding top-k changed no
        # existing top-1 numerics
        gate_vals = gate_vals / gate_vals.sum(-1, keepdims=True)
    # choice-major queueing: flatten (choice, token) so every first
    # choice claims its capacity slot before any second choice
    flat_oh = jax.nn.one_hot(
        expert_idx.T.reshape(-1), num_experts, dtype=jnp.float32
    )  # (k·T, E)
    # position of each assignment within its expert's queue; non-selected
    # columns end up at -1 and never pass the kept mask
    position = jnp.cumsum(flat_oh, axis=0) * flat_oh - 1.0
    kept = (position < capacity) & (flat_oh > 0)
    # exactly one kept column per surviving assignment -> the sum IS its
    # slot; dropped rows sum to 0 but their kept mask zeroes the dispatch
    slot = jnp.where(kept, position, 0.0).sum(-1).astype(jnp.int32)
    pos_oh = jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
    disp_choice = (
        kept.astype(jnp.float32)[:, :, None] * pos_oh[:, None, :]
    ).reshape(top_k, t, num_experts, capacity)
    dispatch = disp_choice.sum(0)
    combine = jnp.einsum("kt,ktec->tec", gate_vals.T, disp_choice)
    stats = {
        # first-choice density (the GShard/Switch balance-loss f term;
        # constant w.r.t. the router — only p carries gradient)
        "f": jax.nn.one_hot(
            expert_idx[:, 0], num_experts, dtype=jnp.float32
        ).mean(0),
        "p": probs.mean(0),
        "z": jnp.mean(
            jax.scipy.special.logsumexp(logits, axis=-1) ** 2
        ),
        "dropped": 1.0 - kept.sum() / (top_k * t),
    }
    return dispatch, combine, stats


def _aux_from_stats(f, p, z, dropped, num_experts: int) -> dict:
    """Balance/z losses from (possibly axis-averaged) routing stats.

    ``balance`` is the Switch/GShard auxiliary load-balance loss
    ``E · Σ_e f_e · p_e`` — exactly 1.0 under perfectly uniform routing,
    larger the more the router concentrates. ``f`` is non-differentiable
    (argmax density), so the gradient pushes ``p`` away from hot experts.
    """
    return {
        "balance": num_experts * jnp.dot(f, p),
        "zloss": z,
        "dropped_frac": dropped,
    }


def moe_ffn(
    params: dict,
    h: jax.Array,
    axis: str = "ep",
    capacity_factor: float = 2.0,
    top_k: int = 1,
    with_aux: bool = False,
) -> "jax.Array | tuple[jax.Array, dict]":
    """Expert-parallel MoE FFN inside ``shard_map``.

    ``h``: the LOCAL (b, t, D) activation block (batch sharded on
    ``axis``). ``params["w_up"]/...`` carry the LOCAL expert shard
    (leading dim E/ep); ``params["router"]`` is replicated and scores all
    E experts. Returns the same shape as ``h`` (plus an aux dict of
    ``balance``/``zloss``/``dropped_frac`` scalars when ``with_aux`` —
    each already ``pmean``-ed over ``axis``, so every device holds the
    GLOBAL value and the losses are exactly mesh-width-invariant).

    Capacity caveat: ``C`` is computed from the LOCAL token count, so the
    per-expert capacity — not just arrival order — depends on the ep
    extent. Under tight ``capacity_factor`` the set of dropped tokens is
    therefore NOT invariant to mesh width; only the ample-capacity
    (no-drop) regime is. The dense reference applies the same per-shard
    rule only when given the same local token count.
    """
    ep = lax.axis_size(axis)
    b, t, d = h.shape
    e_local = params["w_up"].shape[0]
    num_experts = e_local * ep
    if params["router"].shape[1] != num_experts:
        raise ValueError(
            f"router scores {params['router'].shape[1]} experts but the "
            f"local shard x axis implies {num_experts} (= {e_local} local "
            f"x ep={ep}); are the expert weights actually sharded P(ep)?"
        )
    tokens = b * t
    capacity = int(np.ceil(tokens * capacity_factor / num_experts))
    h2 = h.reshape(tokens, d)

    dispatch, combine, stats = _routing(
        h2, params["router"], num_experts, capacity, top_k=top_k
    )
    # pack: (E, C, D) buffer of this device's tokens, by expert and slot
    buf = jnp.einsum("tec,td->ecd", dispatch, h2.astype(jnp.float32))
    # regroup: split E across peers, gather every peer's slots for OUR
    # experts -> (E/ep, ep*C, D)
    buf = lax.all_to_all(
        buf.reshape(ep, e_local, capacity, d), axis, 0, 0, tiled=False
    )
    buf = buf.transpose(1, 0, 2, 3).reshape(e_local, ep * capacity, d)

    out = jax.vmap(_expert_ffn)(
        params["w_up"], params["b_up"], params["w_down"],
        params["b_down"], buf,
    )
    # reverse the exchange: every peer gets its slots back
    out = out.reshape(e_local, ep, capacity, d).transpose(1, 0, 2, 3)
    out = lax.all_to_all(out, axis, 0, 0, tiled=False)
    out = out.reshape(num_experts, capacity, d)
    res = jnp.einsum("tec,ecd->td", combine, out)
    res = res.reshape(b, t, d).astype(h.dtype)
    if not with_aux:
        return res
    # global stats: equal shard sizes make the pmean of local means exact
    # (one pytree pmean -> one fused all-reduce)
    g = lax.pmean(stats, axis)
    aux = _aux_from_stats(
        g["f"], g["p"], g["z"], g["dropped"], num_experts
    )
    return res, aux


def moe_ffn_dense_reference(
    params_full: dict,
    h: jax.Array,
    capacity_factor: float = 2.0,
    top_k: int = 1,
    with_aux: bool = False,
) -> "jax.Array | tuple[jax.Array, dict]":
    """Unsharded ground truth: route each token, run its expert directly.

    ``params_full`` carries ALL experts (leading dim E). Implements the
    identical capacity/overflow rule so the equivalence is exact even when
    tokens drop (given the same local token count — see the capacity
    caveat on :func:`moe_ffn`).
    """
    b, t, d = h.shape
    num_experts = params_full["w_up"].shape[0]
    tokens = b * t
    capacity = int(np.ceil(tokens * capacity_factor / num_experts))
    h2 = h.reshape(tokens, d)
    dispatch, combine, stats = _routing(
        h2, params_full["router"], num_experts, capacity, top_k=top_k
    )
    buf = jnp.einsum("tec,td->ecd", dispatch, h2.astype(jnp.float32))
    out = jax.vmap(_expert_ffn)(
        params_full["w_up"], params_full["b_up"], params_full["w_down"],
        params_full["b_down"], buf,
    )
    res = jnp.einsum("tec,ecd->td", combine, out)
    res = res.reshape(b, t, d).astype(h.dtype)
    if not with_aux:
        return res
    aux = _aux_from_stats(
        stats["f"], stats["p"], stats["z"], stats["dropped"], num_experts
    )
    return res, aux


# -- one chip's share of a dropless, sort-dispatched expert layer -----------

def route_top_k(y2, router, top_k: int, scale: float = 1.0, bias=None):
    """Scores over ALL experts in f32, the ``top_k`` largest, their weights
    divided by their sum and multiplied by ``scale``. Without ``bias`` the
    scores are a softmax. With ``bias`` (``(E,)``, the family's
    ``e_score_correction_bias``) they are sigmoids, the choice is the
    ``top_k`` of ``score + bias`` and the weights are the chosen scores
    WITHOUT it. ``(tokens, D) -> (tokens, k)`` weights and int32 expert ids,
    and the ``(tokens, E)`` scores as shares that sum to 1 a token (the
    load-balancing term's ``P``)."""
    logits = jnp.dot(
        y2.astype(jnp.float32), router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    if bias is None:
        scores = jax.nn.softmax(logits, axis=-1)
        weights, experts = lax.top_k(scores, top_k)
        weights = weights / weights.sum(-1, keepdims=True) * scale
        return weights, experts.astype(jnp.int32), scores
    scores = jax.nn.sigmoid(logits)
    _, experts = lax.top_k(scores + lax.stop_gradient(bias), top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    weights = weights / (weights.sum(-1, keepdims=True) + 1e-20) * scale
    return (weights, experts.astype(jnp.int32),
            scores / scores.sum(-1, keepdims=True))


#: what one expert computes, by name: the parameters it takes (leading
#: dimension: the experts held) and ``(product, rows, *weights) -> rows``,
#: ``product`` the matrix product it is to use: ``moe_ffn_held`` hands it
#: the grouped product, ``dense_expert`` the plain one; the function's own
#: derivative is its backward. The last weight maps out of the expert's
#: width (on its second axis), the others into it (on their last), and a
#: zero there gives a zero
EXPERTS = {
    "swiglu": (("w_gate", "w_up", "w_down"),
               lambda product, rows, w_gate, w_up, w_down: product(
                   jax.nn.silu(product(rows, w_gate)) * product(rows, w_up),
                   w_down)),
    # ungated: W_down relu(W_up x)^2
    "relu2": (("w_up", "w_down"),
              lambda product, rows, w_up, w_down: product(
                  jnp.square(jax.nn.relu(product(rows, w_up))), w_down)),
}


def dense_expert(expert: str, x, *weights):
    """``EXPERTS[expert]`` as one dense feed-forward without biases (a
    shared expert, a dense layer), in ``x``'s dtype with f32 accumulation."""
    dt = x.dtype
    mm = lambda a, w: jnp.dot(
        a, w.astype(dt), preferred_element_type=jnp.float32).astype(dt)
    return EXPERTS[expert][1](mm, x, *weights)


def swiglu(x, w_gate, w_up, w_down):
    """``W_down(silu(W_gate x) * (W_up x))`` without biases, in ``x``'s
    dtype with f32 accumulation."""
    return dense_expert("swiglu", x, w_gate, w_up, w_down)


#: the held experts' width is padded with zeros to a multiple of this
#: (``moe_ffn_held``); the last of an expert's weights maps out of the width
_WIDTH_TILE = 512


def chunk_rows(tokens: int, top_k: int, held: int, routed: int) -> int:
    """The chunk in which ``moe_ffn_held`` walks its buffer: twice the rows
    uniform routing sends to ``held`` of ``routed`` experts, those up to a
    multiple of 256. A chunk costs 3 to 4 ms a layer whatever its rows
    (its scatter-adds and weight-gradient sums) and 0.55 ms a thousand rows
    on the v5e (``scripts/moe_held_sweep.py``, PERF.md section 6, PR 31),
    so the loads a step meets should fit one chunk."""
    return 2 * math.ceil(tokens * top_k * held / routed / 256) * 256


def _expert_rows(expert, rows, weights, row_weight, ends, start):
    """The experts (``EXPERTS[expert]``) over ``rows``, which are rows
    ``start .. start + len(rows)`` of the buffer sorted by expert (``ends``:
    where each expert's rows end in the whole buffer), times ``row_weight``,
    in f32. The weights come in ``rows``' dtype."""
    n, dt = rows.shape[0], rows.dtype
    with profiling.scope("moe_dispatch"):
        span = jnp.clip(ends - start, 0, n)
        sizes = jnp.diff(span, prepend=0).astype(jnp.int32)
        valid = jnp.arange(n) < span[-1]
    with profiling.scope("moe_experts"):
        # a grouped product leaves the rows past its groups as they were
        # in memory (seen on the v5e, PR 27: the gradient into such rows
        # came back as garbage 1e5 times the true one), so every operand
        # and result is cut to the valid rows, forward and backward
        live = lambda a: jnp.where(valid[:, None], a, 0)
        grouped = lambda a, w: live(lax.ragged_dot(
            live(a), w, sizes, preferred_element_type=jnp.float32
        ).astype(dt))
        out_rows = EXPERTS[expert][1](grouped, rows, *weights)
    with profiling.scope("moe_dispatch"):
        return out_rows.astype(jnp.float32) * row_weight[:, None]


def _add_rows(expert, out, y, weights, token, row_weight, ends, start):
    """``out`` with the experts' weighted outputs for the buffer's rows
    ``start .. start + len(token)`` added at their tokens."""
    with profiling.scope("moe_dispatch"):
        rows = jnp.take(y, token, axis=0)
    add = _expert_rows(expert, rows, weights, row_weight, ends, start)
    with profiling.scope("moe_dispatch"):
        return out.at[token].add(add)


def _chunk_of(chunk, j, token, row_weight):
    """Chunk ``j``'s first row, token ids and weights."""
    start = j * chunk
    cut = lambda a: lax.dynamic_slice_in_dim(a, start, chunk)
    return start, cut(token), cut(row_weight)


def _live_chunks(chunk, ends):
    """Chunks up to the buffer's last live row, ``ends[-1]``."""
    return -(-ends[-1] // chunk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _walk(chunk, expert, y, weights, row_weight, token, ends):
    """``zeros.at[token].add(_expert_rows(y[token], ...))`` in ``y``'s
    dtype, the buffer walked ``chunk`` rows at a time and only as far as
    its last live row, ``ends[-1]``: one loop with a traced trip count
    forward and one backward, each holding the layer's body once. The
    backward recomputes a chunk's products from ``y`` (as the block's
    remat would) and keeps the cotangents as carries updated in place;
    the weights' carries have the weights' dtype, as the single pass's
    cotangents have, and a chunk's row sums are f32 inside its product."""
    return _walk_fwd(chunk, expert, y, weights, row_weight, token, ends)[0]


def _walk_fwd(chunk, expert, y, weights, row_weight, token, ends):
    def body(j, out):
        start, tok, weight = _chunk_of(chunk, j, token, row_weight)
        return _add_rows(expert, out, y, weights, tok, weight, ends, start)

    with profiling.scope("moe_dispatch"):
        out = lax.fori_loop(0, _live_chunks(chunk, ends), body,
                            jnp.zeros(y.shape, jnp.float32)).astype(y.dtype)
    return out, (y, weights, row_weight, token, ends)


def _walk_bwd(chunk, expert, residuals, ct):
    y, weights, row_weight, token, ends = residuals

    def body(j, carry):
        d_y, d_weights, d_row_weight = carry
        start, tok, weight = _chunk_of(chunk, j, token, row_weight)
        with profiling.scope("moe_dispatch"):
            rows = jnp.take(y, tok, axis=0)
            ct_rows = jnp.take(ct, tok, axis=0).astype(jnp.float32)
        _, vjp = jax.vjp(
            lambda rows, weights, weight: _expert_rows(
                expert, rows, weights, weight, ends, start),
            rows, weights, weight)
        d_rows, d_chunk, d_weight = vjp(ct_rows)
        with profiling.scope("moe_dispatch"):
            d_y = d_y.at[tok].add(d_rows)
            d_row_weight = lax.dynamic_update_slice_in_dim(
                d_row_weight, d_weight, start, 0)
        with profiling.scope("moe_experts"):
            d_weights = [a + d for a, d in zip(d_weights, d_chunk)]
        return d_y, d_weights, d_row_weight

    with profiling.scope("moe_dispatch"):
        d_y, d_weights, d_row_weight = lax.fori_loop(
            0, _live_chunks(chunk, ends), body,
            (jnp.zeros_like(y), [jnp.zeros_like(w) for w in weights],
             jnp.zeros_like(row_weight)))
    # left as a loop's outputs, the weights' gradients are read by the
    # optimizer at the program's end and live until then: in the Laguna cell
    # 0.55 GB more at the peak and 8 ms a step (PERF.md section 6, PR 31);
    # pinned here, XLA schedules their readers next to the loop
    d_weights = lax.optimization_barrier(d_weights)
    return d_y, d_weights, d_row_weight, None, None


_walk.defvjp(_walk_fwd, _walk_bwd)


def moe_ffn_held(
    params: dict,
    y: jax.Array,
    *,
    top_k: int,
    expert_offset: int = 0,
    row_bound: int,
    scale: float = 1.0,
    routing_grad: bool = True,
    expert: str = "swiglu",
):
    """The part of a sparse expert layer that the experts HELD here give
    (the model-configs guide's section 4): routing scores all
    ``params["router"].shape[1]`` experts, this chip holds experts
    ``expert_offset .. expert_offset + E_held`` (the leading dimension of
    the expert's weights, ``EXPERTS[expert]`` names them) and computes, for every
    token, ``sum over its chosen experts held here of weight · expert``.
    What the other experts would add is left out; no code stands in for
    their chips or the exchange.

    Dropless by sorting, not by capacity: the (token, choice) pairs that
    name an expert held here are sorted by expert into one buffer of
    ``row_bound`` rows, grouped matrix products (``jax.lax.ragged_dot``,
    the TPU compiler's own grouped kernel; three for SwiGLU experts, two
    for ``relu2``) run the experts over it, and the rows are scattered back times
    their weights. ``row_bound`` is static; pairs past it are dropped
    and COUNTED (``rows_dropped``; a correct run reads 0).

    The bound is for the imbalance a step may meet, not for the step at
    hand, so a buffer of more than one chunk (``chunk_rows``, twice the
    rows uniform routing sends here) is walked chunk by chunk as far as the
    step's own count reaches (``_walk``) and the chunks past it cost
    nothing; ``rows_walked`` says how far that was.

    ``routing_grad=False`` makes the routing weights constants of the
    backward pass (``models/arch.py``, ``moe_routing_no_grad``, says when).
    ``params["bias"]``, where present, makes the scores sigmoids and the
    choice that of ``score + bias`` (``route_top_k``).

    ``y``: ``(tokens, D)``. Returns ``(out, counters, (weights,
    experts))``; the counters are f32 scalars: ``rows_held`` (pairs
    routed to the experts held), ``rows_walked`` (buffer rows computed),
    ``load_max_over_mean`` (the fullest expert's rows over the mean),
    ``rows_dropped`` and ``balance`` (the load-balancing term, the one
    counter a gradient passes through).
    """
    tokens, d = y.shape
    names = EXPERTS[expert][0]
    held = params[names[0]].shape[0]
    routed = params["router"].shape[1]
    row_bound = min(row_bound, tokens * top_k)  # there are no more pairs
    chunk = chunk_rows(tokens, top_k, held, routed)
    with profiling.scope("moe_router"):
        weights, experts, scores = route_top_k(
            y, params["router"], top_k, scale, params.get("bias"))
        if not routing_grad:
            weights = lax.stop_gradient(weights)
        # the load-balancing term E · sum_e f_e P_e over ALL experts scored,
        # f_e the share of tokens that chose e (top_k under uniform
        # routing); its gradient passes through P alone
        share = jnp.bincount(experts.reshape(-1), length=routed) / tokens
        balance = routed * jnp.sum(share * scores.mean(0))
    with profiling.scope("moe_dispatch"):
        local = experts.reshape(-1) - expert_offset  # pair p = token·k + c
        here = (local >= 0) & (local < held)
        key = jnp.where(here, local, held)  # the others sort to the end
        order = jnp.argsort(key, stable=True)[:row_bound]
        counts = jnp.bincount(key, length=held + 1)[:held]
        # rows of each expert that fit under the bound, in sorted order
        ends = jnp.minimum(jnp.cumsum(counts), row_bound)
        token = order // top_k
        row_weight = jnp.where(
            jnp.arange(row_bound) < ends[-1],
            jnp.take(weights.reshape(-1), order), 0.0)
    with profiling.scope("moe_experts"):  # cast once, outside any loop
        experts_w = [params[n].astype(y.dtype) for n in names]
        # the grouped kernel wants an expert width of whole 512s: at 1,856
        # a remat'd layer took 25.1 ms on the v5e, at 1,920 24.9 and at
        # 2,048, with a tenth more products, 17.3 (PERF.md section 6, PR
        # 32). Zero columns into the width and zero rows out of it leave
        # the function as it is (every EXPERTS activation maps 0 to 0).
        # Widths under one tile are the tests': left as they are
        width = experts_w[-1].shape[1]
        pad = -width % _WIDTH_TILE if width > _WIDTH_TILE else 0
        if pad:
            experts_w = [jnp.pad(w, ((0, 0), (0, 0), (0, pad)))
                         for w in experts_w[:-1]] + [
                jnp.pad(experts_w[-1], ((0, 0), (0, pad), (0, 0)))]
    if row_bound <= chunk:  # one chunk: no loop
        out = _add_rows(expert, jnp.zeros((tokens, d), jnp.float32), y,
                        experts_w, token, row_weight, ends, 0).astype(y.dtype)
        rows_walked = jnp.float32(row_bound)
    else:
        with profiling.scope("moe_dispatch"):
            # whole chunks; what lies past the bound stays past ``ends``
            pad = (0, -row_bound % chunk)
            token, row_weight = jnp.pad(token, pad), jnp.pad(row_weight, pad)
        out = _walk(chunk, expert, y, experts_w, row_weight, token, ends)
        rows_walked = (_live_chunks(chunk, ends) * chunk).astype(jnp.float32)
    with profiling.scope("moe_dispatch"):
        total = counts.sum().astype(jnp.float32)
        counters = {
            "rows_held": total,
            "rows_walked": rows_walked,
            "load_max_over_mean": counts.max() * held / jnp.maximum(total, 1.0),
            "rows_dropped": jnp.maximum(total - row_bound, 0.0),
            "balance": balance,
        }
    return out, counters, (weights, experts)
