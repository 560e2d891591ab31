"""Causal transformer LM — the long-context model family.

Beyond-parity extension (the reference's only sequence model is the PTB
LSTM, SURVEY.md §5): a pre-LN decoder-only transformer whose attention can
run either dense (single-device sequence) or as exact ring attention over a
mesh axis (``seq_axis`` set — the model is then applied INSIDE shard_map
with the sequence dimension sharded onto that axis, and every device holds
``T/W`` positions; ``mpit_tpu.ops.ring_attention``).

The same parameters produce the same function either way: positions are
computed globally from the ring rank, attention is exact, and the loss is a
per-position mean — see tests/test_seq_parallel.py for the bit-level
equivalence checks across mesh shapes.

TPU notes: bf16 compute / f32 params by default, NHD head layout feeding
128-multiple-friendly matmuls; attention accumulates in f32 (the op's
standard recipe).
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from mpit_tpu.models.arch import (
    LayerSpec, layer_specs, rope_tables, rotate_half_matrix,
)
from mpit_tpu.ops.ring_attention import dense_attention, ring_attention
from mpit_tpu.ops.ulysses import ulysses_attention
from mpit_tpu.utils import profiling


def rms_norm(x, scale, eps: float):
    """``x / rms(x) * scale`` over the last axis, in float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


def apply_rope(x, cos, sin, swap):
    """``x cos + (x @ swap) sin`` on ``(B, T, H, D)``: rotary positions in
    the rotate-half layout (dim ``i`` pairs with ``i + rotary/2``), with
    ``cos``/``sin`` from ``rope_tables`` and ``swap`` from
    ``rotate_half_matrix``. The product only moves and negates elements,
    so it is exact in ``x``'s dtype; the rest is float32."""
    partner = jnp.dot(
        x, jnp.asarray(swap, x.dtype), preferred_element_type=jnp.float32,
        precision=(jax.lax.Precision.HIGHEST
                   if x.dtype == jnp.float32 else None),
    )
    return (x.astype(jnp.float32) * cos[None, :, None, :]
            + partner * sin[None, :, None, :]).astype(x.dtype)


def causal_conv_silu(x, weight, bias=None):
    """``SiLU(conv(x))`` in float32: ``x`` ``(B, T, C)`` through a depthwise
    causal convolution, ``out_t = bias + sum_k weight[:, k] x_{t - (K - 1) +
    k}`` with ``weight`` ``(C, K)`` and ``K - 1`` zeros to the left."""
    t, taps = x.shape[1], weight.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    out = sum(padded[:, k:k + t] * weight[:, k] for k in range(taps))
    return jax.nn.silu(out if bias is None else bias + out)


def softplus_inverse_of_a_step(dt_min, dt_max, dt_floor):
    """An initialiser (Mamba-2's rule for ``dt_bias``): ``softplus^-1`` of a
    step log-uniform in ``[dt_min, dt_max]``, floored at ``dt_floor``."""
    def init(key, shape, dtype):
        step = jnp.exp(jax.random.uniform(key, shape, dtype) * (
            jnp.log(dt_max) - jnp.log(dt_min)) + jnp.log(dt_min))
        step = jnp.maximum(step, dt_floor)
        return step + jnp.log(-jnp.expm1(-step))

    return init


class Block(nn.Module):
    d_model: int
    num_heads: int
    d_ff: int
    compute_dtype: Any
    seq_axis: Optional[str]
    moe_experts: int = 0
    moe_axis: Optional[str] = None
    moe_capacity_factor: float = 2.0
    moe_top_k: int = 1
    # single-device attention implementation: "xla" (fused dense),
    # "flash" (pallas kernels both directions on TPU, dense elsewhere),
    # "flash_force" (pallas everywhere — interpret mode on CPU; tests).
    # Where the kernel is selected a T that does not tile raises
    attn_impl: str = "xla"
    # sequence-parallel scheme when seq_axis is set — see TransformerLM
    seq_impl: str = "ring"
    # autoregressive decode mode: the block consumes ONE token per call
    # and attends over a (B, max_len) K/V cache held in the "cache"
    # variable collection (serving path — models/sampling.generate_fast);
    # decode_len sizes the cache (the LM passes its max_len)
    decode: bool = False
    decode_len: int = 0
    # what this layer is, where the model was given an architecture
    # (TransformerLM.arch): RMSNorm, rotary positions, grouped KV heads,
    # a window, a per-head output gate, a SwiGLU or a sparse expert
    # feed-forward. None = the GPT-2 block below, whose parameter tree
    # (LayerNorm_0..1, Dense_0..3) other modules key on
    spec: Optional[LayerSpec] = None

    @nn.compact
    def __call__(self, x):
        if self.spec is not None:
            return self._described(x)
        # profiling.scope names the device's time by layer part (metadata
        # only; forward, recomputation and backward all carry it): "attention" is the
        # attention arithmetic alone, "attn_proj" the projections around
        # it with their LayerNorm and residual, "mlp" the feed-forward
        dt = self.compute_dtype
        h, d = self.num_heads, self.d_model // self.num_heads
        if self.seq_impl not in ("ring", "ulysses"):
            raise ValueError(
                f"seq_impl={self.seq_impl!r} must be 'ring' or 'ulysses'"
            )
        if self.decode and (self.seq_axis is not None or self.moe_experts):
            raise ValueError(
                "decode mode is single-device dense-FFN only "
                "(seq_axis=None, moe_experts=0)"
            )
        with profiling.scope("attn_proj"):
            y = nn.LayerNorm(dtype=dt)(x)
            qkv = nn.Dense(3 * self.d_model, use_bias=False, dtype=dt)(y)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            split = lambda a: a.reshape(*a.shape[:2], h, d)
            q, k, v = split(q), split(k), split(v)
        with profiling.scope("attention"):
            if self.decode:
                att = self._cached_attention(q, k, v)
            elif self.seq_axis is not None and self.seq_impl == "ulysses":
                att = ulysses_attention(q, k, v, self.seq_axis, causal=True)
            elif self.seq_axis is not None:
                att = ring_attention(q, k, v, self.seq_axis, causal=True)
            elif self.attn_impl in ("flash", "flash_force"):
                from mpit_tpu.ops.flash_attention import flash_attention

                att = flash_attention(
                    q, k, v, causal=True,
                    use_pallas=True if self.attn_impl == "flash_force"
                    else None,
                )
            else:
                att = dense_attention(q, k, v, causal=True)
        with profiling.scope("attn_proj"):
            att = att.reshape(*att.shape[:2], self.d_model)
            x = x + nn.Dense(self.d_model, use_bias=False, dtype=dt)(att)
        with profiling.scope("mlp"):
            y = nn.LayerNorm(dtype=dt)(x)
            if self.moe_experts:
                x = x + self._moe(y)
            else:
                y = nn.Dense(self.d_ff, dtype=dt)(y)
                y = nn.gelu(y)
                x = x + nn.Dense(self.d_model, dtype=dt)(y)
        return x

    def _described(self, x):
        """The block ``spec`` describes: ``x <- x + Mixer(RMSNorm(x))`` (or,
        ``spec.norm_at`` ``output``, ``x + RMSNorm(Mixer(x))``) once a
        name in ``spec.mixers``, no biases: ``attention`` or
        ``linear_attention`` then ``ffn`` for a
        transformer layer, one mixer alone (``ssm``, ``ffn`` or
        ``attention``) for a hybrid model's. Scopes as in the GPT-2 block,
        with ``rope``, ``qk_norm`` and ``attn_gate`` inside ``attn_proj``,
        ``attn_window`` / ``attn_full`` inside ``attention``, the expert
        layer's ``moe_*`` scopes inside ``mlp``, ``ssm`` around the whole
        Mamba-2 mixer and ``linattn`` around the whole gated-delta-rule one."""
        if self.decode or self.seq_axis is not None or self.moe_experts:
            raise ValueError(
                "a block built from an architecture (TrainConfig.arch) "
                "trains on one device's whole sequence: decode=True, "
                "seq_axis and the GShard moe_experts path are not built "
                "for it"
            )
        if self.attn_impl not in ("xla", "flash", "flash_force"):
            raise ValueError(f"attn_impl={self.attn_impl!r}")
        mixer = {"attention": self._attention_mixer, "ffn": self._ffn_mixer,
                 "ssm": self._ssm_mixer,
                 "linear_attention": self._linear_attention_mixer}
        for name in self.spec.mixers:
            x = mixer[name](x)
        return x

    def _weight(self, name, *shape):
        return self.param(name, nn.initializers.lecun_normal(), shape,
                          jnp.float32)

    def _norm_scale(self, name, width=None):
        return self.param(name, nn.initializers.ones_init(),
                          (width or self.spec.d_model,), jnp.float32)

    def _proj(self, a, w):
        dt = self.compute_dtype
        return jnp.dot(a, w.astype(dt), preferred_element_type=jnp.float32)

    def _mixer_input(self, x, norm):
        """What a mixer reads: ``RMSNorm(x)`` by the scale ``norm``, or ``x``
        itself where the block's norm is on the mixer's output."""
        if self.spec.norm_at == "output":
            return x.astype(self.compute_dtype)
        return rms_norm(x, self._norm_scale(norm),
                        self.spec.norm_eps).astype(self.compute_dtype)

    def _residual(self, x, out, norm):
        """``x + out``, ``out`` through the block's norm first where that is
        on the mixer's output (OLMo 2's reordered norm)."""
        if self.spec.norm_at == "output":
            out = rms_norm(out, self._norm_scale(norm), self.spec.norm_eps)
        return x + out.astype(self.compute_dtype)

    def _attention_mixer(self, x):
        from mpit_tpu.ops.flash_attention import flash_attention

        spec, dt = self.spec, self.compute_dtype
        b, t, d = x.shape
        h, h_kv, hd = spec.num_heads, spec.num_kv_heads, spec.head_dim
        weight, proj = self._weight, self._proj
        with profiling.scope("attn_proj"):
            y = self._mixer_input(x, "attn_norm")
            q = proj(y, weight("wq", d, h * hd)).astype(dt)
            k = proj(y, weight("wk", d, h_kv * hd)).astype(dt)
            v = proj(y, weight("wv", d, h_kv * hd)).astype(dt)
            if spec.qk_norm:
                # over the whole width, before the split into heads
                with profiling.scope("qk_norm"):
                    q = rms_norm(q, self._norm_scale("q_norm", h * hd),
                                 spec.norm_eps).astype(dt)
                    k = rms_norm(k, self._norm_scale("k_norm", h_kv * hd),
                                 spec.norm_eps).astype(dt)
            q = q.reshape(b, t, h, hd)
            k, v = (a.reshape(b, t, h_kv, hd) for a in (k, v))
            if spec.rope is not None:
                with profiling.scope("rope"):
                    cos, sin = rope_tables(spec.rope, t, hd)
                    swap = rotate_half_matrix(spec.rope, hd)
                    q, k = apply_rope(q, cos, sin, swap), apply_rope(
                        k, cos, sin, swap)
        with profiling.scope("attention"):
            with profiling.scope(
                "attn_full" if spec.window is None else "attn_window"
            ):
                att = flash_attention(
                    q, k, v, causal=True, window=spec.window,
                    use_pallas={"xla": False, "flash": None,
                                "flash_force": True}[self.attn_impl],
                )
        with profiling.scope("attn_proj"):
            if spec.gate:
                # head-wise sigmoid gate from the normed input, on the
                # attention output before the output projection
                with profiling.scope("attn_gate"):
                    gate = jax.nn.sigmoid(proj(y, weight("wg", d, h)))
                    att = (att * gate[..., None]).astype(dt)
            return self._residual(x, proj(
                att.reshape(b, t, h * hd), weight("wo", h * hd, d)
            ), "attn_norm")

    def _ffn_mixer(self, x):
        from mpit_tpu.ops.moe import swiglu

        spec, dt = self.spec, self.compute_dtype
        b, t, d = x.shape
        with profiling.scope("mlp"):
            y = self._mixer_input(x, "ffn_norm")
            if spec.moe is None:
                return self._residual(x, swiglu(
                    y, self._weight("w_gate", d, spec.d_ff),
                    self._weight("w_up", d, spec.d_ff),
                    self._weight("w_down", spec.d_ff, d),
                ), "ffn_norm")
            return self._residual(x, self._held_experts(
                y.reshape(b * t, d)).reshape(b, t, d), "ffn_norm")

    def _ssm_mixer(self, x):
        """The Mamba-2 mixer: ``[z | xBC | dt] = u W_in``; ``xBC`` through a
        causal depthwise convolution (with bias) and SiLU, then split into
        ``x`` (heads), ``B`` and ``C`` (groups); ``dt = softplus(dt +
        dt_bias)``, ``A = -exp(A_log)``; the recurrence (``ops/ssd.py``);
        ``RMSNorm`` over each group's channels of ``y * SiLU(z)`` (gate
        first, then norm) times a weight; ``W_out``. ``ssd``'s
        ``log_decay_min`` is sown into ``counters``."""
        from mpit_tpu.ops import ssd as ssd_ops

        spec, ssm, dt = self.spec, self.spec.ssm, self.compute_dtype
        b, t, d = x.shape
        f32 = jnp.float32
        inner, gn = ssm.d_inner, ssm.groups * ssm.state

        dt_bias_init = softplus_inverse_of_a_step(
            ssm.dt_min, ssm.dt_max, ssm.dt_floor)
        per_head = lambda name, init: self.param(
            name, init, (ssm.heads,), f32)
        with profiling.scope("ssm"):
            u = rms_norm(x, self._norm_scale("ssm_norm"),
                         spec.norm_eps).astype(dt)
            zxbcdt = self._proj(u, self._weight(
                "in_proj", d, 2 * inner + 2 * gn + ssm.heads))
            z = zxbcdt[..., :inner].astype(dt)
            xbc = zxbcdt[..., inner:inner + ssm.conv_dim]
            step = jax.nn.softplus(
                zxbcdt[..., inner + ssm.conv_dim:]
                + per_head("dt_bias", dt_bias_init))
            with profiling.scope("ssm_conv"):
                conv_w = self.param(
                    "conv_w", nn.initializers.lecun_normal(in_axis=-1,
                                                           out_axis=-2),
                    (ssm.conv_dim, ssm.conv_kernel), f32)
                conv_b = self.param("conv_b", nn.initializers.zeros_init(),
                                    (ssm.conv_dim,), f32)
                xbc = causal_conv_silu(xbc, conv_w, conv_b).astype(dt)
            # what the scan and the gate read, kept by a remat'd block
            # (_REMAT_KEEPS): its backward then recomputes neither the
            # input projection nor the convolution
            z, xbc, step = (checkpoint_name(a, "ssm_in")
                            for a in (z, xbc, step))
            with profiling.scope("ssd"):
                y, log_decay_min = ssd_ops.ssd(
                    xbc[..., :inner].reshape(b, t, ssm.heads, ssm.head_dim),
                    step,
                    -jnp.exp(per_head("A_log", lambda key, shape, dtype:
                                      jnp.log(jnp.arange(1, shape[0] + 1,
                                                         dtype=dtype)))),
                    xbc[..., inner:inner + gn].reshape(
                        b, t, ssm.groups, ssm.state),
                    xbc[..., inner + gn:].reshape(
                        b, t, ssm.groups, ssm.state),
                    per_head("D", nn.initializers.ones_init()),
                    chunk=ssm.chunk,
                )
            self.sow("counters", "ssm_chunk_log_decay_min", log_decay_min)
            with profiling.scope("ssm_gate"):
                gated = (y.reshape(b, t, inner).astype(f32)
                         * jax.nn.silu(z.astype(f32)))
                grouped = gated.reshape(b, t, ssm.groups, inner // ssm.groups)
                gated = (grouped * jax.lax.rsqrt(
                    jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
                    + spec.norm_eps)).reshape(b, t, inner)
                gated = (gated * self._norm_scale("gate_norm", inner)
                         ).astype(dt)
            return x + self._proj(
                gated, self._weight("out_proj", inner, d)).astype(dt)

    def _linear_attention_mixer(self, x):
        """The gated-delta-rule mixer (``ops/gated_delta.py`` has the
        equations): ``q``, ``k``, ``v`` each a projection, a causal depthwise
        convolution without bias and SiLU; ``q`` and ``k`` L2-normalised a
        head, ``q`` times ``d_k^-1/2``; ``beta = sigmoid(u W_b)`` (times 2
        where negative eigenvalues are allowed), ``g = -exp(A_log)
        softplus(u W_a + dt_bias)``; the recurrence; ``RMSNorm`` over each
        head's channels times a weight, THEN the gate ``SiLU(u W_gate)``
        (the other order from ``_ssm_mixer``'s); ``W_o``. The op's
        ``log_decay_min`` is sown into ``counters``."""
        from mpit_tpu.ops.gated_delta import gated_delta

        spec, lin, dt = self.spec, self.spec.linattn, self.compute_dtype
        b, t, d = x.shape
        f32 = jnp.float32
        h, dk, dv = lin.heads, lin.key_dim, lin.value_dim
        conv_init = nn.initializers.lecun_normal(in_axis=-1, out_axis=-2)
        per_head = lambda name, init: self.param(name, init, (h,), f32)
        with profiling.scope("linattn"):
            u = self._mixer_input(x, "linattn_norm")
            proj = lambda name, width: self._proj(
                u, self._weight(name, d, width))
            widths = (("q", h * dk), ("k", h * dk), ("v", h * dv))
            qkv = [proj(f"lin_{name}", width).astype(dt)
                   for name, width in widths]
            with profiling.scope("linattn_conv"):
                q, k, v = (
                    causal_conv_silu(a, self.param(
                        f"conv_{name}", conv_init, (width, lin.conv_kernel),
                        f32)).astype(dt)
                    for a, (name, width) in zip(qkv, widths))
            with profiling.scope("delta_rule"):
                unit = lambda a: a * jax.lax.rsqrt(
                    jnp.sum(jnp.square(a), axis=-1, keepdims=True) + 1e-6)
                q, k = (a.reshape(b, t, h, dk).astype(f32) for a in (q, k))
                q, k = unit(q) * dk ** -0.5, unit(k)
                beta = jax.nn.sigmoid(proj("lin_b", h)) * (
                    2.0 if lin.neg_eigval else 1.0)
                # A uniform in [1, 16], dt_bias by Mamba-2's rule
                a_log = per_head("A_log", lambda key, shape, dtype: jnp.log(
                    jax.random.uniform(key, shape, dtype, 1.0, 16.0)))
                g = -jnp.exp(a_log) * jax.nn.softplus(
                    proj("lin_a", h) + per_head(
                        "dt_bias", softplus_inverse_of_a_step(
                            0.001, 0.1, 1e-4)))
                o, log_decay_min = gated_delta(
                    q.astype(dt), k.astype(dt),
                    v.reshape(b, t, h, dv), g, beta,
                    chunk=lin.chunk)
            self.sow("counters", "delta_chunk_log_decay_min", log_decay_min)
            gate = proj("lin_gate", h * dv).astype(dt)
            with profiling.scope("linattn_gate"):
                o = (rms_norm(o, self._norm_scale("gate_norm", dv),
                              spec.norm_eps).reshape(b, t, h * dv)
                     * jax.nn.silu(gate.astype(f32))).astype(dt)
            return self._residual(
                x, self._proj(o, self._weight("lin_o", h * dv, d)),
                "linattn_norm")

    def _held_experts(self, y2):
        """The sparse feed-forward's part that lives here
        (``ops/moe.moe_ffn_held``) plus the shared expert, which every
        chip of the deployment computes alike. Routing counters are sown
        into the ``counters`` collection (``aggregate_counters``) and the
        chosen expert ids into ``routing``."""
        from mpit_tpu.ops import moe as moe_ops

        moe, d = self.spec.moe, self.spec.d_model
        expert_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,),
        )
        # w_gate, w_up: (d, width); w_down: (width, d)
        shape = lambda name, width: (
            (width, d) if name == "w_down" else (d, width))
        names = moe_ops.EXPERTS[moe.expert][0]
        params = {"router": self._weight("moe_router", d, moe.routed)}
        if moe.scoring == "sigmoid":
            # the family's e_score_correction_bias: no gradient reaches it
            # (the choice is discrete); small and non-zero from the seed
            params["bias"] = self.param(
                "moe_bias", nn.initializers.normal(0.01), (moe.routed,),
                jnp.float32)
        for name in names:
            params[name] = self.param(
                f"moe_{name}", expert_init,
                (moe.held, *shape(name, moe.width)), jnp.float32)
        out, counters, (_, experts) = moe_ops.moe_ffn_held(
            params, y2, top_k=moe.top_k, expert_offset=moe.offset,
            row_bound=moe.rows(y2.shape[0]), scale=moe.scale,
            routing_grad=moe.routing_grad, expert=moe.expert,
        )
        for name, val in counters.items():
            self.sow("counters", name, val)
        # the top-k expert ids, for a comparison that has to be made on
        # the same discrete choices (mutable=["routing"])
        self.sow("routing", "experts", experts)
        if moe.shared_width:
            with profiling.scope("moe_shared"):
                out = out + moe_ops.dense_expert(moe.expert, y2, *(
                    self._weight(f"shared_{name}",
                                 *shape(name, moe.shared_width))
                    for name in names))
        return out

    def _cached_attention(self, q, k, v):
        """Causal attention of a T-token CHUNK over the persistent K/V
        cache (T = 1 per-token decode; T > 1 chunked prefill — the
        prompt lands in the cache as one matmul-bound pass instead of T
        latency-bound ticks).

        The cache lives in the ``cache`` variable collection (flax's
        standard decode recipe): ``cached_key``/``cached_value`` hold the
        first ``cache_index`` positions' keys/values; each call appends
        the chunk's K/V at ``[cache_index, cache_index+T)`` and the
        chunk's query at local row ``r`` (global position
        ``cache_index + r``) attends cache positions ``<= cache_index +
        r`` — exactly the causal rule. Static shapes throughout — the
        cache is allocated at ``decode_len`` and masked, so the whole
        generation loop compiles once per bucket
        (sampling.generate_fast).

        ``cache_index`` is PER ROW, shape (B,): each batch row carries
        its own position clock, so a mixed-length batch prefills every
        row's ENTIRE prompt in one dense pass and ticks from there
        (sampling's batched kernel) — rows no longer share a scalar
        frontier. The K/V append becomes a per-row dynamic_update_slice
        (vmapped) and the causal mask compares against each row's own
        index; with all rows' indices equal this is exactly the old
        shared-clock behavior.

        Numerics match :func:`dense_attention`: f32 scores/softmax/
        accumulation, inputs left in compute dtype for the einsums.
        """
        if self.decode_len <= 0:
            raise ValueError(
                f"decode=True needs decode_len > 0, got {self.decode_len}"
            )
        b, t, h, d = q.shape
        if t > self.decode_len:
            raise ValueError(
                f"chunk of {t} exceeds the {self.decode_len}-slot cache"
            )
        # has_variable BEFORE self.variable: during model.init the cache
        # is created on this very call, and mutating it then would leak
        # a post-step index into the initial cache state
        ready = self.has_variable("cache", "cached_key")
        zeros = nn.initializers.zeros_init()
        ck = self.variable(
            "cache", "cached_key", zeros, None,
            (b, self.decode_len, h, d), k.dtype,
        )
        cv = self.variable(
            "cache", "cached_value", zeros, None,
            (b, self.decode_len, h, d), v.dtype,
        )
        idx = self.variable(
            "cache", "cache_index",
            lambda: jnp.zeros((b,), jnp.int32),
        )
        i = idx.value  # (b,) per-row position clocks
        row_update = jax.vmap(
            lambda cache_row, chunk_row, start:
            jax.lax.dynamic_update_slice(cache_row, chunk_row, (start, 0, 0))
        )
        key_cache = row_update(ck.value, k, i)
        val_cache = row_update(cv.value, v, i)
        if ready:
            ck.value, cv.value = key_cache, val_cache
            idx.value = i + t
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q, key_cache,
            preferred_element_type=jnp.float32,
        ) / (d ** 0.5)
        # row r of batch row n may see cache positions <= i[n] + r
        mask = (
            jnp.arange(self.decode_len)[None, None, :]
            <= i[:, None, None] + jnp.arange(t)[None, :, None]
        )  # (b, t, L)
        s = jnp.where(mask[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", p, val_cache,
            preferred_element_type=jnp.float32,
        ).astype(q.dtype)

    def _moe(self, y):
        """GShard MoE FFN replacing the dense MLP (``mpit_tpu.ops.moe``).

        Param names carry the ``moe_`` prefix — the expert-parallel
        trainer's sharding rules key on it (experts shard over
        ``moe_axis``, the router stays replicated). Outside shard_map
        (``moe_axis=None``) the dense reference computes the same
        function on all experts locally.

        Routing-quality stats (balance loss, router z-loss, drop
        fraction) are sown into the ``moe_losses`` collection — a no-op
        unless the caller applies with ``mutable=["moe_losses"]``, so
        plain ``apply`` paths are untouched.
        """
        from mpit_tpu.ops.moe import moe_ffn, moe_ffn_dense_reference

        e, dm, f = self.moe_experts, self.d_model, self.d_ff
        # flax validates declared param shapes on APPLY too, so inside
        # shard_map the expert leaves must be declared with their LOCAL
        # shard shape (axis size is static there); init runs on the dense
        # clone (moe_axis=None) and produces the global (e, ...) leaves
        # that the trainer's P(axis) in-specs then shard to exactly this
        e_l = e
        if self.moe_axis is not None:
            world = jax.lax.axis_size(self.moe_axis)
            if e % world:
                raise ValueError(
                    f"moe_experts={e} not divisible by the {world}-wide "
                    f"{self.moe_axis!r} axis"
                )
            e_l = e // world
        init = nn.initializers.lecun_normal()
        # the expert dim is a BATCH axis for initialization — plain lecun
        # on (E, d_in, d_out) would count E into fan_in and start every
        # expert sqrt(E) too small
        expert_init = nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal", in_axis=-2, out_axis=-1,
            batch_axis=(0,),
        )
        params = {
            "router": self.param("moe_router", init, (dm, e), jnp.float32),
            "w_up": self.param(
                "moe_w_up", expert_init, (e_l, dm, f), jnp.float32
            ),
            "b_up": self.param(
                "moe_b_up", nn.initializers.zeros_init(), (e_l, f),
                jnp.float32,
            ),
            "w_down": self.param(
                "moe_w_down", expert_init, (e_l, f, dm), jnp.float32
            ),
            "b_down": self.param(
                "moe_b_down", nn.initializers.zeros_init(), (e_l, dm),
                jnp.float32,
            ),
        }
        if self.moe_axis is not None:
            out, aux = moe_ffn(
                params, y, axis=self.moe_axis,
                capacity_factor=self.moe_capacity_factor,
                top_k=self.moe_top_k, with_aux=True,
            )
        else:
            out, aux = moe_ffn_dense_reference(
                params, y, capacity_factor=self.moe_capacity_factor,
                top_k=self.moe_top_k, with_aux=True,
            )
        for name, val in aux.items():
            self.sow("moe_losses", name, val)
        return out


#: what a rematerialized block keeps for its backward pass, by
#: ``checkpoint_name``; everything else in the block is computed again.
#: ``ops/flash_attention.py`` sets all three where the kernels are taken:
#: the forward kernel's output and log-sum-exp, which its backward kernels
#: read (recomputing them is the dearest kernel run twice), and q, k and v
#: as they enter it (after the rotary), which spares the recomputed
#: projections and rotary too. A block that reaches no kernel (dense, ring,
#: Ulysses) names nothing and keeps nothing. A Mamba-2 mixer names ``ssm_in``:
#: the gate ``z``, ``xBC`` after its convolution and the step ``dt`` (169 MB a
#: layer at 8,192 tokens: spares the input projection and convolution), and
#: ``ops/ssd.py``'s kernels name ``ssd_out``: ``y`` and the chunks' entering
#: states (201 MB a layer: spares a second forward kernel; PERF.md, PR 33).
_REMAT_KEEPS = ("flash_out", "flash_lse", "flash_qkv", "ssm_in", "ssd_out")

# explicit names at the call sites: nn.remat renames the wrapped class
# (CheckpointBlock), which would fork the param tree between remat modes
_RematBlock = nn.remat(
    Block, policy=jax.checkpoint_policies.save_only_these_names(*_REMAT_KEEPS)
)


def _sown_by_name(collection: dict) -> dict:
    """``{"Block_i": {name: (value, ...)}}`` -> ``{name: [values]}`` over
    the blocks that sowed it."""
    by_name: dict = {}
    for block_vals in collection.values():
        for name, vals in block_vals.items():
            by_name.setdefault(name, []).extend(vals)
    return by_name


def aggregate_moe_losses(collection: dict) -> dict:
    """Mean each sown MoE stat over the blocks that sowed it.

    ``collection`` is the ``moe_losses`` mutable returned by
    ``model.apply(..., mutable=["moe_losses"])``:
    ``{"Block_i": {name: (scalar,), ...}, ...}`` → ``{name: scalar}``.
    """
    return {
        name: sum(vals) / len(vals)
        for name, vals in _sown_by_name(collection).items()
    }


def aggregate_counters(collection: dict) -> dict:
    """One value a step from the counters the layers sowed
    (``model.apply(..., mutable=["counters"])``). From the expert layers:
    ``moe_rows_held`` the mean over layers of the pairs routed to the
    experts held, ``moe_rows_walked`` the mean of the buffer rows computed
    for them, ``moe_load_max_over_mean`` the worst layer's fullest expert
    over its mean, ``moe_rows_dropped`` the sum of the rows past the bound,
    ``moe_balance`` the mean of the load-balancing terms (top-k = uniform).
    From the Mamba-2 layers: ``ssm_chunk_log_decay_min``, the most negative
    sum of ``dt A`` over one chunk, over heads, chunks and layers; from the
    gated-delta-rule layers ``delta_chunk_log_decay_min``, the same of ``g``."""
    by_name = _sown_by_name(collection)
    reduce = {
        "rows_held": ("moe_rows_held", jnp.mean),
        "rows_walked": ("moe_rows_walked", jnp.mean),
        "load_max_over_mean": ("moe_load_max_over_mean", jnp.max),
        "rows_dropped": ("moe_rows_dropped", jnp.sum),
        "balance": ("moe_balance", jnp.mean),
        "ssm_chunk_log_decay_min": ("ssm_chunk_log_decay_min", jnp.min),
        "delta_chunk_log_decay_min": ("delta_chunk_log_decay_min", jnp.min),
    }
    return {out: over(jnp.stack(by_name[name]))
            for name, (out, over) in reduce.items() if name in by_name}


class TransformerLM(nn.Module):
    """Next-token LM over ``(B, T_local)`` int32 tokens → f32 logits.

    ``seq_axis=None``: ordinary single-sequence model (T_local = T).
    ``seq_axis="sp"``: sequence-parallel — MUST be called inside shard_map
    over a mesh with that axis; tokens are the local contiguous block in
    ring order and positional embeddings are indexed by GLOBAL position
    (ring rank × T_local + local offset).
    """

    vocab_size: int
    num_layers: int = 2
    d_model: int = 128
    num_heads: int = 4
    d_ff: int = 0  # 0 -> 4*d_model
    max_len: int = 1024
    compute_dtype: Any = jnp.bfloat16
    seq_axis: Optional[str] = None
    # rematerialize each block on the backward pass, all but the
    # attention kernel's named inputs and outputs (_REMAT_KEEPS):
    # activation memory drops from O(layers) to O(1) blocks plus those
    # few tensors a layer, for ~1/3 more FLOPs — the standard
    # jax.checkpoint trade to fit longer T or bigger B in HBM
    remat: bool = False
    # mixture-of-experts FFN: moe_experts > 0 replaces every block's MLP
    # with a top-k-routed MoE (ops/moe.py); moe_axis names the mesh axis
    # experts shard over (None = all experts local / dense reference);
    # moe_balance_weight/moe_zloss_weight scale the auxiliary
    # load-balance and router z losses the MoE trainer adds to the CE
    # objective (0.0 = off; the stats are sown either way)
    moe_experts: int = 0
    moe_axis: Optional[str] = None
    moe_capacity_factor: float = 2.0
    moe_top_k: int = 1
    moe_balance_weight: float = 0.0
    moe_zloss_weight: float = 0.0
    # attention tiling for the dense (seq_axis=None) path — see Block
    attn_impl: str = "xla"
    # sequence-parallel scheme when seq_axis is set: "ring" (K/V blocks
    # rotate via ppermute — extreme T, no score matrix) or "ulysses"
    # (all_to_all head<->sequence re-shard around dense attention —
    # moderate T, needs num_heads % axis == 0). Both exact.
    seq_impl: str = "ring"
    # serving path: decode=True turns every block into a cached-attention
    # chunk step (see Block.decode); params are IDENTICAL to the
    # training configuration — only the "cache" collection is added
    decode: bool = False
    # head=False returns the final-norm hidden states (B, T, d_model)
    # instead of logits — chunked prefill projects ONE row through the
    # vocab head (head_logits) rather than materializing (B, T, V) f32
    head: bool = True
    # vocab-head OPERAND dtype override (None -> compute_dtype).
    # Accumulation is always f32 regardless. Exists so the bf16-head
    # quality guard (tests/test_head_dtype.py) can A/B the head in
    # isolation; head_dtype=f32 also serves a bf16 model with a
    # full-precision head when quality comparisons call for it.
    head_dtype: Any = None
    # an architecture in its source's own keys (models/arch.py reads
    # them): RMSNorm, rotary positions, a per-layer pattern of head
    # counts, windows and dense or sparse SwiGLU feed-forwards, no
    # position table, an untied head. It replaces num_layers, d_model,
    # num_heads, d_ff and max_len. None = the GPT-2 model those describe
    arch: Any = None

    @property
    def loss_with_counters(self):
        """``(params, x, y) -> (loss, counters)`` where the layers sow
        counters (an ``arch``'s expert layers: ``aggregate_counters``),
        else None. A trainer that finds it puts the counters into its
        step's metrics."""
        if self.arch is None:
            return None
        from mpit_tpu.parallel.common import cross_entropy_loss

        # the family's auxiliary load-balancing loss (0 = none)
        coef = float(self.arch.get("router_aux_loss_coef", 0.0))

        def loss_fn(params, x, y):
            logits, sown = self.apply(
                {"params": params}, x, mutable=["counters"]
            )
            counters = aggregate_counters(sown.get("counters", {}))
            loss = cross_entropy_loss(logits, y)
            if coef and "moe_balance" in counters:
                loss = loss + coef * counters["moe_balance"]
            return loss, counters

        return loss_fn

    @property
    def _head_operand_dtype(self):
        """The ONE resolution of the head's operand dtype — shared by
        the ``__call__`` head and ``head_logits`` so the prefill==tick
        bit-equality the serving tests pin cannot fork on a rule edit."""
        return (
            self.compute_dtype if self.head_dtype is None
            else self.head_dtype
        )

    def _described(self, tokens):
        """The model ``arch`` describes; parameters ``Embed_0``,
        ``Block_i``, ``final_norm`` and (untied) ``head``."""
        if self.decode or self.seq_axis is not None or self.moe_experts:
            raise ValueError(
                "a model built from an architecture (TrainConfig.arch) "
                "trains on one device's whole sequence: decode=True "
                "(serving), seq_axis (seq-sync) and moe_experts (moe-sync) "
                "are not built for it"
            )
        arch, dt = self.arch, self.compute_dtype
        specs = layer_specs(arch)
        d = specs[0].d_model
        embed = nn.Embed(self.vocab_size, d, dtype=dt, name="Embed_0")
        with profiling.scope("embed"):
            x = embed(tokens)
        block_cls = _RematBlock if self.remat else Block
        for i, spec in enumerate(specs):
            x = block_cls(
                d_model=d, num_heads=spec.num_heads, d_ff=spec.d_ff,
                compute_dtype=dt, seq_axis=None, attn_impl=self.attn_impl,
                spec=spec, name=f"Block_{i}",
            )(x)
        x = rms_norm(
            x, self.param("final_norm", nn.initializers.ones_init(), (d,),
                          jnp.float32),
            specs[0].norm_eps,
        ).astype(dt)
        if not self.head:
            return x
        hdt = self._head_operand_dtype
        with profiling.scope("head"):
            if arch.get("tie_word_embeddings", False):
                table = embed.embedding
            else:
                table = self.param(
                    "head", nn.initializers.lecun_normal(in_axis=-1,
                                                         out_axis=-2),
                    (self.vocab_size, d), jnp.float32)
            return jnp.einsum(
                "btd,vd->btv", x.astype(hdt), table.astype(hdt),
                preferred_element_type=jnp.float32,
            )

    @nn.compact
    def __call__(self, tokens):
        if self.arch is not None:
            return self._described(tokens)
        if self.d_model % self.num_heads:
            raise ValueError(
                f"d_model {self.d_model} not divisible by "
                f"num_heads {self.num_heads}"
            )
        dt = self.compute_dtype
        t_local = tokens.shape[1]
        # name pinned explicitly (matches the flax auto-name so existing
        # checkpoints/param trees are unchanged): head_logits() reaches the
        # tied table via params["Embed_0"]["embedding"], so reordering or
        # renaming this module must not move that path
        embed = nn.Embed(
            self.vocab_size, self.d_model, dtype=dt, name="Embed_0"
        )
        pos_table = self.param(
            "pos_embedding",
            nn.initializers.normal(0.02),
            (self.max_len, self.d_model),
            jnp.float32,
        )
        offset = 0
        total_len = t_local
        if self.decode:
            if self.seq_axis is not None:
                raise ValueError("decode mode requires seq_axis=None")
            # the LM's own position counter (each block keeps its own
            # cache_index; this one feeds the positional embedding) —
            # same create-before-mutate discipline as Block's cache, and
            # PER ROW like cache_index (each batch row at its own position)
            ready = self.has_variable("cache", "pos_index")
            pidx = self.variable(
                "cache", "pos_index",
                lambda: jnp.zeros((tokens.shape[0],), jnp.int32),
            )
            offset = pidx.value  # (B,)
            if ready:
                pidx.value = offset + t_local
            total_len = 1  # bounds are the caller's contract in decode
        elif self.seq_axis is not None:
            # sequence-parallel: this shard's tokens are the ring-rank'th
            # contiguous block, so positions are GLOBAL offsets
            total_len = t_local * jax.lax.axis_size(self.seq_axis)
            offset = jax.lax.axis_index(self.seq_axis) * t_local
        if total_len > self.max_len:
            raise ValueError(
                f"sequence of {total_len} exceeds max_len={self.max_len}"
            )
        # scalar offset -> (t,) positions; per-row decode offset (B,) ->
        # (B, t) positions — the table gather broadcasts either way
        pos = jnp.asarray(offset)[..., None] + jnp.arange(t_local)
        with profiling.scope("embed"):
            x = embed(tokens) + pos_table[pos].astype(dt)
        block_cls = _RematBlock if self.remat else Block
        for i in range(self.num_layers):
            x = block_cls(
                d_model=self.d_model,
                num_heads=self.num_heads,
                d_ff=self.d_ff or 4 * self.d_model,
                compute_dtype=dt,
                seq_axis=self.seq_axis,
                moe_experts=self.moe_experts,
                moe_axis=self.moe_axis,
                moe_capacity_factor=self.moe_capacity_factor,
                moe_top_k=self.moe_top_k,
                attn_impl=self.attn_impl,
                seq_impl=self.seq_impl,
                decode=self.decode,
                decode_len=self.max_len if self.decode else 0,
                name=f"Block_{i}",
            )(x)
        x = nn.LayerNorm(dtype=dt)(x)
        if not self.head:
            return x
        # tied output head: operands in the head operand dtype (default
        # compute_dtype; head_dtype overrides), ACCUMULATION in f32
        # (preferred_element_type). What must not happen is large-vocab
        # logits quantized to bf16 on output (Embed.attend's behavior);
        # f32 accumulation prevents that while keeping the matmul on the
        # MXU's bf16 fast path — an f32xf32 head at GPT-2-small shapes is
        # ~16% of forward FLOPs running at a fraction of MXU rate, which
        # taxes exactly the MFU-ceiling preset built to prove the
        # framework isn't the bottleneck. For compute_dtype=float32
        # models (the equivalence-test configuration) this is bit-
        # identical to the previous all-f32 head.
        hdt = self._head_operand_dtype
        with profiling.scope("head"):
            table = embed.embedding.astype(hdt)
            return jnp.einsum(
                "btd,vd->btv", x.astype(hdt), table,
                preferred_element_type=jnp.float32,
            )

    def head_logits(self, params, h):
        """The tied vocab head applied to (B, d_model) hidden rows —
        the SAME projection ``__call__`` ends with (head-operand-dtype
        operands, f32 accumulation), for callers that ran ``head=False``
        and kept only the rows they need (chunked prefill). The embed
        table's param path is pinned by a test against a full forward."""
        hdt = self._head_operand_dtype
        with profiling.scope("head"):
            table = params["Embed_0"]["embedding"].astype(hdt)
            return jnp.einsum(
                "bd,vd->bv", h.astype(hdt), table,
                preferred_element_type=jnp.float32,
            )
