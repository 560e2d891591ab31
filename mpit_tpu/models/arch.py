"""From a published ``config.json`` to what each layer of the model is.

``TrainConfig.arch`` carries an architecture in its source's own keys (the
Hugging Face ``config.json`` of the model, with the keys a cut replaces set
to what is run); :func:`layer_specs` turns it into one hashable
:class:`LayerSpec` a layer, which ``models/transformer.Block`` takes in place
of the GPT-2 block's fixed shape: its norm, positions, head counts, output
gate, window and feed-forward kind.

Keys read (others are ignored): ``hidden_size``, ``num_hidden_layers``,
``head_dim`` (absent or null: ``hidden_size / num_attention_heads``),
``num_key_value_heads``, ``num_attention_heads`` or
``num_attention_heads_per_layer``, ``layer_types`` (``full_attention`` /
``sliding_attention`` / ``linear_attention``), ``sliding_window``,
``rope_parameters`` (by layer type: ``rope_theta``, null = no rotary
positions; ``rope_type`` ``default`` or ``yarn`` with its
``factor``, ``original_max_position_embeddings``, ``beta_fast``,
``beta_slow``, ``attention_factor``; ``partial_rotary_factor``), ``gating``
(``per-head`` or absent), ``rms_norm_eps``, ``intermediate_size``,
``mlp_layer_types`` (``dense`` / ``sparse``), ``moe_intermediate_size``,
``shared_expert_intermediate_size``, ``num_experts_per_tok``,
``moe_routed_scaling_factor``, ``tie_word_embeddings``; lists longer than
``num_hidden_layers`` are read from their start. Two keys are this repo's,
for one chip's share of an expert-parallel deployment (the model-configs
guide's section 4): ``num_experts`` is the number of routed experts HELD,
``num_routed_experts`` the number the router scores (default: all held) and
``expert_offset`` the first held expert's index; ``moe_row_bound`` is the
dispatch buffer's static row count (default: four times the expected rows);
``moe_routing_no_grad`` true makes the routing weights constants of the
backward pass: no gradient into the router's weights, none through the
scores into the layer's input. A share takes it: that gradient is a sum over
every chosen expert's output, and with the other chips' experts absent the
part computed here says only "the experts held add noise, the absent ones
add nothing", which empties the experts held within 35 steps of AdamW, by
the router's weights or, where those are frozen, by the layer's input alone
(both seen on the v5e, PERF.md section 6, PR 27); the deployment's gradient
has no such term. ``router_aux_loss_coef`` (the family's key; default 0)
weights the mean over sparse layers of the load-balancing term ``E · sum_e
f_e P_e`` (``f_e`` the share of tokens that chose ``e``, ``P_e`` its mean
score: transformers' ``load_balancing_loss_func`` a layer) that
``TransformerLM.loss_with_counters`` adds to the loss.

A ``linear_attention`` layer's mixer is the gated delta rule
(``ops/gated_delta.py``), by the source's keys ``linear_num_key_heads``,
``linear_num_value_heads`` (equal: value heads shared by groups of key heads
are not built), ``linear_key_head_dim``, ``linear_value_head_dim``,
``linear_conv_kernel_dim``, ``linear_allow_neg_eigval``, and this repo's
``linear_chunk_size`` (default 64, a power of two). Two more keys are this
repo's, for what a ``config.json`` leaves to the modelling code: ``norm_at``
(``input``, the default, ``x + Mixer(RMSNorm(x))``; ``output``, OLMo 2's
``x + RMSNorm(Mixer(x))``) and ``qk_norm`` (default false; true: an RMSNorm
over the whole width of ``q`` and of ``k`` before they are split into heads).

An ``arch`` with ``hybrid_override_pattern`` is of the ``nemotron_h`` family
and is read by that family's keys: every layer is ONE mixer, named by its
letter in the pattern (``M`` Mamba-2, ``E`` experts, ``*`` attention; ``-``,
a dense MLP, is not built), the first ``num_hidden_layers`` letters. Keys:
``layer_norm_epsilon``; ``mamba_num_heads``, ``mamba_head_dim``,
``n_groups``, ``ssm_state_size``, ``conv_kernel``, ``chunk_size``,
``time_step_min`` / ``_max`` / ``_floor`` (the Mamba-2 mixer,
``ops/ssd.py``); ``num_attention_heads``, ``num_key_value_heads``,
``head_dim`` (attention without rotary or any other position signal, as in
the family's published modelling code: ``rope_theta`` is read by nothing);
``n_routed_experts`` (the experts HELD, as ``num_experts`` above, with
``num_routed_experts`` / ``expert_offset`` beside it),
``num_experts_per_tok``, ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``, ``routed_scaling_factor``,
``mlp_hidden_act`` (``relu2``: ungated experts ``W_down relu(W_up x)^2``),
sigmoid scores with the choice made on ``score + e_score_correction_bias``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np


@dataclasses.dataclass(frozen=True)
class RopeSpec:
    theta: float
    rotary_dim: int  # leading dims of each head that rotate
    # YaRN (None = plain rope): frequencies blended between the published
    # and the ``factor``-times-slower ones, cos and sin scaled
    yarn: Optional[tuple] = None  # (factor, original_max, beta_fast, beta_slow)
    attention_factor: float = 1.0


@dataclasses.dataclass(frozen=True)
class MoESpec:
    routed: int  # experts the router scores
    held: int  # experts whose weights live here
    offset: int  # index of the first held expert
    top_k: int
    width: int
    shared_width: int  # 0 = no shared expert
    scale: float
    row_bound: int  # 0 = from the token count (four times the expectation)
    routing_grad: bool = True  # False: routing weights are constants backward
    # "softmax", or "sigmoid": the choice made on score + a bias leaf, the
    # weights taken from the scores without it
    scoring: str = "softmax"
    # the expert's function, a key of ops/moe.EXPERTS: "swiglu" | "relu2"
    expert: str = "swiglu"

    def rows(self, tokens: int) -> int:
        """The dispatch buffer's rows for ``tokens`` tokens: the given
        bound, or four times the rows uniform routing sends to the
        experts held, in multiples of 256 (at most every pair)."""
        if self.row_bound:
            return self.row_bound
        expected = tokens * self.top_k * self.held / self.routed
        return min(int(math.ceil(4 * expected / 256.0)) * 256,
                   tokens * min(self.top_k, self.held))


@dataclasses.dataclass(frozen=True)
class SSMSpec:
    """A Mamba-2 mixer: ``heads`` of ``head_dim`` channels (``d_inner`` their
    product, whatever ``expand`` says), ``groups`` of B and C shared by
    ``heads / groups`` heads each, a state of ``state`` a channel."""
    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    dt_min: float
    dt_max: float
    dt_floor: float

    @property
    def d_inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_dim(self) -> int:  # x, B and C pass the convolution
        return self.d_inner + 2 * self.groups * self.state


@dataclasses.dataclass(frozen=True)
class LinearAttentionSpec:
    """A gated-delta-rule mixer: ``heads`` of ``key_dim`` for ``q`` and ``k``
    and of ``value_dim`` for ``v`` and the output, a state of ``value_dim x
    key_dim`` a head."""
    heads: int
    key_dim: int
    value_dim: int
    conv_kernel: int
    neg_eigval: bool  # beta in (0, 2) instead of (0, 1)
    chunk: int


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """One layer: the residual passes ``x + Mixer(RMSNorm(x))`` (``norm_at``
    ``input``) or ``x + RMSNorm(Mixer(x))`` (``output``) once a name in
    ``mixers``, in order. ``attention`` reads the head counts, ``window``,
    ``rope``, ``gate`` and ``qk_norm``; ``ffn`` reads ``d_ff`` or ``moe``;
    ``ssm`` reads ``ssm``; ``linear_attention`` reads ``linattn``."""
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: Optional[int]  # None = full causal attention
    rope: Optional[RopeSpec]  # None = no rotary positions
    gate: bool  # per-head sigmoid gate on the attention output
    norm_eps: float
    d_ff: int  # dense SwiGLU width (0 where the layer is sparse)
    moe: Optional[MoESpec]
    mixers: tuple = ("attention", "ffn")
    ssm: Optional[SSMSpec] = None
    linattn: Optional[LinearAttentionSpec] = None
    norm_at: str = "input"
    qk_norm: bool = False  # RMSNorm over all of q and of k, before the heads


def _rope_spec(params: dict, head_dim: int) -> RopeSpec:
    rotary = int(head_dim * params.get("partial_rotary_factor", 1.0))
    kind = params.get("rope_type", "default")
    if kind == "default":
        return RopeSpec(float(params["rope_theta"]), rotary)
    if kind != "yarn":
        raise ValueError(f"rope_type {kind!r}: have default, yarn")
    factor = float(params["factor"])
    return RopeSpec(
        float(params["rope_theta"]), rotary,
        yarn=(factor, int(params["original_max_position_embeddings"]),
              float(params.get("beta_fast", 32)),
              float(params.get("beta_slow", 1))),
        attention_factor=float(
            params.get("attention_factor") or 0.1 * math.log(factor) + 1.0),
    )


def _check_share(moe: MoESpec) -> None:
    if not 0 <= moe.offset <= moe.routed - moe.held:
        raise ValueError(
            f"arch: experts {moe.offset}..{moe.offset + moe.held} held "
            f"of {moe.routed} routed"
        )


def _hybrid_specs(arch: dict) -> tuple[LayerSpec, ...]:
    """The ``nemotron_h`` family: one mixer a layer, by its letter."""
    n = int(arch["num_hidden_layers"])
    pattern = str(arch["hybrid_override_pattern"])[:n]
    if len(pattern) != n:
        raise ValueError(
            f"arch: {n} layers but hybrid_override_pattern has {len(pattern)}")
    for key in ("mamba_proj_bias", "use_bias", "attention_bias", "mlp_bias"):
        if arch.get(key):
            raise ValueError(f"arch: {key} true is not built")
    if not arch.get("use_conv_bias", True):
        raise ValueError("arch: use_conv_bias false is not built")
    if int(arch.get("n_group", 1)) != 1 or int(arch.get("topk_group", 1)) != 1:
        raise ValueError("arch: group-limited routing (n_group > 1) is not "
                         "built")
    if not arch.get("norm_topk_prob", True):
        raise ValueError("arch: norm_topk_prob false is not built")
    if arch.get("mlp_hidden_act", "relu2") != "relu2":
        raise ValueError(
            f"arch: mlp_hidden_act {arch['mlp_hidden_act']!r}: have relu2")
    if arch.get("mamba_hidden_act", "silu") != "silu":
        raise ValueError(
            f"arch: mamba_hidden_act {arch['mamba_hidden_act']!r}: have silu")
    if int(arch.get("n_shared_experts", 1)) != 1:
        raise ValueError("arch: n_shared_experts other than 1 is not built")
    common = dict(
        d_model=int(arch["hidden_size"]), window=None, rope=None, gate=False,
        norm_eps=float(arch.get("layer_norm_epsilon", 1e-5)), d_ff=0)
    kinds = {}
    if "M" in pattern:
        ssm = SSMSpec(
            heads=int(arch["mamba_num_heads"]),
            head_dim=int(arch["mamba_head_dim"]),
            groups=int(arch["n_groups"]), state=int(arch["ssm_state_size"]),
            conv_kernel=int(arch["conv_kernel"]),
            chunk=int(arch["chunk_size"]),
            dt_min=float(arch.get("time_step_min", 0.001)),
            dt_max=float(arch.get("time_step_max", 0.1)),
            dt_floor=float(arch.get("time_step_floor", 1e-4)),
        )
        if ssm.heads % ssm.groups:
            raise ValueError(
                f"arch: {ssm.heads} Mamba-2 heads in {ssm.groups} groups")
        kinds["M"] = LayerSpec(**common, num_heads=0, num_kv_heads=0,
                               head_dim=0, moe=None, mixers=("ssm",), ssm=ssm)
    if "*" in pattern:
        kinds["*"] = LayerSpec(
            **common, num_heads=int(arch["num_attention_heads"]),
            num_kv_heads=int(arch["num_key_value_heads"]),
            head_dim=int(arch["head_dim"]), moe=None, mixers=("attention",))
    if "E" in pattern:
        held = int(arch["n_routed_experts"])
        moe = MoESpec(
            routed=int(arch.get("num_routed_experts", held)), held=held,
            offset=int(arch.get("expert_offset", 0)),
            top_k=int(arch["num_experts_per_tok"]),
            width=int(arch["moe_intermediate_size"]),
            shared_width=int(arch.get(
                "moe_shared_expert_intermediate_size", 0)),
            scale=float(arch.get("routed_scaling_factor", 1.0)),
            row_bound=int(arch.get("moe_row_bound", 0)),
            routing_grad=not arch.get("moe_routing_no_grad", False),
            scoring="sigmoid", expert="relu2",
        )
        _check_share(moe)
        kinds["E"] = LayerSpec(**common, num_heads=0, num_kv_heads=0,
                               head_dim=0, moe=moe, mixers=("ffn",))
    for letter in pattern:
        if letter not in kinds:
            raise ValueError(
                f"arch: layer kind {letter!r} in hybrid_override_pattern: "
                "have M (Mamba-2), E (experts), * (attention)")
    return tuple(kinds[letter] for letter in pattern)


def _linear_attention_spec(arch: dict) -> LinearAttentionSpec:
    heads = int(arch["linear_num_key_heads"])
    if int(arch.get("linear_num_value_heads", heads)) != heads:
        raise ValueError(
            f"arch: linear_num_value_heads "
            f"{arch['linear_num_value_heads']} on {heads} key heads: value "
            "heads in groups are not built")
    chunk = int(arch.get("linear_chunk_size", 64))
    if chunk < 1 or chunk & (chunk - 1):
        raise ValueError(
            f"arch: linear_chunk_size {chunk} is not a power of two")
    return LinearAttentionSpec(
        heads=heads, key_dim=int(arch["linear_key_head_dim"]),
        value_dim=int(arch["linear_value_head_dim"]),
        conv_kernel=int(arch.get("linear_conv_kernel_dim", 4)),
        neg_eigval=bool(arch.get("linear_allow_neg_eigval", False)),
        chunk=chunk)


def layer_specs(arch: dict) -> tuple[LayerSpec, ...]:
    if "hybrid_override_pattern" in arch:
        return _hybrid_specs(arch)
    n = int(arch["num_hidden_layers"])
    d = int(arch["hidden_size"])
    head_dim = int(arch.get("head_dim")
                   or d // int(arch["num_attention_heads"]))
    kinds = list(arch.get("layer_types") or ["full_attention"] * n)[:n]
    ffns = list(arch.get("mlp_layer_types") or ["dense"] * n)[:n]
    heads = list(arch.get("num_attention_heads_per_layer")
                 or [arch["num_attention_heads"]] * n)[:n]
    if not len(kinds) == len(ffns) == len(heads) == n:
        raise ValueError(
            f"arch: {n} layers but layer_types, mlp_layer_types and "
            f"num_attention_heads_per_layer give {len(kinds)}, {len(ffns)} "
            f"and {len(heads)}"
        )
    gating = arch.get("gating")
    if gating not in (None, "per-head"):
        raise ValueError(f"arch: gating {gating!r}: have per-head or none")
    ropes = arch["rope_parameters"]
    if "rope_theta" in ropes:  # one rope for every layer type
        ropes = {"full_attention": ropes, "sliding_attention": ropes}
    norm_at = arch.get("norm_at", "input")
    if norm_at not in ("input", "output"):
        raise ValueError(f"arch: norm_at {norm_at!r}: have input, output")
    shared = dict(d_model=d, norm_eps=float(arch.get("rms_norm_eps", 1e-6)),
                  norm_at=norm_at)
    linattn = (_linear_attention_spec(arch)
               if "linear_attention" in kinds else None)
    moe = None
    if "sparse" in ffns:
        held = int(arch["num_experts"])
        moe = MoESpec(
            routed=int(arch.get("num_routed_experts", held)), held=held,
            offset=int(arch.get("expert_offset", 0)),
            top_k=int(arch["num_experts_per_tok"]),
            width=int(arch["moe_intermediate_size"]),
            shared_width=int(arch.get("shared_expert_intermediate_size", 0)),
            scale=float(arch.get("moe_routed_scaling_factor", 1.0)),
            row_bound=int(arch.get("moe_row_bound", 0)),
            routing_grad=not arch.get("moe_routing_no_grad", False),
        )
        if not arch.get("norm_topk_prob", True):
            raise ValueError("arch: norm_topk_prob false is not built")
        if arch.get("moe_router_logit_softcapping"):
            raise ValueError("arch: router logit soft-capping is not built")
        _check_share(moe)
    specs = []
    for kind, ffn, h in zip(kinds, ffns, heads):
        if kind not in ("full_attention", "sliding_attention",
                        "linear_attention"):
            raise ValueError(f"arch: layer type {kind!r}")
        if ffn not in ("dense", "sparse"):
            raise ValueError(f"arch: mlp layer type {ffn!r}")
        feed_forward = dict(
            d_ff=int(arch["intermediate_size"]) if ffn == "dense" else 0,
            moe=moe if ffn == "sparse" else None)
        if kind == "linear_attention":
            specs.append(LayerSpec(
                **shared, **feed_forward, num_heads=0, num_kv_heads=0,
                head_dim=0, window=None, rope=None, gate=False,
                mixers=("linear_attention", "ffn"), linattn=linattn))
            continue
        specs.append(LayerSpec(
            **shared, **feed_forward, num_heads=int(h),
            num_kv_heads=int(arch["num_key_value_heads"]), head_dim=head_dim,
            window=(int(arch["sliding_window"])
                    if kind == "sliding_attention" else None),
            rope=(None if ropes[kind].get("rope_theta") is None
                  else _rope_spec(ropes[kind], head_dim)),
            gate=gating == "per-head",
            qk_norm=bool(arch.get("qk_norm", False)),
        ))
    return tuple(specs)


def rope_inv_freq(rope: RopeSpec) -> np.ndarray:
    """``(rotary_dim / 2,)`` float64 inverse frequencies. YaRN follows
    transformers' ``_compute_yarn_parameters``: dimensions that turn more
    than ``beta_fast`` times over the original context keep the published
    frequency, those under ``beta_slow`` turns are slowed ``factor``
    times, a linear ramp between. They are static, so they hold at any
    sequence length."""
    dim = rope.rotary_dim
    pos_freqs = rope.theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.yarn is None:
        return 1.0 / pos_freqs
    factor, original, beta_fast, beta_slow = rope.yarn
    turns_dim = lambda turns: (
        dim * math.log(original / (turns * 2 * math.pi))
        / (2 * math.log(rope.theta)))
    low = max(math.floor(turns_dim(beta_fast)), 0)
    high = min(math.ceil(turns_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (1.0 / (factor * pos_freqs)) * ramp + (1.0 / pos_freqs) * (1 - ramp)


#: positions a block of the rotary tables' fine part
_ROPE_BLOCK = 128


def rope_tables(rope: RopeSpec, length: int, head_dim: int):
    """float32 ``cos`` and ``sin`` of shape ``(length, head_dim)``: over the
    rotary dims the half-width angles repeated (rotate-half layout) times
    the attention factor, over the dims that pass through 1 and 0. Made in
    the program from two small float64-exact tables, position
    ``p = 128 a + b`` by ``cos(x + y) = cos x cos y - sin x sin y``: a
    table of all positions would sit in the step program as a constant of
    4 MB a layer, forward and backward, and angles multiplied out in
    float32 are off by 5e-4 at 8,192 tokens."""
    import jax.numpy as jnp

    inv_freq = rope_inv_freq(rope)

    def table(positions):
        angles = np.outer(positions, inv_freq)
        return (jnp.asarray(np.cos(angles), jnp.float32),
                jnp.asarray(np.sin(angles), jnp.float32))

    blocks = -(-length // _ROPE_BLOCK)
    (cos_a, sin_a) = table(np.arange(blocks) * float(_ROPE_BLOCK))
    (cos_b, sin_b) = table(np.arange(_ROPE_BLOCK, dtype=np.float64))
    position = jnp.arange(length)
    a, b = position // _ROPE_BLOCK, position % _ROPE_BLOCK
    cos = cos_a[a] * cos_b[b] - sin_a[a] * sin_b[b]
    sin = sin_a[a] * cos_b[b] + cos_a[a] * sin_b[b]
    factor = jnp.float32(rope.attention_factor)
    rest = (length, head_dim - rope.rotary_dim)
    return (jnp.concatenate([cos * factor, cos * factor,
                             jnp.ones(rest, jnp.float32)], axis=-1),
            jnp.concatenate([sin * factor, sin * factor,
                             jnp.zeros(rest, jnp.float32)], axis=-1))


def rotate_half_matrix(rope: RopeSpec, head_dim: int) -> np.ndarray:
    """``(head_dim, head_dim)`` of 0, 1 and -1 with ``x @ M`` the
    rotate-half partner of ``x`` over the rotary dims (``-x[i + r/2]`` for
    ``i < r/2``, ``x[i - r/2]`` above) and 0 over the others: the
    half-swap as one exact product on the MXU instead of a lane shuffle
    (on the v5e split-and-concatenate took 3.2 ms a pass over a 72-head
    query, a tenth of the whole step; PERF.md section 6, PR 27)."""
    half = rope.rotary_dim // 2
    m = np.zeros((head_dim, head_dim), np.float32)
    for i in range(half):
        m[i + half, i] = -1.0
        m[i, i + half] = 1.0
    return m
