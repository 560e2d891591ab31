"""The gated-delta-rule mixer as the benchmark reads it: the scopes it sets,
the shapes of the recurrences a step runs, and the operations and bytes the
rule needs a layer and pass, from shapes: what ``delta_roofline_pct`` is
computed from.

The work is the recurrence's own, ``S' = exp(g_t) S_{t-1}``, ``S_t = S' +
beta_t (v_t - S' k_t) k_t^T`` and ``o_t = S_t q_t`` over ``T`` steps, ``H``
heads, keys of ``d_k`` and values of ``d_v``: the read ``S' k``, the rank-one
update and the read ``S q`` are each one multiply-add a step, head and state
element, ``3 · 2 · T H d_k d_v`` FLOPs forward; backward twice that (the
cotangents of every product's operands). Bytes are one read of every operand
and one write of every result at the arrays' own sizes: ``q`` and ``k`` ``(T,
H, d_k)``, ``v`` and ``o`` ``(T, H, d_v)`` in the operands' item size, ``g``
and ``beta`` ``(T, H)`` float32; backward reads those five operands and ``do``
and writes the five cotangents. Never counted from what an implementation
materialises (the chunked form's ``C x C`` matrices, its inverse, ``W``,
``U``, the chunk states), so that a later kernel is read against the same
work. The decay of the state and the exponentials are left out, so a share is
a little under what the scan does.
"""

from benchmark.lib.peaks import peak

#: the scopes ``Block._linear_attention_mixer`` sets, and the detail line a
#: traced run prints of the time under them (``lib/scope_spans.py``)
SCOPES = ("linattn", "linattn_conv", "delta_rule", "linattn_gate")
SPANS = "delta_spans"


def kernel_shapes(arch: dict, per_chip: int, seq_len: int) -> dict:
    """The shapes of the recurrences the step runs that a reader reads
    (bfloat16 operands), by the first ``num_hidden_layers`` of
    ``layer_types``. The full-attention layers' causal flash kernels have no
    reader in such a cell (PERF.md section 7), so no shape is given for
    them."""
    kinds = list(arch.get("layer_types", []))[:arch["num_hidden_layers"]]
    out = {}
    if "linear_attention" in kinds:
        out["delta"] = {
            "batch": per_chip, "t": seq_len,
            "layers": kinds.count("linear_attention"),
            "heads": arch["linear_num_key_heads"],
            "key_dim": arch["linear_key_head_dim"],
            "value_dim": arch["linear_value_head_dim"], "itemsize": 2}
    return out


def flops(kind: str, shape: dict) -> float:
    """``shape``: ``batch``, ``t``, ``heads``, ``key_dim``, ``value_dim``,
    ``itemsize`` (and ``layers``); ``kind``: ``fwd`` or ``bwd``."""
    forward = (3.0 * 2.0 * shape["batch"] * shape["t"] * shape["heads"]
               * shape["key_dim"] * shape["value_dim"])
    return {"fwd": forward, "bwd": 2.0 * forward}[kind]


def bytes_moved(kind: str, shape: dict) -> float:
    rows = shape["batch"] * shape["t"] * shape["heads"]
    qk = rows * shape["key_dim"] * shape["itemsize"]
    v = rows * shape["value_dim"] * shape["itemsize"]
    scalars = rows * 4
    operands = 2 * qk + v + 2 * scalars  # q, k, v, g, beta
    return {
        "fwd": operands + v,             # ... -> o
        "bwd": operands + v + operands,  # ..., do -> dq, dk, dv, dg, dbeta
    }[kind]


def least_seconds(kind: str, shape: dict, device_kind: str) -> float:
    """The least time the chip could take for one layer's pass: the larger
    of operations over the bf16 peak and bytes over the HBM bandwidth."""
    return max(flops(kind, shape) / peak(device_kind, "bf16_flops_per_s"),
               bytes_moved(kind, shape) / peak(device_kind, "hbm_bytes_per_s"))


def least_seconds_unit(shape: dict, device_kind: str) -> float:
    """One forward and one backward pass of each of ``shape["layers"]``
    layers: what a training step has to do. A second forward that
    rematerialisation runs is the implementation's, and is not counted."""
    return shape["layers"] * (least_seconds("fwd", shape, device_kind)
                              + least_seconds("bwd", shape, device_kind))
