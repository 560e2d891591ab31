"""The Mamba-2 mixer as the benchmark reads it: the scopes it sets, the shapes
of the kernels a step runs, and the operations and bytes the recurrence needs
a layer and pass, from shapes: what ``ssd_roofline_pct`` is computed from.

The work is the recurrence's own, ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
B_t^T`` and ``y_t = S_t C_t + D x_t`` over ``T`` steps, ``H`` heads, ``P``
channels a head and a state of ``N``: the state's update and its read are
each one multiply-add a step, head, channel and state element, ``2 · 2 · T H
P N`` FLOPs forward; backward twice that (the cotangents of both products'
operands). Bytes are one read of every operand and one write of every result
at the arrays' own sizes: ``x`` and ``y`` ``(T, H, P)``, ``B`` and ``C`` ``(T,
G, N)`` (once a group, not once a head) in the operands' item size, ``dt``
``(T, H)`` float32; backward reads those four operands and ``dy`` and writes
the four cotangents. Never counted from what an implementation materialises
(the chunked form's decay masks, its chunk states), so that a later kernel is
read against the same work. ``A``, ``D`` and the decays' exponentials are left
out, so a share is a little under what the scan does.
"""

from benchmark.lib.peaks import peak

#: the scopes ``Block._ssm_mixer`` sets, and the detail line a traced run
#: prints of the time under them (``lib/scope_spans.py``)
SCOPES = ("ssm", "ssm_conv", "ssd", "ssm_gate")
SPANS = "ssm_spans"


def kernel_shapes(arch: dict, per_chip: int, seq_len: int) -> dict:
    """The shapes of the kernels and scans the step runs (bfloat16
    operands), by the first ``num_hidden_layers`` letters of
    ``hybrid_override_pattern``: ``flash_causal`` (``lib/lm_kernels``) where
    it has attention layers, ``ssd`` (this file) where it has Mamba-2
    layers."""
    n = arch["num_hidden_layers"]
    pattern = arch.get("hybrid_override_pattern", "")[:n]
    out = {}
    if "*" in pattern:
        out["flash_causal"] = {
            "batch": per_chip, "heads": arch["num_attention_heads"],
            "kv_heads": arch["num_key_value_heads"], "t": seq_len,
            "d": arch["head_dim"], "window": None, "itemsize": 2}
    if "M" in pattern:
        out["ssd"] = {
            "batch": per_chip, "t": seq_len, "layers": pattern.count("M"),
            "heads": arch["mamba_num_heads"],
            "head_dim": arch["mamba_head_dim"], "groups": arch["n_groups"],
            "state": arch["ssm_state_size"], "itemsize": 2}
    return out


def flops(kind: str, shape: dict) -> float:
    """``shape``: ``batch``, ``t``, ``heads``, ``head_dim``, ``groups``,
    ``state``, ``itemsize``; ``kind``: ``fwd`` or ``bwd``."""
    forward = (2.0 * 2.0 * shape["batch"] * shape["t"] * shape["heads"]
               * shape["head_dim"] * shape["state"])
    return {"fwd": forward, "bwd": 2.0 * forward}[kind]


def bytes_moved(kind: str, shape: dict) -> float:
    rows = shape["batch"] * shape["t"]
    x = rows * shape["heads"] * shape["head_dim"] * shape["itemsize"]
    bc = rows * shape["groups"] * shape["state"] * shape["itemsize"]
    dt = rows * shape["heads"] * 4
    operands = x + 2 * bc + dt
    return {
        "fwd": operands + x,             # x, B, C, dt -> y
        "bwd": operands + x + operands,  # x, B, C, dt, dy -> dx, dB, dC, ddt
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
