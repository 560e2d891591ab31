"""Operations and bytes the Mamba-2 recurrence needs a layer and pass, from
shapes: what ``ssd_roofline_pct`` is computed from.

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
