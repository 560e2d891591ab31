"""The compressed-convolutional-attention mixer and the MLP router as the
benchmark reads them: the scopes they set, the shapes of what a step runs,
and the operations and bytes CCA's mixing needs a layer and pass, from
shapes: what ``cca_mix_roofline_pct`` is computed from.

The mixing is what lies under the scope ``cca_mix`` (``ops/cca.py``): the
value shift, the two causal convolutions over ``u = [q0 ; k0]`` (``H = H_q +
H_kv`` heads of ``d``), the q-k mean and the L2 norms. Its products are the
convolutions' multiply-adds: the depthwise one ``2 · time0 · T H d`` FLOPs,
the per-head one ``2 · time1 · T H d²``; the mean, the norms and the shift
are left out, so a share is a little under what the mixing does. Backward
twice the forward (the cotangents of both operands of every product). Bytes
are one read of every operand and one write of every result at the arrays'
own sizes, in the operands' item size: forward reads ``q0`` ``(T, H_q d)``,
``k0`` ``(T, H_kv d)``, the value projection's two halves ``(T, H_kv d / 2)``
each and the taps, and writes ``q`` ``(T, H_q d)``, ``k`` and ``v`` ``(T,
H_kv d)``; backward reads those operands and the results' cotangents and
writes the operands' cotangents. Never counted from what an implementation
materialises (``c``, ``z``, the concatenations), so that a later kernel is
read against the same work.
"""

from benchmark.lib.peaks import peak

#: the scopes ``Block._cca_mixer`` and ``Block._router_mlp`` set, and the
#: detail line a traced run prints of the time under them
#: (``lib/scope_spans.py``)
SCOPES = ("cca_mix", "router_mlp")
SPANS = "cca_spans"


def kernel_shapes(arch: dict, per_chip: int, seq_len: int) -> dict:
    """The shapes of what the step runs that a reader reads (bfloat16
    operands): the mixing a layer (``cca_mix``) and the grouped causal flash
    kernels it feeds (``flash_causal``, ``lib/lm_kernels.py``'s shape)."""
    layers = arch["num_hidden_layers"]
    heads, kv_heads = arch["num_attention_heads"], arch["num_key_value_heads"]
    return {
        "cca_mix": {
            "batch": per_chip, "t": seq_len, "layers": layers,
            "heads": heads, "kv_heads": kv_heads, "d": arch["head_dim"],
            "time0": arch["cca_time0"], "time1": arch["cca_time1"],
            "itemsize": 2},
        "flash_causal": {
            "batch": per_chip, "heads": heads, "kv_heads": kv_heads,
            "t": seq_len, "d": arch["head_dim"], "window": None,
            "itemsize": 2, "layers": layers},
    }


def flops(kind: str, shape: dict) -> float:
    """``shape``: ``cca_mix``'s; ``kind``: ``fwd`` or ``bwd``."""
    rows = shape["batch"] * shape["t"] * (shape["heads"] + shape["kv_heads"])
    forward = 2.0 * rows * shape["d"] * (
        shape["time0"] + shape["time1"] * shape["d"])
    return {"fwd": forward, "bwd": 2.0 * forward}[kind]


def bytes_moved(kind: str, shape: dict) -> float:
    size, d = shape["itemsize"], shape["d"]
    t = shape["batch"] * shape["t"]
    h, h_kv = shape["heads"], shape["kv_heads"]
    taps = (h + h_kv) * d * (shape["time0"] + shape["time1"] * d) * size
    operands = t * (h + h_kv) * d * size + t * h_kv * d * size + taps
    results = t * (h + 2 * h_kv) * d * size  # q, k, v
    return {
        "fwd": operands + results,
        "bwd": operands + results + operands,  # ..., dq, dk, dv -> d operands
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
