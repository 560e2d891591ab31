"""Operations and bytes the Pallas flash-attention kernels need a call, from
shapes and the mask: what their shares of the roofline are computed from.

Counted from the live ``(query, key)`` pairs of the mask, never from the tiles
the kernel walks (a tile is partly masked) nor from padded buffers. ``pairs``
a head: causal ``T (T + 1) / 2``; with a window ``w`` query ``i`` sees
``min(i + 1, w)`` keys, ``w T - w (w - 1) / 2`` in all.

A pair costs a multiply-add (2 FLOPs) a head dimension in each matrix product
the kernel's algorithm has to make: the forward two (``Q Kᵀ`` and ``P V``);
dQ three (``Q Kᵀ`` again, because the probabilities are recomputed from the
saved log-sum-exp and never stored, ``dO Vᵀ`` and ``dS K``); dK/dV four
(``K Qᵀ``, ``Pᵀ dO``, ``V dOᵀ`` and ``dSᵀ Q``). Bytes are one read of every
operand and one write of every result at the arrays' own sizes: grouped KV
heads are read once a KV head, the per-row statistics (log-sum-exp, ``D``)
as one float32 a row. The softmax's exponentials are left out, so a share is
a little under what the kernel does.
"""

from benchmark.lib.peaks import peak

PRODUCTS = {"fwd": 2, "dq": 3, "dkv": 4}


def live_pairs(t: int, window=None) -> int:
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * t - window * (window - 1) // 2


def flops(kind: str, shape: dict) -> float:
    """``shape``: ``batch``, ``heads``, ``kv_heads``, ``t``, ``d``,
    ``window`` (or None), ``itemsize``."""
    pairs = live_pairs(shape["t"], shape.get("window"))
    return (2.0 * PRODUCTS[kind] * shape["d"] * pairs
            * shape["batch"] * shape["heads"])


def bytes_moved(kind: str, shape: dict) -> float:
    rows = shape["batch"] * shape["t"]
    q = rows * shape["heads"] * shape["d"] * shape["itemsize"]
    kv = rows * shape["kv_heads"] * shape["d"] * shape["itemsize"]
    stat = rows * shape["heads"] * 4
    return {
        "fwd": q + 2 * kv + q + stat,          # q, k, v -> out, lse
        "dq": 2 * q + 2 * kv + 2 * stat + q,   # q, dO, k, v, lse, D -> dQ
        "dkv": 2 * q + 2 * kv + 2 * stat + 2 * kv,  # ... -> dK, dV
    }[kind]


def least_seconds(kind: str, shape: dict, device_kind: str) -> float:
    """The least time the chip could take for one call: the larger of
    operations over the bf16 peak and bytes over the HBM bandwidth."""
    return max(flops(kind, shape) / peak(device_kind, "bf16_flops_per_s"),
               bytes_moved(kind, shape) / peak(device_kind, "hbm_bytes_per_s"))
