"""Operations the forward and backward passes require, from shapes.

``jaxpr_flops`` is a copy of ``bench._jaxpr_flops`` (sound arithmetic, PERF.md
verdict table): matmul and convolution FLOPs at 2 a multiply-add in the jaxpr
of ``jax.grad(loss_fn)``, scan bodies times their trip count. Recomputed
operations never enter, because the jaxpr is that of the plain gradient and
not of what the compiler made of it.
"""

import numpy as np


def _prod(dims) -> float:
    return float(np.prod(list(dims), dtype=np.float64))


def jaxpr_flops(jaxpr) -> float:
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            batch = _prod(lhs[i] for i in lb)
            contract = _prod(lhs[i] for i in lc)
            lhs_free = _prod(
                d for i, d in enumerate(lhs) if i not in lc and i not in lb
            )
            rhs_free = _prod(
                d for i, d in enumerate(rhs) if i not in rc and i not in rb
            )
            total += 2.0 * batch * contract * lhs_free * rhs_free
        elif name == "conv_general_dilated":
            out = eqn.outvars[0].aval.shape
            rhs = eqn.invars[1].aval.shape
            rhs_spec = eqn.params["dimension_numbers"].rhs_spec
            k_spatial = _prod(rhs[i] for i in rhs_spec[2:])
            # the kernel's input-feature dim is already per group
            total += 2.0 * _prod(out) * k_spatial * float(rhs[rhs_spec[1]])
        elif eqn.params:
            mult = float(eqn.params.get("length", 1)) if name == "scan" else 1.0
            for val in eqn.params.values():
                for sub in val if isinstance(val, (tuple, list)) else (val,):
                    inner = getattr(sub, "jaxpr", None)
                    if inner is not None and hasattr(inner, "eqns"):
                        total += mult * jaxpr_flops(inner)
                    elif hasattr(sub, "eqns"):
                        total += mult * jaxpr_flops(sub)
    return total


def train_flops_per_sample(loss_fn, params, x, y) -> float:
    """Forward-and-backward FLOPs of one sample of the batch ``(x, y)``;
    arguments may be arrays or ``jax.ShapeDtypeStruct``s (nothing runs)."""
    import jax

    jaxpr = jax.make_jaxpr(jax.grad(loss_fn))(params, x, y)
    flops = jaxpr_flops(jaxpr.jaxpr)
    if not np.isfinite(flops) or flops <= 0:
        raise ValueError(f"counted {flops} FLOPs in the gradient's jaxpr")
    return flops / x.shape[0]
