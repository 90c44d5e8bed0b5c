"""Public wrapper: pad to tile boundaries, Q-format value-domain interface."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quantize import ACT_Q, WEIGHT_Q, QFormat
from repro.kernels import default_interpret
from repro.kernels.int8_matmul.int8_matmul import int8_matmul


def _pad(x, axis, mult, value=0):
    p = (-x.shape[axis]) % mult
    if p == 0:
        return x
    w = [(0, 0)] * x.ndim
    w[axis] = (0, p)
    return jnp.pad(x, w, constant_values=value)


def quantized_fc(feats: jax.Array, w: jax.Array, b: jax.Array,
                 act_fmt: QFormat = ACT_Q, w_fmt: QFormat = WEIGHT_Q,
                 interpret: bool | None = None) -> jax.Array:
    """Value-domain FC through the int8 kernel.

    feats real (M, D) -> codes via act_fmt; w/b via w_fmt.  The product grid
    is act_fmt.scale * w_fmt.scale; the kernel right-shift brings it back to
    act_fmt's grid: shift = frac(act)+frac(w) - frac(act) = frac(w).
    Returns real values on the act grid, shape (M, N).
    """
    if interpret is None:
        interpret = default_interpret()
    m0, n0 = feats.shape[0], w.shape[1]
    xq = act_fmt.to_int(feats, jnp.int8)
    wq = w_fmt.to_int(w, jnp.int8)
    # bias joins the accumulator on the product grid
    bq = jnp.round(b / (act_fmt.scale * w_fmt.scale)).astype(jnp.int32)
    xq = _pad(_pad(xq, 0, 256), 1, 128)
    wq = _pad(_pad(wq, 0, 128), 1, 128)
    bq = _pad(bq, 0, 128)
    out = int8_matmul(xq, wq, bq, shift=w_fmt.frac_bits, out_max=act_fmt.qmax,
                      interpret=interpret)
    return out[:m0, :n0].astype(jnp.float32) * act_fmt.scale
