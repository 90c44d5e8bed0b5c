"""Public wrappers: pytree-flat and session-batched SGA updates through
the Pallas kernels."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import default_interpret
from repro.kernels.sga_update.sga_update import sga_update, sga_update_rows


def sga_update_tree(params, grads, accums, lr: float, g_th: float,
                    interpret: bool | None = None):
    """Apply the fused update leaf-wise; shapes preserved."""
    if interpret is None:
        interpret = default_interpret()
    leaves_w, treedef = jax.tree_util.tree_flatten(params)
    leaves_g = treedef.flatten_up_to(grads)
    leaves_a = treedef.flatten_up_to(accums)
    new_w, new_a = [], []
    for w, g, a in zip(leaves_w, leaves_g, leaves_a):
        shape = w.shape
        flat = lambda x: x.reshape(-1)
        n = w.size
        pad = (-n) % 1024
        wp = jnp.pad(flat(w), (0, pad))
        gp = jnp.pad(flat(g), (0, pad))
        ap = jnp.pad(flat(a), (0, pad))
        nw, na = sga_update(wp, gp, ap, lr=float(lr), g_th=float(g_th),
                            interpret=interpret)
        new_w.append(nw[:n].reshape(shape))
        new_a.append(na[:n].reshape(shape))
    return (jax.tree_util.tree_unflatten(treedef, new_w),
            jax.tree_util.tree_unflatten(treedef, new_a))


def sga_update_batch(w: jax.Array, g: jax.Array, accum: jax.Array,
                     lr: jax.Array, g_th: jax.Array, *,
                     w_scale: float = 1.0 / 128, w_max: float = 127.0 / 128,
                     a_scale: float = 2.0 ** -15,
                     interpret: bool | None = None):
    """Session-batched fused SGA update: ONE ``pallas_call`` for B rows.

    w/g/accum: (B, N) stacked flattened optimizer states (one row per
    enrollment session — repro.serving.customize packs [fc_w, fc_b] and
    their SGA banks per row); lr/g_th: (B,) per-row scalars, since each
    session sits at its own point of the LR schedule.  Pads N to the
    kernel block and crops back; returns (new_w, new_accum)."""
    if interpret is None:
        interpret = default_interpret()
    b, n = w.shape
    pad = (-n) % 1024
    wp = jnp.pad(w, ((0, 0), (0, pad)))
    gp = jnp.pad(g, ((0, 0), (0, pad)))
    ap = jnp.pad(accum, ((0, 0), (0, pad)))
    nw, na = sga_update_rows(wp, gp, ap, jnp.asarray(lr, jnp.float32),
                             jnp.asarray(g_th, jnp.float32),
                             w_scale=w_scale, w_max=w_max, a_scale=a_scale,
                             interpret=interpret)
    return nw[:, :n], na[:, :n]
