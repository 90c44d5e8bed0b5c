"""Per-kernel validation vs the pure-jnp oracles (interpret mode on CPU),
sweeping shapes and dtypes per the deliverable."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantize import ACT_Q, WEIGHT_Q
from repro.kernels import default_interpret
from repro.kernels.imc_mav import ops as mav_ops
from repro.kernels.imc_mav.ref import imc_mav_ref
from repro.kernels.int8_matmul.int8_matmul import int8_matmul
from repro.kernels.int8_matmul.ref import int8_matmul_ref
from repro.kernels.sga_update.ops import sga_update_tree
from repro.kernels.sga_update.ref import sga_update_ref


def _pm1(key, shape, dtype=jnp.float32):
    return jnp.where(jax.random.bernoulli(key, 0.5, shape), 1.0,
                     -1.0).astype(dtype)


@pytest.mark.parametrize("m,k,n", [(64, 72, 24), (300, 72, 96),
                                   (257, 48, 130), (512, 128, 576)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_imc_mav_shapes_dtypes(m, k, n, dtype):
    key = jax.random.PRNGKey(m * 7 + k + n)
    x = _pm1(key, (m, k), dtype)
    w = _pm1(jax.random.fold_in(key, 1), (k, n), dtype)
    bias = (jnp.round(jax.random.normal(jax.random.fold_in(key, 2),
                                        (n,)) * 10) * 2).astype(jnp.float32)
    flip = _pm1(jax.random.fold_in(key, 3), (n,), jnp.float32)
    out = mav_ops.mav_matmul(x, w, bias, flip)
    ref = imc_mav_ref(x, w, bias, flip)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(ref, np.float32))


def test_imc_mav_with_noise():
    key = jax.random.PRNGKey(0)
    x = _pm1(key, (128, 72))
    w = _pm1(jax.random.fold_in(key, 1), (72, 96))
    bias = jnp.zeros((96,))
    flip = jnp.ones((96,))
    noise = 4.0 * jax.random.normal(jax.random.fold_in(key, 2), (128, 96))
    out = mav_ops.mav_matmul(x, w, bias, flip, noise)
    ref = imc_mav_ref(x, w, bias, flip, noise)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # noise must actually flip some decisions
    clean = imc_mav_ref(x, w, bias, flip)
    assert np.mean(np.asarray(out) != np.asarray(clean)) > 0.01


def test_imc_mav_conv_path_matches_model():
    """conv_mav == the model's conv+mav_sa reference on a group conv."""
    from repro.core import imc
    key = jax.random.PRNGKey(5)
    x = _pm1(key, (2, 40, 48))
    w = _pm1(jax.random.fold_in(key, 1), (3, 24, 96))
    bias = (jnp.round(jax.random.normal(jax.random.fold_in(key, 2),
                                        (96,)) * 5) * 2)
    flip = _pm1(jax.random.fold_in(key, 3), (96,), jnp.float32)
    got = mav_ops.conv_mav(x, w, bias, flip, groups=2)
    counts = imc.binary_group_conv_counts(x, w, groups=2)
    want = imc.mav_sa(counts, bias, flip)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("m,k,n", [(256, 128, 128), (256, 576, 128),
                                   (512, 128, 256)])
@pytest.mark.parametrize("shift", [0, 4, 7])
def test_int8_matmul_bitexact(m, k, n, shift):
    key = jax.random.PRNGKey(m + n + shift)
    x = jax.random.randint(key, (m, k), -127, 128, jnp.int8)
    w = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -127, 128,
                           jnp.int8)
    b = jax.random.randint(jax.random.fold_in(key, 2), (n,), -1000, 1000,
                           jnp.int32)
    out = int8_matmul(x, w, b, shift=shift, interpret=default_interpret())
    ref = int8_matmul_ref(x, w, b, shift=shift)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("n", [1000, 1024, 5003])
@pytest.mark.parametrize("lr,g_th", [(1 / 16, 0.078125), (1 / 128, 0.5)])
def test_sga_update_kernel(n, lr, g_th):
    key = jax.random.PRNGKey(n)
    w = WEIGHT_Q.quantize(jax.random.uniform(key, (n,), minval=-1, maxval=1))
    g = jax.random.normal(jax.random.fold_in(key, 1), (n,)) * 0.05
    a = jax.random.uniform(jax.random.fold_in(key, 2), (n,),
                           minval=-0.05, maxval=0.05)
    nw, na = sga_update_tree({"w": w}, {"w": g}, {"w": a}, lr, g_th)
    rw, ra = sga_update_ref(w, g, a, lr, g_th)
    np.testing.assert_allclose(np.asarray(nw["w"]), np.asarray(rw),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(na["w"]), np.asarray(ra),
                               atol=1e-6)
