"""Compile-only checks of the served path's Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each test lowers a kernel with
``interpret=False`` at the paper network's real widths and compiles it
with the TPU compiler for a described (not attached) v5e, which refuses
what Mosaic cannot tile and kernels that overflow VMEM.  Covered:

* ``fused_conv_mav`` for every IMC layer (conv1..conv5 of ``KWSConfig()``)
  at the full 16000-sample window with B = 1 and B = 4 slots, and at the
  hop-64 streaming tail with B = 4, clean and with an SA-noise operand;
* ``sga_update_rows`` (the batched customization update) at B = 1, 2, 4.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests; the tests skip only
where no v5e topology can be described.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import imc
from repro.kernels.imc_mav import ops as mav_ops
from repro.kernels.sga_update.sga_update import sga_update_rows
from repro.models import kws as m
from repro.serving import make_stream_geometry

CFG = m.KWSConfig()
HOP = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip can be written to the persistent
    # cache but never read back here: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache

    def cache(on):
        jax.config.update("jax_enable_compilation_cache", on)
        cc.reset_cache()

    cache(False)
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure: no topology
        cache(was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    cache(was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _assert_one_kernel(compiled):
    hlo = compiled.as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1


@pytest.mark.parametrize("layer", [1, 2, 3, 4, 5],
                         ids=lambda i: f"conv{i}")
@pytest.mark.parametrize("where,b,noisy", [
    ("window", 1, False), ("window", 4, False),
    ("tail", 4, False), ("tail", 4, True)],
    ids=["window-B1", "window-B4", "tail-B4", "tail-B4-noise"])
def test_fused_conv_mav_compiles_for_v5e(one_chip, layer, where, b, noisy):
    geom = make_stream_geometry(CFG, HOP).layers[layer]
    t_in = geom.t_in if where == "window" else geom.tail_in
    groups, c_in, c_out = (CFG.groups(layer), CFG.channels[layer - 1],
                           CFG.channels[layer])
    k, stride = CFG.kernels[layer], CFG.strides[layer]
    pool = CFG.pools[layer]
    lt = imc.make_group_pack_layout(groups, c_out // groups, k,
                                    c_in // groups)
    k_pad = -(-lt.k_pack // lt.lanes) * lt.lanes
    n_pad = -(-lt.n_pack // lt.lanes) * lt.lanes
    t_conv = (t_in - k) // stride + 1

    def layer_fn(x, w, wp, bias_p, flip_p, off, noise):
        packed = imc.PackedLayer(lt, wp, bias_p, flip_p)
        return mav_ops.fused_conv_mav(
            x, w, None, None, groups=groups, stride=stride, pool=pool,
            chip_offset=off, sa_noise=noise, interpret=False,
            packed=packed)

    s = lambda shape: _spec(shape, one_chip)   # noqa: E731
    args = (s((b, t_in, c_in)), s((k, c_in // groups, c_out)),
            s((lt.packs, k_pad, n_pad)), s((lt.packs, n_pad)),
            s((lt.packs, n_pad)), s((c_out,)),
            s((b, t_conv, c_out)) if noisy else None)
    compiled = jax.jit(layer_fn).lower(*args).compile()
    _assert_one_kernel(compiled)
    out = jax.eval_shape(layer_fn, *args)
    assert out.shape == (b, t_conv // pool, c_out)


@pytest.mark.parametrize("b", [1, 2, 4])
def test_sga_update_rows_compiles_for_v5e(one_chip, b):
    # one row per session: the paper head's [fc_w, fc_b], padded to the
    # kernel block as sga_update_batch does
    n = CFG.channels[-1] * CFG.num_classes + CFG.num_classes
    n += (-n) % 1024
    s = lambda shape: _spec(shape, one_chip)   # noqa: E731
    compiled = jax.jit(
        lambda w, g, a, lr, th: sga_update_rows(w, g, a, lr, th,
                                                interpret=False)
    ).lower(s((b, n)), s((b, n)), s((b, n)), s((b,)), s((b,))).compile()
    _assert_one_kernel(compiled)
