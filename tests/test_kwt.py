"""KWT (repro.models.kwt) on the served path: the MFCC frame ring, the
engine against a float64 numpy reference, the compiled block against the
interpreted tick, the MFCC frame counter, the named scopes and the options
a KWT server refuses.  A small net (width 32, 2 heads, 2 layers, MLP 64)
over a 4000-sample window (23 frames) keeps it fast on the CPU."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _equiv import advance_to, assert_events_equal, assert_server_equal
from repro.models import kwt, mfcc
from repro.serving import (CompiledTickConfig, DynamicHopConfig, FaultConfig,
                           HealthConfig, ObsConfig, StreamServer, VADConfig,
                           kwt_stream)

CFG = kwt.KWTConfig(sample_len=4000, dim=32, heads=2, depth=2, mlp_dim=64)
CFG32 = kwt.KWTConfig(sample_len=4000, dim=32, heads=2, depth=2, mlp_dim=64,
                      compute_dtype="float32")
HOP = 160


@pytest.fixture(scope="module")
def params():
    return kwt.init_params(jax.random.PRNGKey(7), CFG)


def _audio(n, seed=0):
    """Speech-like audio on the 8-bit grid: a few tones over noise."""
    rng = np.random.default_rng(seed)
    t = np.arange(n) / 16000.0
    x = sum(rng.uniform(0.1, 0.3) * np.sin(2 * np.pi * rng.uniform(200, 4000)
                                           * t + rng.uniform(0, 6))
            for _ in range(4))
    x = x + 0.01 * rng.standard_normal(n)
    return (np.round(np.clip(x, -1, 1) * 127) / 127).astype(np.float32)


# -- a float64 numpy reference ----------------------------------------------


def _np_mfcc(window, cfg):
    n, nfft = cfg.frame_len, cfg.n_fft
    hann = 0.5 - 0.5 * np.cos(2 * np.pi * np.arange(n) / n)
    f = (len(window) - n) // cfg.frame_shift + 1
    frames = np.stack([window[i * cfg.frame_shift:i * cfg.frame_shift + n]
                       for i in range(f)]).astype(np.float64)
    mag = np.abs(np.fft.rfft(frames * hann, n=nfft))
    hz = np.linspace(0, cfg.sample_rate / 2, nfft // 2 + 1)

    def mel(v):
        return 1127.0 * np.log(1 + np.asarray(v) / 700.0)

    edges = np.linspace(mel(cfg.mel_lo_hz), mel(cfg.mel_hi_hz),
                        cfg.n_mels + 2)
    fb = np.zeros((len(hz), cfg.n_mels))
    for j in range(cfg.n_mels):
        m = mel(hz[1:])
        fb[1:, j] = np.maximum(0, np.minimum(
            (m - edges[j]) / (edges[j + 1] - edges[j]),
            (edges[j + 2] - m) / (edges[j + 2] - edges[j + 1])))
    logmel = np.log(mag @ fb + cfg.log_floor)
    k = np.arange(cfg.n_mels)
    dct = np.stack([np.sqrt((1 if c else 0.5) * 2 / cfg.n_mels)
                    * np.cos(np.pi * (k + 0.5) * c / cfg.n_mels)
                    for c in range(cfg.n_mfcc)], axis=1)
    return logmel @ dct


def _np_logits(p, window, cfg):
    p = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    d, h = cfg.dim, cfg.heads
    hd = d // h

    def lin(q, x):
        return x @ q["w"] + q["b"]

    def norm(q, x):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / np.sqrt(var + cfg.ln_eps) * q["scale"] + q["bias"]

    x = np.concatenate([p["cls"][None], lin(p["embed"],
                                            _np_mfcc(window, cfg))])
    x = x + p["pos"]
    t = len(x)
    for q in p["layers"]:
        qkv = lin(q["qkv"], x)
        heads = []
        for j in range(h):
            qs, ks, vs = (qkv[:, i * d + j * hd:i * d + (j + 1) * hd]
                          for i in range(3))
            s = qs @ ks.T / math.sqrt(hd)
            a = np.exp(s - s.max(-1, keepdims=True))
            heads.append((a / a.sum(-1, keepdims=True)) @ vs)
        x = norm(q["ln1"], x + lin(q["out"], np.concatenate(heads, 1)))
        u = lin(q["mlp1"], x)
        g = 0.5 * u * (1 + np.vectorize(math.erf)(u / math.sqrt(2)))
        x = norm(q["ln2"], x + lin(q["mlp2"], g))
    assert x.shape == (t, d)
    return lin(p["head"], x[0])


def _ref_hops(p, audio, n_hops, cfg, hop=HOP):
    return np.stack([_np_logits(p, audio[j * hop:j * hop + cfg.sample_len],
                                cfg) for j in range(n_hops + 1)])


# -- the frame ring ---------------------------------------------------------


@pytest.mark.parametrize("hop", [160, 320])
def test_mfcc_ring_matches_full_window(params, hop):
    """After each of 20 hops the ring holds the current window's frames
    computed from scratch, within 1e-6 (absolute, and relative to the
    coefficient): the same float32 numbers but for an ulp or two where the
    CPU's log and matmul kernels round a lone frame and a window's 23
    frames differently."""
    n = 20
    audio = _audio(CFG.sample_len + n * hop)[None]
    eng = kwt_stream.KWTEngine(params, CFG, hop)
    _, st = eng.init(jnp.asarray(audio[:, :CFG.sample_len]), None)
    for j in range(1, n + 1):
        _, st = eng.step(st, jnp.asarray(
            audio[:, CFG.sample_len + (j - 1) * hop:CFG.sample_len + j * hop]))
        win = audio[:, j * hop:j * hop + CFG.sample_len]
        full = mfcc.audio_mfcc(jnp.asarray(win), CFG)
        np.testing.assert_allclose(np.asarray(st.frames), np.asarray(full),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(st.tail),
                                      win[:, -(CFG.frame_len
                                               - CFG.frame_shift):])
    assert st.frames.shape == (1, CFG.n_frames, CFG.n_mfcc)
    assert int(st.hop[0]) == n + 1


def test_mfcc_matches_numpy():
    win = _audio(CFG.sample_len)
    got = mfcc.audio_mfcc(jnp.asarray(win[None]), CFG)[0]
    np.testing.assert_allclose(np.asarray(got), _np_mfcc(win, CFG),
                               rtol=0, atol=2e-4)


# -- the engine against the reference ---------------------------------------


def _engine_logits(params, cfg, audio, n_hops, hop=HOP, multi=None):
    """init, then ``n_hops`` hops one at a time (or in ``multi``-hop
    calls): logits (n_hops + 1, C) of a batch of two copies of a stream."""
    eng = kwt_stream.KWTEngine(params, cfg, hop)
    x = np.stack([audio, audio])
    lg, st = eng.init(jnp.asarray(x[:, :cfg.sample_len]), None)
    out = [np.asarray(lg)]
    j = 0
    while j < n_hops:
        a = x[:, cfg.sample_len + j * hop:]
        if multi:
            lgs, st = eng.multi_step(st, jnp.asarray(a[:, :multi * hop]),
                                     multi)
            out.extend(np.asarray(lgs).swapaxes(0, 1))
            j += multi
        else:
            lg, st = eng.step(st, jnp.asarray(a[:, :hop]))
            out.append(np.asarray(lg))
            j += 1
    out = np.stack(out)
    np.testing.assert_array_equal(out[:, 0], out[:, 1])   # row-parallel
    return out[:, 0]


@pytest.mark.parametrize("multi", [None, 3])
def test_engine_float32_matches_reference(params, multi):
    n = 6
    audio = _audio(CFG.sample_len + n * HOP, seed=1)
    got = _engine_logits(params, CFG32, audio, n, multi=multi)
    want = _ref_hops(params, audio, n, CFG32)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5)


def test_engine_bfloat16_close_to_reference(params):
    """At the stated precision every matmul operand (weights included) is
    rounded to bfloat16, a relative error of up to 2^-9 each; through the
    embedding, two layers and the head that leaves logits of order one
    within a few hundredths of the float64 reference — and far from the
    reference of another stream position (a ring off by one frame)."""
    n = 6
    audio = _audio(CFG.sample_len + (n + 1) * HOP, seed=2)
    got = _engine_logits(params, CFG, audio, n)
    want = _ref_hops(params, audio, n + 1, CFG32)
    err = np.abs(got - want[:-1]).max()
    assert err < 0.05, err
    assert np.abs(got - want[1:]).max() > 10 * err


# -- the server: compiled block, counters, scopes ---------------------------


def _server(params, compiled, slots=3, **kw):
    return StreamServer(params, CFG, hop=HOP, slots=slots, use_kernel=False,
                        compiled=compiled, obs=ObsConfig(), seed=5, **kw)


def _feed(srv, lengths):
    for i, n in enumerate(lengths):
        srv.submit(f"mic{i}", _audio(CFG.sample_len + n * HOP, seed=10 + i))


def test_compiled_block_matches_interpreted_tick(params):
    """A KWT server serves through step_block's compiled scan, and its
    events, states and counters equal the interpreted tick's."""
    comp = _server(params, CompiledTickConfig(block=4))
    plain = _server(params, None)
    for srv in (comp, plain):
        _feed(srv, [9, 5, 12])
    for ticks in (1, 6, 13):
        ev_c = advance_to(comp, ticks)
        ev_p = advance_to(plain, ticks)
        assert_events_equal(ev_c, ev_p, f"tick {ticks}")
        assert_server_equal(comp, plain, f"tick {ticks}")
    assert comp.stats()["compiled"]["blocks"] >= 3
    assert isinstance(comp.engine, kwt_stream.KWTEngine)


def test_mfcc_frames_counter(params):
    """serving.mfcc_frames books a window's frames per admitted stream and
    one frame per computed hop: per stream, 23 + its hops."""
    hops = [9, 5, 12]
    for compiled in (CompiledTickConfig(block=4), None):
        srv = _server(params, compiled)
        _feed(srv, hops)
        srv.drain()
        assert srv.metrics.value("serving.mfcc_frames") == sum(
            CFG.n_frames + h for h in hops)
        assert srv.metrics.value("serving.hops", kind="speech") == sum(hops)
        per = srv.stats()["per_stream"]
        assert [per[f"mic{i}"]["hops"] for i in range(3)] == [
            h + 1 for h in hops]
    assert kwt.KWT3.n_frames == 98


def test_step_scopes_in_lowered_block(params):
    """The served step carries the named scopes mfcc, embed, enc0.. and
    head (and the decision head's) inside the compiled block."""
    srv = _server(params, CompiledTickConfig(block=2))
    _feed(srv, [4, 4, 4])
    srv.step()                                   # admissions
    fn = srv._compiled._main_fn(1, False, False, False)
    n, hop = srv.slots, HOP
    text = fn.lower(srv._state, srv._dstate, jnp.zeros((2, n, hop)),
                    jnp.ones((2, n), bool), None, None, None, None, None,
                    None).as_text(debug_info=True)
    scopes = ["mfcc", "embed", "head", "decision"]
    for scope in scopes + [f"enc{i}" for i in range(CFG.depth)]:
        assert f"/{scope}/" in text or f'"{scope}/' in text, scope


def test_snapshot_restore(params):
    a = _server(params, CompiledTickConfig(block=4))
    _feed(a, [8, 8, 8])
    advance_to(a, 3)
    snap = a.snapshot()
    b = _server(params, CompiledTickConfig(block=4))
    b.restore(snap)
    assert_events_equal(advance_to(a, 8), advance_to(b, 8), "restored")
    assert_server_equal(a, b, "restored")


def test_param_count_and_macs():
    assert kwt.KWT3.param_count() == 5_367_756
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(
        lambda: kwt.init_params(jax.random.PRNGKey(0), kwt.KWT3)))
    assert sum(int(np.prod(x.shape)) for x in leaves) == 5_367_756
    # one hop: a frame's DFT/mel/DCT, the 98-frame embedding, 11 full
    # layers, the class token's last layer and the head
    assert 2 * kwt.decision_macs(kwt.KWT3, 1) == 1_063_714_896


# -- refused options --------------------------------------------------------


REFUSED = {
    "chip_offsets": dict(chip_offsets={"conv1": jnp.zeros(8)}),
    "sa_noise_std": dict(sa_noise_std=1.0),
    "use_kernel": dict(use_kernel=True),
    "streaming": dict(streaming=False),
    "vad": dict(vad=VADConfig()),
    "dynamic_hop": dict(dynamic_hop=DynamicHopConfig()),
    "faults": dict(faults=FaultConfig()),
    "health": dict(health=HealthConfig()),
    "profiles": dict(profiles=object()),
    "obs": dict(obs=ObsConfig(audit="flag")),
}


@pytest.mark.parametrize("option", sorted(REFUSED))
def test_refuses_option(params, option):
    kw = dict(use_kernel=False, obs=ObsConfig())
    kw.update(REFUSED[option])
    with pytest.raises(ValueError, match=f"cannot take: {option} "):
        StreamServer(params, CFG, hop=HOP, **kw)


def test_refuses_recorder_and_customization(params):
    with pytest.raises(ValueError, match="obs"):
        StreamServer(params, CFG, hop=HOP, use_kernel=False,
                     obs=ObsConfig(recorder=16))
    srv = _server(params, None)
    with pytest.raises(ValueError, match="customization"):
        srv.customize("mic0")
    with pytest.raises(ValueError, match="customization"):
        srv.install_custom("mic0", None)


def test_refuses_hop_off_the_frame_grid(params):
    with pytest.raises(ValueError, match="frame shift"):
        StreamServer(params, CFG, hop=64, use_kernel=False, obs=ObsConfig())
