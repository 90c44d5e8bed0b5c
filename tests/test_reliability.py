"""The fault-injection / self-healing / crash-safety contract:

* the fault model (repro.core.faults) is seeded-deterministic, resumable
  from its own snapshot, and its deltas ride the existing batched-launch
  operands — a faulted server equals a clean server whose jnp reference
  path is fed the same per-layer bias deltas, bit for bit;
* the one-launch-per-layer invariant HOLDS under fault + canary: a tick
  whose batch carries live hops and a canary hop still traces exactly one
  pallas_call per IMC layer (trace-enforced);
* canary health monitoring detects an injected fault within ticks,
  localizes the faulty layer and columns (in bias-channel coordinates —
  the injection's own coordinates), and walks healthy -> degraded ->
  quarantined; recompensation heals drift faults back to healthy;
  unrecoverable stuck columns are permanently masked and written into the
  expected reference so the monitor converges instead of flapping;
* snapshot/restore round-trips the COMPLETE serving state — slot carries,
  GAP rings, decision/VAD state, noise-field keys, fault + health state,
  mid-flight customization sessions — and the restored server continues
  bit-identically (events and states) to an uninterrupted run;
* satellites: profile auto-install at admission + stale-profile eviction
  on store mtime change; duty-aware dynamic hop (silence widens faster,
  forced-speech bit-exactness preserved); chip-accurate retention silence
  fill (pinned to the constant fill at zero read noise); a randomized
  soak interleaving admissions/evictions/faults/snapshots.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

import _equiv as eq
from repro.core import faults as flt
from repro.core import imc
from repro.models import kws as m
from repro.serving import (DynamicHopConfig, HealthConfig, StreamServer,
                           VADConfig)
from repro.serving import customize as cz
from repro.serving import stream as sv
from repro.serving import vad as vd
from repro.checkpoint.profiles import ProfileStore

L, HOP = 640, 64
CFG = m.KWSConfig(sample_len=L)


@pytest.fixture(scope="module")
def folded():
    params = m.init_params(jax.random.PRNGKey(5), CFG)
    state = m.init_state(CFG)
    return m.fold_params(params, state, CFG, pack=True)


def _chip(std=4.0):
    chans = {f"conv{i}": CFG.channels[i]
             for i in range(1, CFG.num_conv_layers)}
    return imc.sample_chip_offsets(
        jax.random.PRNGKey(9), chans,
        imc.IMCNoiseParams(mav_offset_std=std))


def _result(hw, bump_layer=None, bump=1.0):
    """A synthetic CustomizationResult: the base fold's own arrays, with
    an optional integer bias bump on one layer (a visible rider)."""
    hwp, _ = m.as_hw_params(hw)
    bias = {n: np.asarray(hwp.bias[n], np.int32).copy()
            for n in CFG.imc_layer_names()}
    if bump_layer is not None:
        bias[bump_layer] = bias[bump_layer] + int(bump)
    return cz.CustomizationResult(
        bias=bias, fc_w=np.asarray(hwp.fc_w), fc_b=np.asarray(hwp.fc_b),
        epochs=1, n_utterances=2, history=[], energy={})


def _delta_result(hw, deltas):
    """The base fold's arrays with per-layer integer deltas folded into
    the biases — the 'same fault, via the profile rider path' result."""
    hwp, _ = m.as_hw_params(hw)
    bias = {n: np.asarray(hwp.bias[n], np.int32)
            + np.asarray(deltas[n], np.int32)
            for n in CFG.imc_layer_names()}
    return cz.CustomizationResult(
        bias=bias, fc_w=np.asarray(hwp.fc_w), fc_b=np.asarray(hwp.fc_b),
        epochs=1, n_utterances=2, history=[], energy={})


# ---------------------------------------------------------------------------
# Fault model (repro.core.faults)
# ---------------------------------------------------------------------------


def test_fault_model_deterministic_and_resumable():
    """Same seed + same injection sequence => identical deltas at every
    step; a model restored from a mid-run snapshot continues identically
    (drift is keyed by absolute step, not by accumulated RNG state)."""
    def build():
        return flt.FaultModel.for_config(
            CFG, flt.FaultConfig(drift_std=0.3, seed=7))

    a, b = build(), build()
    for t in range(5):
        a.tick()
        b.tick()
    a.inject_bit_flips(n=3)
    b.inject_bit_flips(n=3)
    a.inject_stuck("conv2", [1, 4], value=-1)
    b.inject_stuck("conv2", [1, 4], value=-1)
    snap = a.snapshot()
    for t in range(5):
        a.tick()
        b.tick()
    da, db = a.deltas(), b.deltas()
    for name in da:
        assert np.array_equal(da[name], db[name]), name

    c = build()
    c.restore(snap)
    assert c.pop_dirty()
    for t in range(5):
        c.tick()
    dc = c.deltas()
    for name in da:
        assert np.array_equal(da[name], dc[name]), name


def test_fault_model_delta_composition():
    """Stuck rails pin at +/-stuck_magnitude; macro dropout is a stuck
    range; bit flips land on single (layer, channel) cells with
    power-of-two magnitudes; clear() returns to inactive."""
    f = flt.FaultModel.for_config(CFG, flt.FaultConfig(seed=1))
    assert not f.active
    f.inject_stuck("conv3", [2], value=1)
    f.inject_macro_dropout("conv1", start=8, width=4)
    d = f.deltas()
    assert d["conv3"][2] == f.fcfg.stuck_magnitude
    assert np.all(d["conv1"][8:12] == -f.fcfg.stuck_magnitude)
    assert np.all(d["conv1"][:8] == 0)
    mask = f.stuck_mask()
    assert mask["conv1"].sum() == 4 and mask["conv3"].sum() == 1
    f.inject_bit_flips(n=2, layer="conv4")
    d = f.deltas()
    nz = np.nonzero(d["conv4"])[0]
    assert 1 <= nz.size <= 2
    for c in nz:
        assert abs(d["conv4"][c]) in {
            f.fcfg.flip_magnitude * (1 << b)
            for b in range(f.fcfg.flip_bits)}
    f.clear()
    assert not f.active and f.pop_dirty()


@pytest.mark.streaming
def test_faulted_server_bitexact_vs_delta_riders(folded):
    """Faults ARE bias-delta riders on the existing operands: a server
    with the fault model active is bit-identical (events and state
    leaves) to a CLEAN server serving the same deltas through the
    per-stream customization rider path — and both differ from pristine."""
    hw = folded
    offs = _chip()
    rng = np.random.default_rng(2)
    wav = rng.uniform(-1, 1, L + 5 * HOP).astype(np.float32)

    srv = StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False,
                       chip_offsets=offs, sa_noise_std=1.5, seed=11)
    srv_f = StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False,
                         chip_offsets=offs, sa_noise_std=1.5, seed=11,
                         faults=flt.FaultConfig(seed=3))
    srv_f.faults.inject_stuck("conv2", [0, 5])
    srv_f.faults.inject_bit_flips(n=2)      # integer-valued deltas
    deltas = srv_f.faults.deltas()

    # clean server, same deltas folded into an installed profile
    srv.install_custom("a", _delta_result(hw, deltas))
    srv.submit("a", wav)
    srv_f.submit("a", wav)
    ev_rider, ev_fault = srv.drain(), srv_f.drain()
    assert len(ev_fault) == 6
    eq.assert_events_equal(ev_rider, ev_fault, "rider vs fault")
    eq.assert_leaves_equal(srv._state, srv_f._state, "rider vs fault")

    clean = StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False,
                         chip_offsets=offs, sa_noise_std=1.5, seed=11)
    clean.submit("a", wav)
    ev_clean = clean.drain()
    assert [e["score"] for e in ev_fault] != [e["score"] for e in ev_clean]


@pytest.mark.streaming
def test_one_launch_per_layer_under_fault_and_canary(folded, monkeypatch):
    """The tentpole invariant under fault: a tick whose batch carries live
    hops AND a canary hop, on a faulted chip, still traces exactly ONE
    pallas_call per IMC layer."""
    hw = folded
    srv = StreamServer(hw, CFG, hop=HOP, slots=4, use_kernel=True,
                       faults=flt.FaultConfig(drift_std=0.2, seed=3),
                       health=HealthConfig(interval=6))
    srv.faults.inject_bit_flips(n=2)
    rng = np.random.default_rng(0)
    for i in range(2):
        srv.submit(f"s{i}", rng.uniform(-1, 1, L + 16 * HOP)
                   .astype(np.float32))
    for _ in range(10):              # admission wave, then canary spawn
        srv.step()
        if any(rec.internal for rec in srv._streams.values()):
            break
    assert any(rec.internal for rec in srv._streams.values()), \
        "canary should have been submitted"
    srv.step()                       # canary init rides the admission wave
    # next tick: live hops + the canary's hop share ONE batched call
    jax.clear_caches()
    calls = []
    real = pl.pallas_call

    def counting(*args, **kwargs):
        calls.append(kwargs.get("grid"))
        return real(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", counting)
    srv.step()
    monkeypatch.setattr(pl, "pallas_call", real)
    assert len(calls) == CFG.num_conv_layers - 1, calls
    assert srv.health.canaries >= 1


# ---------------------------------------------------------------------------
# Canary detection, localization, healing
# ---------------------------------------------------------------------------


def _run(srv, rng, n, sid="a"):
    for _ in range(n):
        srv.submit(sid, rng.standard_normal(HOP).astype(np.float32))
        srv.step()


@pytest.mark.streaming
def test_canary_detects_localizes_and_masks_stuck(folded):
    """A stuck column fails canaries within ~2 intervals, is localized to
    the injected layer AND channels (bias-channel coordinates), cannot
    heal (the bias clip saturates), gets permanently masked, and the
    monitor returns to healthy with the write-off recorded."""
    hw = folded
    srv = StreamServer(hw, CFG, hop=HOP, slots=3, use_kernel=False,
                       faults=flt.FaultConfig(seed=3),
                       health=HealthConfig(interval=4, layers_per_tick=2))
    rng = np.random.default_rng(0)
    srv.submit("a", rng.standard_normal(L).astype(np.float32))
    _run(srv, rng, 12)
    assert srv.health.state == "healthy" and srv.health.canaries >= 1
    assert srv.health.failed_canaries == 0

    srv.faults.inject_stuck("conv3", [2, 7])
    injected_tick = srv._steps
    _run(srv, rng, 60)
    h = srv.health.stats()
    assert h["state"] == "healthy"
    assert h["masked_channels"] == {"conv3": [2, 7]}
    assert h["recoveries"] >= 1
    states = [e["state"] for e in h["history"]]
    assert states[:1] == ["healthy"]
    assert ["degraded", "quarantined", "recovering"] == [
        s for s in states if s != "healthy"][:3]
    # detection latency: within ~2 canary intervals of injection
    assert h["detected_tick"] - injected_tick <= 2 * 4 + 2


@pytest.mark.streaming
def test_drift_fault_heals_back_to_healthy(folded):
    """A large uniform offset drift is detected, recompensated through the
    chip-global rider, and the monitor returns to healthy with zero
    post-heal divergence — the self-healing loop closes."""
    hw = folded
    srv = StreamServer(hw, CFG, hop=HOP, slots=3, use_kernel=False,
                       chip_offsets=_chip(),
                       faults=flt.FaultConfig(seed=3),
                       health=HealthConfig(interval=4))
    rng = np.random.default_rng(0)
    srv.submit("a", rng.standard_normal(L).astype(np.float32))
    _run(srv, rng, 12)
    srv.faults._drift["conv2"][:] = 40.0
    srv.faults._dirty = True
    _run(srv, rng, 60)
    h = srv.health.stats()
    assert h["state"] == "healthy"
    assert h["recoveries"] == 1
    assert h["masked_channels"] == {}
    assert all(v == 0.0 for v in h["divergence"].values())
    assert h["recovery_energy_uj"] > 0
    # the heal rides the chip-global delta, not the per-slot rows
    assert srv._heal_delta is not None and "conv2" in srv._heal_delta
    # events emitted while degraded/quarantined carried the flag
    srv.submit("a", rng.standard_normal(HOP).astype(np.float32))
    ev = srv.step()
    assert all(e["degraded"] is False for e in ev)


@pytest.mark.streaming
def test_canaries_pause_without_live_traffic(folded):
    """No live stream -> no canary spawns (drain terminates); traffic
    resumes -> canaries resume."""
    hw = folded
    srv = StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False,
                       health=HealthConfig(interval=1))
    for _ in range(5):
        srv.step()
    assert srv.health.canaries == 0
    rng = np.random.default_rng(0)
    srv.submit("a", rng.standard_normal(L + 4 * HOP).astype(np.float32))
    _run(srv, rng, 6)
    assert srv.health.canaries >= 1
    srv.evict("a")
    srv.drain()                         # must terminate


# ---------------------------------------------------------------------------
# Crash safety: snapshot / restore
# ---------------------------------------------------------------------------


@pytest.mark.streaming
def test_snapshot_restore_bit_identical(folded, tmp_path):
    """Snapshot to disk mid-run (faults + health + VAD + SA noise + chip
    offsets active), restore into a freshly constructed server, and both
    servers' next 12 ticks produce identical events and identical state
    leaves — the restart is invisible."""
    hw = folded
    chip = _chip()

    def mk():
        return StreamServer(hw, CFG, hop=HOP, slots=3, use_kernel=False,
                            chip_offsets=chip, sa_noise_std=2.0,
                            vad=VADConfig(),
                            faults=flt.FaultConfig(drift_std=0.2, seed=3),
                            health=HealthConfig(interval=4), seed=7)

    rng = np.random.default_rng(0)
    srv = mk()
    srv.submit("a", rng.standard_normal(L + HOP).astype(np.float32))
    srv.submit("b", (0.001 * rng.standard_normal(L + HOP))
               .astype(np.float32))
    for _ in range(8):
        srv.submit("a", rng.standard_normal(HOP).astype(np.float32))
        srv.step()
    srv.faults.inject_bit_flips(n=2)

    path = os.fspath(tmp_path / "server.npz")
    assert srv.snapshot(path) == path
    future = [rng.standard_normal(HOP).astype(np.float32)
              for _ in range(12)]

    def play(s):
        evs = []
        for ch in future:
            s.submit("a", ch)
            s.submit("b", 0.001 * ch)
            evs.extend(s.step())
        return evs

    ev1 = play(srv)

    srv2 = mk()
    srv2.restore(path)
    ev2 = play(srv2)
    eq.assert_events_equal(ev1, ev2, "restored vs uninterrupted")
    eq.assert_leaves_equal(srv._state, srv2._state,
                           "restored vs uninterrupted")
    assert srv.health.stats() == srv2.health.stats()
    assert srv.faults.stats() == srv2.faults.stats()


@pytest.mark.streaming
def test_snapshot_restore_mid_customization_session(folded):
    """A snapshot taken while an enrollment session is mid-flight restores
    the session (captures, calibration progress, head state) and drives
    it to the SAME CustomizationResult, bit for bit."""
    hw = folded

    def mk():
        return StreamServer(hw, CFG, hop=HOP, slots=4, use_kernel=False,
                            sa_noise_std=1.0, seed=2)

    rng = np.random.default_rng(1)
    utts = [rng.standard_normal(L).astype(np.float32) for _ in range(4)]
    srv = mk()
    sess = srv.customize("enroll", cz.CustomizeConfig())
    for j, u in enumerate(utts):
        sess.enroll(j % CFG.num_classes, u)
    sess.finish_enrollment()
    for _ in range(6):
        srv.step()
    snap = srv.snapshot()            # in-memory snapshot, mid-session

    def finishing(s):
        se = s._cust.sessions[0]
        for _ in range(300):
            s.step()
            if se.phase in ("ready", "swapped"):
                return se
        raise AssertionError(f"session stuck in {se.phase}")

    s1 = finishing(srv)
    srv2 = mk()
    srv2.restore(snap)
    s2 = finishing(srv2)
    r1, r2 = s1.result, s2.result
    for name in r1.bias:
        assert np.array_equal(np.asarray(r1.bias[name]),
                              np.asarray(r2.bias[name])), name
    assert np.array_equal(np.asarray(r1.fc_w), np.asarray(r2.fc_w))
    assert np.array_equal(np.asarray(r1.fc_b), np.asarray(r2.fc_b))


def test_snapshot_restore_rejects_mismatched_config(folded):
    hw = folded
    srv = StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False)
    snap = srv.snapshot()
    other = StreamServer(hw, CFG, hop=2 * HOP, slots=2, use_kernel=False)
    with pytest.raises(ValueError, match="configuration mismatch"):
        other.restore(snap)
    with_faults = StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False,
                               faults=flt.FaultConfig(seed=0))
    with pytest.raises(ValueError, match="fault-model mismatch"):
        with_faults.restore(snap)


# ---------------------------------------------------------------------------
# Satellites: profiles at admission, duty-aware hop, retention fills
# ---------------------------------------------------------------------------


def test_profile_auto_install_and_stale_eviction(folded, tmp_path):
    """submit(user_id=...) installs the stored profile on admission; a
    re-saved profile hot-swaps on the next tick (mtime moved); a deleted
    profile resets the stream to the base model."""
    hw = folded
    store = ProfileStore(os.fspath(tmp_path / "profiles"))
    store.save("alice", _result(hw, "conv2", 1))
    srv = StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False,
                       profiles=store)
    rng = np.random.default_rng(0)
    srv.submit("mic0", rng.standard_normal(L).astype(np.float32),
               user_id="alice")
    rec = srv._streams["mic0"]
    assert rec.custom is not None and rec.profile_mtime is not None
    assert np.all(np.asarray(rec.custom["delta"]["conv2"]) == 1.0)
    srv.step()

    store.save("alice", _result(hw, "conv2", 2))      # fresh inode
    srv.submit("mic0", rng.standard_normal(HOP).astype(np.float32))
    srv.step()
    assert np.all(np.asarray(rec.custom["delta"]["conv2"]) == 2.0)
    assert srv.stats()["profile_swaps"] == 1

    store.delete("alice")
    srv.step()
    assert rec.custom is None and rec.profile_mtime is None
    assert srv.stats()["profile_swaps"] == 2

    # a user with no stored profile serves the base model but is tracked:
    # a later save is installed by the sweep
    srv.submit("mic1", rng.standard_normal(L).astype(np.float32),
               user_id="bob")
    rec1 = srv._streams["mic1"]
    assert rec1.custom is None
    store.save("bob", _result(hw, "conv3", 1))
    srv.step()
    assert rec1.custom is not None

    # user_id without a store is a usage error
    bare = StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False)
    with pytest.raises(ValueError, match="profile store"):
        bare.submit("x", np.zeros((HOP,), np.float32), user_id="alice")


def test_duty_aware_hop_widen_faster_when_silent(folded):
    """With calm_silence set, an all-silent stream earns the wider hop in
    calm_silence ticks instead of widen_after; an all-speech stream is
    bit-identical to the same server without the knob (forced-speech
    contract)."""
    hw = folded
    rng = np.random.default_rng(4)
    quiet = (1e-4 * rng.standard_normal(L + 20 * HOP)).astype(np.float32)

    def run(calm_silence, wav, force=None):
        srv = StreamServer(
            hw, CFG, hop=HOP, slots=2, use_kernel=False,
            vad=VADConfig() if force is None else VADConfig(force=force),
            dynamic_hop=DynamicHopConfig(widen_after=50,
                                         calm_silence=calm_silence))
        srv.submit("s", wav)
        mults, events = [], []
        for _ in range(16):
            events.extend(srv.step())
            mults.append(srv.hop_multiplier)
        return mults, events

    mults_fast, _ = run(3, quiet)
    mults_slow, _ = run(None, quiet)
    assert max(mults_fast) > 1          # widened within 16 ticks
    assert max(mults_slow) == 1         # widen_after=50 never reached

    loud = rng.uniform(-1, 1, L + 20 * HOP).astype(np.float32)
    _, ev_knob = run(3, loud, force="speech")
    _, ev_base = run(None, loud, force="speech")
    eq.assert_events_equal(ev_knob, ev_base,   # forced speech: the knob
                           "calm_silence knob")  # is invisible


def test_retention_fill_modes(folded):
    """retention_fills at zero read noise IS silence_fills (the pinned
    default); with noise it differs but stays shape/dtype-compatible; the
    scheduler validates the mode string."""
    hw = folded
    base = sv.silence_fills(CFG, m.silence_columns(hw, CFG))
    ret0 = sv.retention_fills(hw, CFG, key=jax.random.PRNGKey(0),
                              sa_noise_std=0.0)
    for a, b in zip(base, ret0):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    retn = sv.retention_fills(hw, CFG, key=jax.random.PRNGKey(0),
                              sa_noise_std=2.0, chip_offsets=_chip())
    assert any(not np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(base, retn))
    for a, b in zip(base, retn):
        assert np.asarray(a).shape == np.asarray(b).shape
    with pytest.raises(ValueError, match="silence_fill"):
        StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False,
                     silence_fill="nope")
    srv = StreamServer(hw, CFG, hop=HOP, slots=2, use_kernel=False,
                       vad=VADConfig(), sa_noise_std=2.0,
                       silence_fill="retention")
    rng = np.random.default_rng(0)
    srv.submit("a", rng.standard_normal(L + 4 * HOP).astype(np.float32))
    for _ in range(6):
        srv.step()
    assert srv.stats()["silence_fill"] == "retention"


# ---------------------------------------------------------------------------
# Soak: everything at once, randomized
# ---------------------------------------------------------------------------


def _soak(folded, seed, ticks, snapshot_every):
    """Randomized interleaving of admissions, evictions, VAD-gated audio,
    fault injections, and periodic snapshot+restore-into-fresh-server
    swaps.  Invariants checked every tick; returns final stats."""
    hw = folded
    chip = _chip()

    def mk():
        return StreamServer(hw, CFG, hop=HOP, slots=3, use_kernel=False,
                            chip_offsets=chip, sa_noise_std=1.0,
                            vad=VADConfig(),
                            faults=flt.FaultConfig(drift_std=0.1,
                                                   seed=seed),
                            health=HealthConfig(interval=5), seed=seed)

    rng = np.random.default_rng(seed)
    srv = mk()
    alive = {}
    for t in range(ticks):
        r = rng.random()
        if r < 0.25 and len(alive) < 5:
            sid = f"s{t}"
            alive[sid] = True
            srv.submit(sid, rng.uniform(-1, 1, L).astype(np.float32))
        elif r < 0.35 and alive:
            sid = rng.choice(sorted(alive))
            del alive[sid]
            srv.evict(sid)
        elif r < 0.45 and srv.faults is not None:
            kind = rng.integers(3)
            if kind == 0:
                srv.faults.inject_bit_flips(n=1)
            elif kind == 1:
                name = f"conv{1 + int(rng.integers(CFG.num_conv_layers - 1))}"
                srv.faults.inject_stuck(
                    name, [int(rng.integers(CFG.channels[int(name[4:])]))])
            else:
                srv.faults.clear()
        for sid in list(alive):
            amp = 1.0 if rng.random() < 0.5 else 1e-4
            srv.submit(sid, (amp * rng.standard_normal(HOP))
                       .astype(np.float32))
        srv.step()
        if (t + 1) % snapshot_every == 0:
            snap = srv.snapshot()
            srv2 = mk()
            srv2.restore(snap)
            assert srv2.health.stats() == srv.health.stats()
            assert srv2.faults.stats() == srv.faults.stats()
            srv = srv2               # continue on the restored server
        assert srv.health.state in srv.health.STATES
        live_slots = [rec.stream_id for rec in srv._slots
                      if rec is not None and not rec.internal]
        assert len(live_slots) == len(set(live_slots))
    st = srv.stats()
    assert st["steps"] == ticks
    return st


@pytest.mark.streaming
def test_soak_quick(folded):
    st = _soak(folded, seed=13, ticks=24, snapshot_every=8)
    assert st["health"]["canaries"] >= 1


@pytest.mark.slow
@pytest.mark.streaming
@pytest.mark.parametrize("seed", [101, 202])
def test_soak_long(folded, seed):
    st = _soak(folded, seed=seed, ticks=120, snapshot_every=25)
    assert st["health"]["canaries"] >= 3


# ---------------------------------------------------------------------------
# Sharded soak: the same chaos across a 2-device fleet
# ---------------------------------------------------------------------------


def _sharded_soak(folded, seed, ticks, snapshot_every):
    """The PR-6 soak across a 2-device fleet: random admissions (beyond
    per-pool capacity, so placement + queueing engage), evictions that
    free capacity for later streams to land on either device
    (cross-device re-routing), fleet-wide fault campaigns with per-pool
    canary heals, and periodic sharded snapshot -> restore-into-fresh-
    fleet swaps.  Invariants checked every tick; returns fleet stats."""
    hw = folded
    chip = _chip()
    from repro.serving import ShardedStreamServer

    def mk():
        return ShardedStreamServer(
            hw, CFG, devices=eq.pool_devices(2), slots=2, hop=HOP,
            use_kernel=False,
            chip_offsets=chip, sa_noise_std=1.0, vad=VADConfig(),
            faults=flt.FaultConfig(drift_std=0.1, seed=seed),
            health=HealthConfig(interval=5), seed=seed)

    rng = np.random.default_rng(seed)
    sh = mk()
    alive = {}
    placed_on = set()
    for t in range(ticks):
        r = rng.random()
        if r < 0.3 and len(alive) < 6:
            sid = f"s{t}"
            alive[sid] = True
            sh.submit(sid, rng.uniform(-1, 1, L).astype(np.float32))
            placed_on.add(sh.where(sid))
        elif r < 0.4 and alive:
            sid = rng.choice(sorted(alive))
            del alive[sid]
            sh.evict(sid)
        elif r < 0.5:
            kind = rng.integers(3)
            for fm in sh.fault_models:      # fleet-wide campaign: every
                if kind == 0:               # pool mutates identically
                    fm.inject_bit_flips(n=1)
                elif kind == 1:
                    fm.inject_stuck("conv2", [3])
                else:
                    fm.clear()
        for sid in list(alive):
            amp = 1.0 if rng.random() < 0.5 else 1e-4
            sh.submit(sid, (amp * rng.standard_normal(HOP))
                      .astype(np.float32))
        events = sh.step()
        for ev in events:                   # device tags track placement
            assert ev["device"] == sh.where(ev["stream"])
        if (t + 1) % snapshot_every == 0:
            snap = sh.snapshot()
            sh2 = mk()
            sh2.restore(snap)
            for a, b in zip(sh2.pools, sh.pools):
                assert a.health.stats() == b.health.stats()
                assert a.faults.stats() == b.faults.stats()
            assert sh2._where == sh._where
            sh = sh2                        # continue on the restored fleet
        for srv in sh.pools:
            assert srv.health.state in srv.health.STATES
        live = [rec.stream_id for srv in sh.pools for rec in srv._slots
                if rec is not None and not rec.internal]
        assert len(live) == len(set(live))  # no stream on two devices
        for sid in live:
            assert sh.where(sid) is not None
    assert placed_on == {0, 1}              # both devices took streams
    st = sh.stats()
    assert st["steps"] == ticks
    return st


@pytest.mark.streaming
def test_sharded_soak_quick(folded):
    st = _sharded_soak(folded, seed=17, ticks=24, snapshot_every=8)
    assert sum(d["health"]["canaries"]
               for d in st["per_device"]) >= 2    # >=1 canary per pool


@pytest.mark.slow
@pytest.mark.streaming
@pytest.mark.parametrize("seed", [303, 404])
def test_sharded_soak_long(folded, seed):
    st = _sharded_soak(folded, seed=seed, ticks=120, snapshot_every=25)
    assert sum(d["health"]["canaries"]
               for d in st["per_device"]) >= 6
