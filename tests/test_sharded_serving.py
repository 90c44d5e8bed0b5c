"""The sharded-serving contract (repro.serving.shard + repro.sharding):

* a ``ShardedStreamServer`` — N per-device slot pools behind the
  deterministic placement router — is bit-identical PER STREAM to one
  single-device ``StreamServer`` fed the same streams: SA-noise fields
  (global uid parity), chip offsets, ``FaultConfig`` deltas (bit flips,
  stuck columns, tick-lockstep drift) and VAD gating included;
* a property soak drives random interleavings of submit / speech /
  silence / evict / finish / fault-inject / snapshot-restore ops through
  both servers and compares every stream's full decision sequence;
* the sharded snapshot bundle (per-pool v2 snapshots + router state in
  one atomic npz) restores bit-identically into a fresh fleet, and
  refuses a mismatched device count;
* the placement policy is deterministic: least-loaded spreads streams
  across pools, exact ties rotate round-robin, and the router never
  consumes a global uid for a rejected stream;
* every event carries its ``device`` tag and the fleet ``stats()``
  rollup (the tier's only cross-device gather) sums the per-device pool
  counters with zero launch-audit violations.

Pools get the explicit list ``_equiv.pool_devices(N)``, which reuses
devices in turn, so this file runs N logical pools on one physical
device; the CI sharding gate re-runs it
under ``XLA_FLAGS=--xla_force_host_platform_device_count=2`` for real
per-device placement.
"""

import numpy as np
import jax
import pytest
from _hypothesis_shim import given, settings, st

import _equiv as eq
from repro.core import faults as flt
from repro.core import imc
from repro.models import kws as m
from repro.serving import (HealthConfig, ObsConfig, ShardedStreamServer,
                           StreamServer, VADConfig)
from repro.sharding import PlacementConfig, PlacementPolicy, PoolLoad

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


def _wav(key, n):
    return np.asarray(jax.random.uniform(jax.random.PRNGKey(key), (n,),
                                         minval=-1, maxval=1), np.float32)


def _assert_equiv(ev_oracle, ev_sharded):
    # per-stream, device tags stripped — the shared harness's by_stream
    # mode (tests/_equiv.py): the sharded server must match the oracle
    # on everything else, field for field
    return eq.assert_events_equal(ev_oracle, ev_sharded,
                                  "sharded vs oracle", by_stream=True)


# ---------------------------------------------------------------------------
# Placement policy (repro.sharding.placement)
# ---------------------------------------------------------------------------


def test_placement_least_loaded_then_queue_then_rr():
    p = PlacementPolicy(3)
    # most free slots wins
    assert p.place([PoolLoad(1, 0), PoolLoad(3, 0), PoolLoad(2, 0)]) == 1
    # equal slots: shortest queue wins
    assert p.place([PoolLoad(2, 4), PoolLoad(2, 1), PoolLoad(2, 2)]) == 1
    # exact ties rotate via the cursor (last pick was 1 -> cursor at 2)
    assert p.place([PoolLoad(2, 0), PoolLoad(2, 0), PoolLoad(2, 0)]) == 2
    assert p.place([PoolLoad(2, 0), PoolLoad(2, 0), PoolLoad(2, 0)]) == 0
    # duty-aware tie-break: quietest pool absorbs the new talker
    pd = PlacementPolicy(2, PlacementConfig(duty_aware=True))
    assert pd.place([PoolLoad(2, 0, duty=0.9),
                     PoolLoad(2, 0, duty=0.1)]) == 1


def test_placement_round_robin_and_snapshot():
    p = PlacementPolicy(2, PlacementConfig(strategy="round_robin"))
    loads = [PoolLoad(0, 9), PoolLoad(4, 0)]
    assert [p.place(loads) for _ in range(4)] == [0, 1, 0, 1]
    snap = p.snapshot()
    q = PlacementPolicy(2, PlacementConfig(strategy="round_robin"))
    q.restore(snap)
    assert q.place(loads) == p.place(loads)
    with pytest.raises(ValueError):
        PlacementPolicy(2).restore(snap)          # strategy mismatch
    with pytest.raises(ValueError):
        PlacementConfig(strategy="hash")
    with pytest.raises(ValueError):
        p.place([PoolLoad(1, 0)])                 # wrong arity


# ---------------------------------------------------------------------------
# Directed bit-identity: sharded == single-device per stream
# ---------------------------------------------------------------------------


@pytest.mark.streaming
def test_sharded_bitident_noise_and_chip_offsets(folded):
    """2 pools x 2 slots vs one 4-slot oracle on the full noisy path —
    fused kernels, SA-noise fields keyed by the GLOBAL uid, chip
    offsets.  The crux: stream s3 lands on device 1 slot 1, but its
    noise field must equal the one the oracle drew for its slot."""
    kw = dict(hop=HOP, sa_noise_std=0.3, chip_offsets=_chip(), seed=0)
    oracle = StreamServer(folded, CFG, slots=4, **kw)
    sh = ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2),
                             slots=2, **kw)
    wavs = {f"s{i}": _wav(100 + i, L + 6 * HOP) for i in range(4)}
    for sid, w in wavs.items():
        oracle.submit(sid, w)
        oracle.finish(sid)
        sh.submit(sid, w)
        sh.finish(sid)
    po = _assert_equiv(oracle.drain(), sh.drain())
    assert all(len(v) == 7 for v in po.values())   # init + 6 hops each
    # balanced placement: two streams per pool
    assert sorted(sh.where(s) for s in wavs) == [0, 0, 1, 1]


@pytest.mark.streaming
def test_sharded_bitident_vad_gating(folded):
    """Per-stream VAD gating (silent fills + wake replay) shards
    transparently: gating state is per slot, so a mid-stream quiet
    stretch gates on whichever device the stream lives on exactly as it
    would on the oracle."""
    vad = VADConfig(threshold_on_db=-40.0, threshold_off_db=-50.0,
                    wake_margin=1, hang=0)
    kw = dict(hop=HOP, sa_noise_std=0.2, vad=vad, seed=0)
    oracle = StreamServer(folded, CFG, slots=4, **kw)
    sh = ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2),
                             slots=2, **kw)
    rng = np.random.default_rng(11)
    for i in range(4):
        w = rng.uniform(-1, 1, L + 12 * HOP).astype(np.float32)
        w[L + 4 * HOP:L + 9 * HOP] *= 1e-4        # silent stretch
        oracle.submit(f"s{i}", w)
        oracle.finish(f"s{i}")
        sh.submit(f"s{i}", w)
        sh.finish(f"s{i}")
    _assert_equiv(oracle.drain(), sh.drain())
    st = sh.stats()
    assert st["fleet"]["gated_hops"] > 0          # the gate actually ran
    assert (st["fleet"]["gated_hops"]
            == oracle.stats()["gated_hops"])


@pytest.mark.streaming
def test_sharded_bitident_faults_and_drift(folded):
    """One FaultConfig, one seeded FaultModel PER POOL: every pool ticks
    its model once per router tick, so tick-keyed drift stays in
    lockstep with the oracle, and a fleet-wide bit-flip campaign
    (same draws on every model) perturbs each stream identically."""
    fcfg = flt.FaultConfig(drift_std=0.2, seed=3)
    kw = dict(hop=HOP, sa_noise_std=0.2, seed=0)
    oracle = StreamServer(folded, CFG, slots=4, faults=fcfg, **kw)
    sh = ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2), slots=2,
                             faults=fcfg, **kw)
    assert len(sh.fault_models) == 2
    for i in range(4):
        w = _wav(300 + i, L + 8 * HOP)
        oracle.submit(f"s{i}", w)
        oracle.finish(f"s{i}")
        sh.submit(f"s{i}", w)
        sh.finish(f"s{i}")
    ev_o, ev_s = [], []
    for t in range(4):
        ev_o += oracle.step()
        ev_s += sh.step()
    oracle.faults.inject_bit_flips(n=4)
    oracle.faults.inject_stuck("conv2", [1, 5], value=-1)
    for fm in sh.fault_models:
        fm.inject_bit_flips(n=4)
        fm.inject_stuck("conv2", [1, 5], value=-1)
    ev_o += oracle.drain()
    ev_s += sh.drain()
    _assert_equiv(ev_o, ev_s)
    # a shared FaultModel instance would double-tick across pools
    with pytest.raises(ValueError):
        ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2), slots=2,
                            faults=oracle.faults, **kw)


@pytest.mark.streaming
def test_sharded_snapshot_restore_bit_identical(folded, tmp_path):
    """Mid-run sharded bundle -> fresh identically-configured fleet ->
    the remaining decisions match an uninterrupted oracle exactly.
    The bundle carries per-pool v2 snapshots plus router state (stream
    placements, global uid counter, policy cursor)."""
    fcfg = flt.FaultConfig(seed=5)
    kw = dict(hop=HOP, sa_noise_std=0.25, chip_offsets=_chip(),
              faults=fcfg, seed=0)
    oracle = StreamServer(folded, CFG, slots=4, **kw)

    def mk():
        return ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2),
                                   slots=2, **kw)

    sh = mk()
    for i in range(4):
        w = _wav(400 + i, L + 8 * HOP)
        oracle.submit(f"s{i}", w)
        oracle.finish(f"s{i}")
        sh.submit(f"s{i}", w)
        sh.finish(f"s{i}")
    ev_o, ev_s = [], []
    for _ in range(3):
        ev_o += oracle.step()
        ev_s += sh.step()
    path = str(tmp_path / "fleet.npz")
    assert sh.snapshot(path) == path
    sh2 = mk()
    sh2.restore(path)
    assert sh2.where("s0") == sh.where("s0")
    assert sh2._next_uid == sh._next_uid
    ev_o += oracle.drain()
    ev_s += sh2.drain()
    po = _assert_equiv(ev_o, ev_s)
    assert sum(len(v) for v in po.values()) > 0
    # a fleet of the wrong width must refuse the bundle
    with pytest.raises(ValueError):
        ShardedStreamServer(folded, CFG, devices=eq.pool_devices(3), slots=2,
                            **kw).restore(path)


def test_device_count_beyond_available_raises(folded):
    """``devices=N`` never reuses a device: asking for more pools than
    ``jax.devices()`` holds is an error, not a silent wrap onto device 0
    (an explicit list is how N pools share devices on purpose)."""
    n = len(jax.devices())
    with pytest.raises(ValueError, match="explicit device list"):
        ShardedStreamServer(folded, CFG, hop=HOP, devices=n + 1, slots=1)
    sh = ShardedStreamServer(folded, CFG, hop=HOP,
                             devices=eq.pool_devices(n + 1), slots=1)
    assert sh.n_devices == n + 1


@pytest.mark.streaming
def test_router_rejection_consumes_no_uid(folded):
    """A stream rejected by its pool's admission queue leaves the router
    untouched — no placement, no global uid — so the noise-field
    identities of later streams still match the single-device oracle
    (whose rejected submits don't advance its uid either)."""
    from repro.serving import AdmissionConfig
    kw = dict(hop=HOP, seed=0,
              admission=AdmissionConfig(max_queue=0))
    sh = ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2),
                             slots=1, **kw)
    for i in range(2):                      # fill both pools' only slots
        assert sh.submit(f"s{i}", _wav(i, L)) == "slot"
    uid_before = sh._next_uid
    assert sh.submit("overflow", _wav(9, L)) == "rejected"
    assert sh.where("overflow") is None
    assert sh._next_uid == uid_before
    st = sh.stats()
    assert st["fleet"]["rejected_streams"] == 1


@pytest.mark.streaming
def test_events_device_tags_and_fleet_rollup(folded):
    """Every decision event names the device that produced it (matching
    the router's placement), and the fleet stats rollup equals the sum
    of the per-device pools with zero audit violations."""
    obs = ObsConfig(recorder=32, audit="raise")
    sh = ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2),
                             slots=2, hop=HOP,
                             seed=0, obs=obs)
    for i in range(4):
        sh.submit(f"s{i}", _wav(500 + i, L + 4 * HOP))
        sh.finish(f"s{i}")
    events = sh.drain()
    assert events
    for ev in events:
        assert ev["device"] == sh.where(ev["stream"])
    st = sh.stats()
    assert st["devices"] == 2 and len(st["per_device"]) == 2
    assert st["fleet"]["decisions"] == sum(
        d["decisions"] for d in st["per_device"])
    assert st["fleet"]["decisions"] == len(events)
    assert st["audit"]["violations"] == 0
    assert [a["device"] for a in st["audit"]["per_device"]] == [0, 1]


# ---------------------------------------------------------------------------
# Property soak: random op interleavings, sharded == oracle throughout
# ---------------------------------------------------------------------------


def _dual_soak(folded, seed, ticks=12):
    """Drive one random interleaving of submit/speech/silence/evict/
    finish/fault/snapshot ops through a 2x2 sharded fleet AND a 4-slot
    single-device oracle, then compare every stream's full decision
    sequence.  Live streams are capped at the slot capacity (4) so
    admission is immediate on both sides — the timing alignment that
    makes tick-keyed fault drift comparable."""
    hw = folded
    fcfg = flt.FaultConfig(drift_std=0.1, seed=seed)
    vad = VADConfig(threshold_on_db=-40.0, threshold_off_db=-50.0,
                    wake_margin=1, hang=0)
    kw = dict(hop=HOP, use_kernel=False, sa_noise_std=0.5, vad=vad,
              faults=fcfg, seed=seed)
    oracle = StreamServer(hw, CFG, slots=4, **kw)

    def mk():
        return ShardedStreamServer(hw, CFG, devices=eq.pool_devices(2),
                                   slots=2, **kw)

    sh = mk()
    rng = np.random.default_rng(seed)
    alive = {}
    ev_o, ev_s = [], []
    for t in range(ticks):
        r = rng.random()
        if r < 0.35 and len(alive) < 4:
            sid = f"s{t}"
            alive[sid] = True
            w = rng.uniform(-1, 1, L).astype(np.float32)
            oracle.submit(sid, w)
            sh.submit(sid, w)
        elif r < 0.45 and alive:
            sid = rng.choice(sorted(alive))
            del alive[sid]
            oracle.evict(sid)
            sh.evict(sid)
        elif r < 0.55 and alive:
            sid = rng.choice(sorted(alive))
            del alive[sid]
            oracle.finish(sid)
            sh.finish(sid)
        elif r < 0.65:
            oracle.faults.inject_bit_flips(n=1)
            for fm in sh.fault_models:
                fm.inject_bit_flips(n=1)
        for sid in list(alive):
            amp = 1.0 if rng.random() < 0.6 else 1e-4   # speech/silence
            w = (amp * rng.standard_normal(HOP)).astype(np.float32)
            oracle.submit(sid, w)
            sh.submit(sid, w)
        ev_o += oracle.step()
        ev_s += sh.step()
        if t == ticks // 2:                   # mid-soak fleet swap
            sh2 = mk()
            sh2.restore(sh.snapshot())
            sh = sh2
    for sid in alive:
        oracle.finish(sid)
        sh.finish(sid)
    ev_o += oracle.drain()
    ev_s += sh.drain()
    return _assert_equiv(ev_o, ev_s)


_HW_CACHE = []


def _hw():
    # the property wrapper exposes a zero-arg signature (hypothesis and
    # the shim alike), so the module fixture can't be injected — fold
    # once and cache instead
    if not _HW_CACHE:
        params = m.init_params(jax.random.PRNGKey(5), CFG)
        state = m.init_state(CFG)
        _HW_CACHE.append(m.fold_params(params, state, CFG, pack=True))
    return _HW_CACHE[0]


@pytest.mark.streaming
@settings(max_examples=4, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_sharded_soak_property(seed):
    """Any op interleaving keeps the sharded fleet bit-identical to the
    oracle — noise, gating, drift + flip faults and a mid-soak sharded
    snapshot swap included."""
    _dual_soak(_hw(), seed)
