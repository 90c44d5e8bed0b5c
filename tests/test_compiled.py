"""Compiled whole-tick fast path (repro.serving.compiled).

The differential proof for the tentpole claim: K steady-state scheduler
ticks fused into ONE jitted ``lax.scan`` dispatch are bit-identical to K
interpreted Python ticks — decision events, stream/decision/VAD carry
state and every metrics-registry cell (``tests/_equiv.py`` defines the
shared notion of equal, excluding only wall time and the
``serving.compiled`` dispatch counters).  Coverage spans the configs the
invariants live in: SA-noise fields, chip offsets, fault riders (drift
and injected flips), VAD gating with wake-margin replay, dynamic hop,
slot autoscaling + SLO shedding, admissions/evictions mid-run, snapshot/
restore across tick modes, sharded pools, and the launch auditor's
``compiled``-cause rules in raise mode.

Golden decision-trace regression: ``tests/golden/decision_trace.json``
pins the stream, hop, keyword and trigger of every event of a fixed
compiled run, byte for byte; the same run on the interpreted tick must
emit identical events, scores included.
Regenerate (after an INTENTIONAL decision-path change) with::

    REPRO_REGEN_GOLDEN=1 PYTHONPATH=src python -m pytest \
        tests/test_compiled.py -k golden -q
"""

import json
import os
import pathlib

import numpy as np
import jax
import pytest
from _hypothesis_shim import given, settings, st

import _equiv as eq
from repro.core import faults as flt
from repro.core import imc
from repro.models import kws as m
from repro.obs import LaunchAuditError, LaunchAuditor, ObsConfig
from repro.serving import (AdmissionConfig, CompiledTickConfig,
                           DynamicHopConfig, ShardedStreamServer,
                           StreamServer, VADConfig)

L, HOP = 640, 64
CFG = m.KWSConfig(sample_len=L)
GOLDEN = pathlib.Path(__file__).parent / "golden" / "decision_trace.json"

pytestmark = [pytest.mark.streaming, pytest.mark.compiled]


@pytest.fixture(scope="module")
def folded():
    params = m.init_params(jax.random.PRNGKey(5), CFG)
    state = m.init_state(CFG)
    return m.fold_params(params, state, CFG, pack=True)


def _chip(std=4.0):
    chans = {f"conv{i}": CFG.channels[i]
             for i in range(1, CFG.num_conv_layers)}
    return imc.sample_chip_offsets(jax.random.PRNGKey(9), chans,
                                   imc.IMCNoiseParams(mav_offset_std=std))


def _duty(n, seed, duty=0.45, period=3 * HOP):
    """Speech/silence duty-cycled audio: uniform noise with seeded runs
    of near-silence, so VAD gating, wake replay and calm ticks all
    actually exercise."""
    r = np.random.default_rng(seed)
    x = r.uniform(-1.0, 1.0, n).astype(np.float32)
    t = 0
    while t < n:
        if r.random() > duty:
            x[t:t + period] *= 1e-4
        t += period
    return x


def _run_pair(hw, kw, ticks=30, n_streams=3, block=8, slots=3,
              inject=None, audio_len=None):
    """Drive a Python-tick reference and a compiled-block candidate over
    identical traffic to the same absolute tick, then assert the full
    equivalence contract.  Returns ``(ref, cand, events)``."""
    ref = StreamServer(hw, CFG, hop=HOP, slots=slots, **kw)
    cand = StreamServer(hw, CFG, hop=HOP, slots=slots,
                        compiled=CompiledTickConfig(block=block), **kw)
    if inject is not None:
        inject(ref)
        inject(cand)
    n = audio_len if audio_len is not None else L + 22 * HOP
    auds = [_duty(n, 100 + i) for i in range(n_streams)]
    for srv in (ref, cand):
        for i, x in enumerate(auds):
            srv.submit(f"s{i}", x)
    ev_ref = eq.advance_to(ref, ticks)
    ev_cand = eq.advance_to(cand, ticks)
    assert cand._steps == ref._steps == ticks
    eq.assert_events_equal(ev_ref, ev_cand, "compiled vs python")
    eq.assert_server_equal(ref, cand, "compiled vs python")
    return ref, cand, ev_ref


# ---------------------------------------------------------------------------
# bit-identity across the invariant-bearing configs
# ---------------------------------------------------------------------------


CASES = {
    "gated_clean": lambda: dict(vad=VADConfig()),
    "ungated": lambda: dict(),
    "noise_and_chip": lambda: dict(vad=VADConfig(), sa_noise_std=0.15,
                                   chip_offsets=_chip()),
    "wake_margin2": lambda: dict(
        vad=VADConfig(threshold_on_db=-40.0, threshold_off_db=-50.0,
                      wake_margin=2, hang=0), sa_noise_std=0.2),
    "fault_drift": lambda: dict(vad=VADConfig(),
                                faults=flt.FaultConfig(drift_std=0.5)),
    "dynamic_hop": lambda: dict(
        vad=VADConfig(),
        dynamic_hop=DynamicHopConfig(widen_after=4, max_multiplier=2)),
    "dynhop_duty_aware": lambda: dict(
        vad=VADConfig(),
        dynamic_hop=DynamicHopConfig(widen_after=5, max_multiplier=2,
                                     calm_silence=2)),
    "autoscale": lambda: dict(
        vad=VADConfig(),
        admission=AdmissionConfig(min_slots=1, max_slots=3,
                                  scale_up_after=2, scale_down_after=3)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiled_block_bitident(folded, case):
    """One fused dispatch for a block of steady-state ticks equals the
    interpreted ticks bit for bit — events, carries, counters."""
    _, cand, events = _run_pair(folded, CASES[case](), ticks=30)
    assert events                           # the case actually decided
    assert cand._compiled_ticks > 0, "fast path never engaged"
    assert cand._compiled_blocks <= cand._compiled_ticks


def test_compiled_injected_faults_bitident(folded):
    """Stuck-column + bit-flip deltas (integer-valued riders) flow into
    the compiled block through the same staged operands as drift."""
    def inject(srv):
        srv.faults.inject_stuck("conv2", [0, 5])
        srv.faults.inject_bit_flips(n=2)
    _, cand, _ = _run_pair(
        folded, dict(vad=VADConfig(), sa_noise_std=0.3,
                     chip_offsets=_chip(),
                     faults=flt.FaultConfig(seed=3)),
        inject=inject)
    assert cand._compiled_ticks > 0


def test_compiled_slo_shed_falls_back(folded):
    """A backlog over the latency SLO is a structural event: the horizon
    refuses to fuse and the Python tick sheds — equivalence holds with
    the fast path engaging only on the SLO-clean stretches."""
    kw = dict(vad=VADConfig(),
              admission=AdmissionConfig(max_lag_s=(L + 6 * HOP) / 16000))
    _run_pair(folded, kw, ticks=30, audio_len=L + 40 * HOP)


# ---------------------------------------------------------------------------
# tick-mode plumbing: step() routing, block sizes, drain
# ---------------------------------------------------------------------------


def test_step_routes_single_tick_blocks(folded):
    """``step()`` on a compiled server serves eligible ticks as K=1
    blocks — same events as the Python tick, one dispatch per tick."""
    ref = StreamServer(folded, CFG, hop=HOP, slots=2, vad=VADConfig())
    cand = StreamServer(folded, CFG, hop=HOP, slots=2, vad=VADConfig(),
                        compiled=CompiledTickConfig(block=8))
    for srv in (ref, cand):
        for i in range(2):
            srv.submit(f"s{i}", _duty(L + 10 * HOP, 40 + i))
    ev_ref, ev_cand = [], []
    for _ in range(14):
        ev_ref.extend(ref.step())
        ev_cand.extend(cand.step())         # NOT step_block
    eq.assert_events_equal(ev_ref, ev_cand, "step() routing")
    eq.assert_server_equal(ref, cand, "step() routing")
    assert cand._compiled_ticks > 0
    assert cand._compiled_blocks == cand._compiled_ticks   # K=1 blocks


def test_block_sizes_all_equal(folded):
    """Every block size serves the same decisions; bigger blocks just
    use fewer dispatches."""
    kw = dict(vad=VADConfig(), sa_noise_std=0.2)
    runs = {}
    for block in (1, 2, 3, 8, 32):
        srv = StreamServer(folded, CFG, hop=HOP, slots=2,
                           compiled=CompiledTickConfig(block=block), **kw)
        for i in range(2):
            srv.submit(f"s{i}", _duty(L + 16 * HOP, 70 + i))
        ev = eq.advance_to(srv, 20)
        runs[block] = (srv, ev)
    ref_srv, ref_ev = runs[1]
    for block, (srv, ev) in runs.items():
        eq.assert_events_equal(ref_ev, ev, f"block={block}")
        eq.assert_server_equal(ref_srv, srv, f"block={block}")
    assert runs[32][0]._compiled_blocks < runs[1][0]._compiled_blocks


def test_compiled_drain_matches(folded):
    """``drain()`` on a compiled server (which drains via step_block)
    retires everything the interpreted drain does, in as many ticks."""
    ref = StreamServer(folded, CFG, hop=HOP, slots=2, vad=VADConfig())
    cand = StreamServer(folded, CFG, hop=HOP, slots=2, vad=VADConfig(),
                        compiled=CompiledTickConfig(block=8))
    for srv in (ref, cand):
        for i in range(2):
            srv.submit(f"s{i}", _duty(L + 12 * HOP, 55 + i))
            srv.finish(f"s{i}")
    ev_ref, ev_cand = ref.drain(), cand.drain()
    eq.assert_events_equal(ev_ref, ev_cand, "drain")
    assert ref._steps == cand._steps
    eq.assert_server_equal(ref, cand, "drain")
    assert cand._compiled_ticks > 0


def test_compiled_admission_eviction_mid_run(folded):
    """Admissions and evictions are block boundaries, not failures: a
    stream submitted or evicted mid-run breaks the block, the Python
    tick handles the structural work, and fusing resumes after."""
    kw = dict(vad=VADConfig(), sa_noise_std=0.2)
    ref = StreamServer(folded, CFG, hop=HOP, slots=3, **kw)
    cand = StreamServer(folded, CFG, hop=HOP, slots=3,
                        compiled=CompiledTickConfig(block=4), **kw)
    for srv in (ref, cand):
        srv.submit("a", _duty(L + 20 * HOP, 1))
        srv.submit("b", _duty(L + 20 * HOP, 2))
    ev_ref = eq.advance_to(ref, 6)
    ev_cand = eq.advance_to(cand, 6)
    for srv in (ref, cand):
        srv.submit("c", _duty(L + 12 * HOP, 3))    # mid-run admission
        srv.evict("a")                             # and an eviction
    ev_ref += eq.advance_to(ref, 18)
    ev_cand += eq.advance_to(cand, 18)
    eq.assert_events_equal(ev_ref, ev_cand, "admit/evict mid-run")
    eq.assert_server_equal(ref, cand, "admit/evict mid-run")
    assert cand._compiled_ticks > 0


def test_snapshot_restore_across_tick_modes(folded):
    """v2 snapshots are tick-mode agnostic: a snapshot taken mid-run by
    a COMPILED server restores into a Python-tick server (and vice
    versa) and both futures stay bit-identical."""
    kw = dict(vad=VADConfig(), sa_noise_std=0.25, chip_offsets=_chip(),
              faults=flt.FaultConfig(seed=5))

    def mk(compiled):
        return StreamServer(folded, CFG, hop=HOP, slots=2,
                            compiled=(CompiledTickConfig(block=4)
                                      if compiled else None), **kw)

    cand = mk(True)
    for i in range(2):
        cand.submit(f"s{i}", _duty(L + 18 * HOP, 90 + i))
    eq.advance_to(cand, 7)
    snap = cand.snapshot()

    plain = mk(False)
    plain.restore(snap)
    resumed = mk(True)
    resumed.restore(snap)
    ev_plain = eq.advance_to(plain, 20)
    ev_resumed = eq.advance_to(resumed, 20)
    ev_cand = eq.advance_to(cand, 20)
    eq.assert_events_equal(ev_cand, ev_plain, "compiled->python restore")
    eq.assert_events_equal(ev_cand, ev_resumed,
                           "compiled->compiled restore")
    eq.assert_server_equal(cand, plain, "compiled->python restore",
                           counters=False)
    eq.assert_server_equal(cand, resumed, "compiled->compiled restore")
    assert resumed._compiled_ticks > 0


# ---------------------------------------------------------------------------
# property: random interleavings (hypothesis or the deterministic shim)
# ---------------------------------------------------------------------------


_HW_CACHE = []


def _hw():
    # the property wrapper exposes a zero-arg signature, so the module
    # fixture can't be injected — fold once and cache instead
    if not _HW_CACHE:
        params = m.init_params(jax.random.PRNGKey(5), CFG)
        state = m.init_state(CFG)
        _HW_CACHE.append(m.fold_params(params, state, CFG, pack=True))
    return _HW_CACHE[0]


def _compiled_soak(hw, seed, rounds=8):
    """One random interleaving of submit/speech/silence/evict/finish/
    snapshot ops, served by a Python-tick oracle and a compiled-block
    candidate advanced to the same tick after every round."""
    rng = np.random.default_rng(seed)
    kw = dict(hop=HOP, use_kernel=False, sa_noise_std=0.5,
              vad=VADConfig(threshold_on_db=-40.0,
                            threshold_off_db=-50.0,
                            wake_margin=1, hang=0),
              dynamic_hop=DynamicHopConfig(widen_after=3,
                                           max_multiplier=2),
              faults=flt.FaultConfig(drift_std=0.1, seed=seed),
              seed=seed)
    oracle = StreamServer(hw, CFG, slots=3, **kw)

    def mk():
        return StreamServer(hw, CFG, slots=3,
                            compiled=CompiledTickConfig(block=4), **kw)

    cand = mk()
    alive = {}
    ev_o, ev_c = [], []
    for t in range(rounds):
        r = rng.random()
        if r < 0.4 and len(alive) < 3:
            sid = f"s{t}"
            alive[sid] = True
            w = rng.uniform(-1, 1, L).astype(np.float32)
            oracle.submit(sid, w)
            cand.submit(sid, w)
        elif r < 0.5 and alive:
            sid = rng.choice(sorted(alive))
            del alive[sid]
            oracle.evict(sid)
            cand.evict(sid)
        elif r < 0.6 and alive:
            sid = rng.choice(sorted(alive))
            del alive[sid]
            oracle.finish(sid)
            cand.finish(sid)
        for sid in list(alive):             # speech/silence duty bursts
            amp = 1.0 if rng.random() < 0.6 else 1e-4
            n = int(rng.integers(1, 4)) * HOP
            w = (amp * rng.standard_normal(n)).astype(np.float32)
            oracle.submit(sid, w)
            cand.submit(sid, w)
        target = oracle._steps + int(rng.integers(1, 5))
        ev_o += eq.advance_to(oracle, target)
        ev_c += eq.advance_to(cand, target)
        if t == rounds // 2:                # mid-soak snapshot swap
            cand2 = mk()
            cand2.restore(cand.snapshot())
            cand = cand2
    for sid in alive:
        oracle.finish(sid)
        cand.finish(sid)
    ev_o += oracle.drain()
    ev_c += cand.drain()
    eq.assert_events_equal(ev_o, ev_c, f"soak seed={seed}")
    eq.assert_server_equal(oracle, cand, f"soak seed={seed}",
                           counters=False)   # snapshot swap resets wall


@settings(max_examples=4, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_compiled_soak_property(seed):
    """Any op interleaving keeps the compiled server bit-identical to
    the Python-tick oracle — gating, dynamic hop, drift faults and a
    mid-soak snapshot swap included."""
    _compiled_soak(_hw(), seed)


# ---------------------------------------------------------------------------
# golden decision trace
# ---------------------------------------------------------------------------


def _golden_run(hw, compiled=True):
    """The pinned run: fixed traffic, noise + chip offsets + gating,
    served entirely by the compiled fast path where eligible (or by the
    interpreted tick with ``compiled=False``)."""
    srv = StreamServer(hw, CFG, hop=HOP, slots=2, sa_noise_std=0.3,
                       chip_offsets=_chip(), vad=VADConfig(), seed=0,
                       compiled=(CompiledTickConfig(block=8) if compiled
                                 else None))
    for i in range(2):
        srv.submit(f"s{i}", _duty(L + 16 * HOP, 1234 + i))
        srv.finish(f"s{i}")
    events = srv.drain()
    return {"config": {"sample_len": L, "hop": HOP, "slots": 2,
                       "sa_noise_std": 0.3, "chip_std": 4.0,
                       "vad": "default", "block": 8, "seed": 0},
            "compiled_ticks": srv._compiled_ticks,
            "events": events}


def _pinned(trace):
    """What the golden file pins: each event's stream, hop, keyword and
    trigger.  The score floats are left out: their last bits follow the
    op order XLA picks for the softmax and smoothing, which a JAX
    release may change without any change to a decision."""
    return {**trace, "events": [{k: v for k, v in ev.items()
                                 if k != "score"}
                                for ev in trace["events"]]}


def _render(trace):
    # sort_keys + fixed indent + trailing newline: the byte-stable form
    return (json.dumps(_pinned(trace), indent=2, sort_keys=True)
            + "\n").encode()


def test_golden_decision_trace(folded):
    """The compiled server's decision trace (stream, hop, keyword,
    trigger) matches the checked-in golden file byte for byte, and the
    interpreted tick emits exactly the same events, scores included.
    Regen (see module docstring) with REPRO_REGEN_GOLDEN=1 after an
    intentional decision-path change."""
    trace = _golden_run(folded)
    plain = _golden_run(folded, compiled=False)
    eq.assert_events_equal(trace["events"], plain["events"],
                           "golden: compiled vs interpreted")
    assert trace["compiled_ticks"] > 0 == plain["compiled_ticks"]
    got = _render(trace)
    if os.environ.get("REPRO_REGEN_GOLDEN"):
        GOLDEN.parent.mkdir(parents=True, exist_ok=True)
        GOLDEN.write_bytes(got)
    want = GOLDEN.read_bytes()
    assert got == want, (
        "golden decision trace diverged — if the change is intentional, "
        "regenerate with REPRO_REGEN_GOLDEN=1 (module docstring)")


# ---------------------------------------------------------------------------
# launch auditor: the `compiled` cause
# ---------------------------------------------------------------------------


def test_auditor_compiled_cause_rules():
    """Unit rules: one compiled block per tick, never co-issued with
    interpreted calls, trace bounded by imc_layers like a hop's."""
    a = LaunchAuditor(3, mode="flag")
    a.begin_tick(0)
    a._on_call("compiled", 3)               # fresh trace: full bound OK
    a.end_tick()
    assert not a.violations

    a.begin_tick(1)                         # two blocks in one tick
    a._on_call("compiled", 0)
    a._on_call("compiled", 0)
    a.end_tick()
    assert any(v["cause"] == "compiled" for v in a.violations)

    b = LaunchAuditor(3, mode="flag")
    b.begin_tick(0)                         # block + interpreted hop
    b._on_call("compiled", 0)
    b._on_call("hop", 0)
    b.end_tick()
    assert any("co-issued" in v["detail"] for v in b.violations)

    c = LaunchAuditor(3, mode="raise")
    c.begin_tick(0)
    with pytest.raises(LaunchAuditError):   # per-slot loop leaked in
        c._on_call("compiled", 7)


def test_compiled_audit_raise_clean_env(folded, monkeypatch):
    """REPRO_OBS_AUDIT=raise + compiled tick: a full gated noisy run
    stays violation-free, the block attributes to its first tick and
    the remaining fused ticks legitimately show zero launches."""
    monkeypatch.setenv("REPRO_OBS_AUDIT", "raise")
    srv = StreamServer(folded, CFG, hop=HOP, slots=2, vad=VADConfig(),
                       sa_noise_std=0.2, chip_offsets=_chip(),
                       compiled=CompiledTickConfig(block=8))
    assert srv.obs.audit == "raise"
    for i in range(2):
        srv.submit(f"s{i}", _duty(L + 16 * HOP, 20 + i))
        srv.finish(f"s{i}")
    srv.drain()                             # raise mode: would throw
    s = srv.auditor.stats()
    assert s["violations"] == 0
    assert s["calls"]["compiled"] == srv._compiled_blocks > 0
    hist = srv.auditor.history()
    block_ticks = [h for h in hist if h["calls"]["compiled"]]
    assert block_ticks and all(h["calls"]["compiled"] == 1
                               for h in block_ticks)
    # fused non-first ticks show zero launches of any cause
    assert any(h["launches"] == 0 for h in hist)


# ---------------------------------------------------------------------------
# sharded: per-device pools, per-device auditors
# ---------------------------------------------------------------------------


def test_sharded_compiled_bitident(folded):
    """A sharded fleet with compiled pools serves every stream the same
    decisions as the sharded Python-tick fleet AND the single-device
    oracle — with each device's auditor raise-clean and attributing
    compiled blocks to its own pool."""
    obs = ObsConfig(audit="raise")
    kw = dict(hop=HOP, sa_noise_std=0.2, vad=VADConfig(), seed=0,
              obs=obs)
    oracle = StreamServer(folded, CFG, slots=4, **kw)
    plain = ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2),
                                slots=2, **kw)
    fast = ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2),
                               slots=2,
                               compiled=CompiledTickConfig(block=8),
                               **kw)
    for i in range(4):
        w = _duty(L + 12 * HOP, 500 + i)
        for srv in (oracle, plain, fast):
            srv.submit(f"s{i}", w)
            srv.finish(f"s{i}")
    ev_o, ev_p, ev_f = oracle.drain(), plain.drain(), fast.drain()
    eq.assert_events_equal(ev_p, ev_f, "sharded python vs compiled",
                           by_stream=True)
    eq.assert_events_equal(ev_o, ev_f, "oracle vs sharded compiled",
                           by_stream=True)
    for d, pool in enumerate(fast.pools):
        assert pool._compiled_ticks > 0
        s = pool.auditor.stats()
        assert s["violations"] == 0
        assert s["device"] == d
        assert s["calls"]["compiled"] == pool._compiled_blocks > 0


def test_compiled_stats_section(folded):
    srv = StreamServer(folded, CFG, hop=HOP, slots=2,
                       compiled=CompiledTickConfig(block=4))
    srv.submit("s0", _duty(L + 8 * HOP, 7))
    srv.finish("s0")
    srv.drain()
    st_ = srv.stats()["compiled"]
    assert st_["block"] == 4
    assert st_["ticks"] >= st_["blocks"] > 0
