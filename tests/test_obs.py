"""The observability contract (repro.obs + its serving integration):

* the metrics registry keys cells by (name, labels), keeps counter /
  gauge / histogram kinds apart, snapshots/restores and merges with the
  documented semantics (counters sum, gauges last-write, hists pool);
* the flight recorder is a bounded ring: wraparound keeps the newest
  events, ``dropped()`` counts the fallen, snapshots round-trip;
* the launch auditor catches a deliberately doubled batched hop (flag
  records, raise throws), a gate region that traces kernels, and a
  per-call over-trace — and reports ZERO violations on real gated /
  faulted / canary / learning traffic in raise mode;
* telemetry fully on (registry + recorder + auditor raise + trace) is
  bit-identical to telemetry off — SA noise, chip offsets and fault
  models included;
* ``StreamServer.snapshot()`` v2 round-trips the registry and recorder,
  and the restored server's subsequent events are bit-identical;
* the Chrome/Perfetto export and the Prometheus text render are
  well-formed.
"""

import json
import re

import jax
import numpy as np
import pytest

import _equiv as eq

from repro.core import faults as flt
from repro.core import imc
from repro.models import kws as m
from repro.obs import (FlightRecorder, LaunchAuditError, LaunchAuditor,
                       MetricsRegistry, ObsConfig, TraceBuilder,
                       counter_property)
from repro.serving import HealthConfig, StreamServer, VADConfig

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


def _gated_wav(rng, n_hops=12, quiet=(4, 9)):
    """Speech with a mid-stream silent stretch: init + hops + gated
    fills + a wake replay in one drain."""
    wav = rng.uniform(-1, 1, L + n_hops * HOP).astype(np.float32)
    wav[L + quiet[0] * HOP:L + quiet[1] * HOP] *= 1e-4
    return wav


_VAD = VADConfig(threshold_on_db=-40.0, threshold_off_db=-50.0,
                 wake_margin=1, hang=0)

_OBS_ON = ObsConfig(recorder=64, audit="raise", trace=True)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_kinds_labels_values():
    reg = MetricsRegistry()
    reg.inc("calls", cause="hop")
    reg.inc("calls", 3, cause="hop")
    reg.inc("calls", cause="gate")
    reg.set_gauge("depth", 7)
    reg.observe("uj", 2.0)
    reg.observe("uj", 4.0)
    assert reg.value("calls", cause="hop") == 4
    assert reg.value("calls", cause="gate") == 1
    assert reg.value("calls") == 0               # unlabelled cell absent
    assert reg.total("calls") == 5
    assert reg.value("depth") == 7
    h = reg.value("uj")
    assert h["count"] == 2 and h["sum"] == 6.0
    assert h["min"] == 2.0 and h["max"] == 4.0 and h["mean"] == 3.0
    assert {"cause": "hop"} in reg.labels("calls")
    col = reg.collect()
    assert col["calls"]["kind"] == "counter"
    assert col["uj"]["kind"] == "histogram"
    # label order never splits a cell
    reg.inc("pair", a=1, b=2)
    reg.inc("pair", b=2, a=1)
    assert reg.value("pair", a=1, b=2) == 2


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.inc("x")
    with pytest.raises(ValueError, match="already registered"):
        reg.set_gauge("x", 1)
    with pytest.raises(ValueError, match="already registered"):
        reg.observe("x", 1.0)


def test_registry_snapshot_restore_roundtrip():
    reg = MetricsRegistry()
    reg.inc("c", 5, cause="hop")
    reg.set_gauge("g", -2.5)
    reg.observe("h", 1.0, layer="conv2")
    reg.observe("h", 9.0, layer="conv2")
    snap = reg.snapshot()
    json.dumps(snap)                             # JSON-serializable
    reg2 = MetricsRegistry()
    reg2.inc("junk")                             # must be cleared
    reg2.restore(snap)
    assert reg2.snapshot() == snap
    assert reg2.value("junk", default=None) is None
    assert reg2.value("h", layer="conv2") == reg.value("h", layer="conv2")
    # restored cells keep their write paths working
    reg2.inc("c", cause="hop")
    assert reg2.value("c", cause="hop") == 6
    with pytest.raises(ValueError, match="version"):
        reg2.restore({"version": 99, "cells": []})


def test_registry_merge_semantics():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.inc("c", 2)
    b.inc("c", 3)
    a.set_gauge("g", 1)
    b.set_gauge("g", 9)
    a.observe("h", 1.0)
    b.observe("h", 5.0)
    b.inc("only_b", kind="x")
    a.merge(b)
    assert a.value("c") == 5                     # counters sum
    assert a.value("g") == 9                     # gauges last-write
    h = a.value("h")                             # histograms pool
    assert h["count"] == 2 and h["min"] == 1.0 and h["max"] == 5.0
    assert a.value("only_b", kind="x") == 1
    b2 = MetricsRegistry()
    b2.set_gauge("c", 1)
    with pytest.raises(ValueError, match="already registered"):
        a.merge(b2)


def test_registry_prometheus_text():
    reg = MetricsRegistry()
    reg.inc("serving.batched_calls", 4, cause="hop")
    reg.set_gauge("health.state", 0)
    reg.observe("serving.tick_uj", 2.5)
    text = reg.prometheus_text()
    lines = text.strip().split("\n")
    assert "# TYPE serving_batched_calls counter" in lines
    assert 'serving_batched_calls{cause="hop"} 4' in lines
    assert "# TYPE health_state gauge" in lines
    assert "# TYPE serving_tick_uj summary" in lines
    assert "serving_tick_uj_count 1" in lines
    assert "serving_tick_uj_sum 2.5" in lines
    # every sample line is name{labels}? value
    sample = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*"
                        r"(\{[a-zA-Z0-9_]+=\"[^\"]*\""
                        r"(,[a-zA-Z0-9_]+=\"[^\"]*\")*\})? \S+$")
    for line in lines:
        if not line.startswith("#"):
            assert sample.match(line), line


def test_counter_property_attribute_api():
    class Holder:
        n = counter_property("demo.n")
        k = counter_property("demo.k", cause="hop")

        def __init__(self):
            self._metrics = MetricsRegistry()

    h = Holder()
    assert h.n == 0
    h.n += 1
    h.n += 1
    h.k = 5
    assert h.n == 2
    assert h._metrics.value("demo.n") == 2
    assert h._metrics.value("demo.k", cause="hop") == 5
    h._metrics.set_counter("demo.n", 9)
    assert h.n == 9                              # reads go through too


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_recorder_wraparound_and_dropped():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record(i, "tick", uj=float(i))
    assert len(rec) == 4
    assert rec.dropped() == 6
    evs = rec.events()
    assert [e["seq"] for e in evs] == [6, 7, 8, 9]
    assert [e["tick"] for e in evs] == [6, 7, 8, 9]
    rec.record(10, "admit", stream="s0")
    assert rec.events("admit")[0]["stream"] == "s0"
    assert len(rec.events("tick")) == 3
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_recorder_snapshot_roundtrip_and_dump(tmp_path):
    rec = FlightRecorder(capacity=3)
    for i in range(5):
        rec.record(i, "tick", computed=i)
    snap = rec.snapshot()
    json.dumps(snap)
    rec2 = FlightRecorder(capacity=8)
    rec2.restore(snap)
    assert rec2.capacity == 3
    assert rec2.events() == rec.events()
    assert rec2.dropped() == rec.dropped()
    rec2.record(5, "tick")                       # seq continues
    assert rec2.events()[-1]["seq"] == 5
    path = tmp_path / "flight.jsonl"
    assert rec.dump(path) == 3
    got = [json.loads(line) for line in path.read_text().splitlines()]
    assert got == rec.events()
    with pytest.raises(ValueError, match="version"):
        rec2.restore({"version": 99})


# ---------------------------------------------------------------------------
# ObsConfig
# ---------------------------------------------------------------------------


def test_obsconfig_validation_and_env(monkeypatch):
    assert ObsConfig() == ObsConfig(recorder=0, audit="off", trace=False)
    with pytest.raises(ValueError):
        ObsConfig(audit="bogus")
    with pytest.raises(ValueError):
        ObsConfig(recorder=-1)
    monkeypatch.setenv("REPRO_OBS_RECORDER", "32")
    monkeypatch.setenv("REPRO_OBS_AUDIT", "raise")
    monkeypatch.setenv("REPRO_OBS_TRACE", "1")
    assert ObsConfig.from_env() == ObsConfig(recorder=32, audit="raise",
                                             trace=True)
    monkeypatch.setenv("REPRO_OBS_TRACE", "0")
    assert not ObsConfig.from_env().trace


# ---------------------------------------------------------------------------
# Launch auditor
# ---------------------------------------------------------------------------


def test_auditor_catches_doubled_hop():
    """Two batched hop calls in one tick — a per-slot hop loop — must be
    flagged: that is exactly the regression the one-launch contract
    forbids."""
    aud = LaunchAuditor(imc_layers=5, mode="flag")
    aud.begin_tick(0)
    with aud.region("hop"):
        pass
    with aud.region("hop"):
        pass
    aud.end_tick()
    assert len(aud.violations) == 1
    assert aud.violations[0]["cause"] == "hop"
    assert aud.stats()["max_hop_calls_per_tick"] == 2

    aud = LaunchAuditor(imc_layers=5, mode="raise")
    aud.begin_tick(0)
    with aud.region("hop"):
        pass
    with aud.region("hop"):
        pass
    with pytest.raises(LaunchAuditError, match="hop"):
        aud.end_tick()


def test_auditor_gate_and_overtrace_rules():
    aud = LaunchAuditor(imc_layers=5, mode="raise")
    aud.begin_tick(0)
    # a gate fill must trace zero kernels
    with pytest.raises(LaunchAuditError, match="gate"):
        aud._on_call("gate", traced=1)
    # a compute call may trace up to imc_layers fresh launches (nested
    # per-layer jits cache across outer traces), never more
    aud = LaunchAuditor(imc_layers=5, mode="raise")
    aud.begin_tick(0)
    aud._on_call("hop", traced=5)
    with pytest.raises(LaunchAuditError, match="replay"):
        aud._on_call("replay", traced=6)
    # doubled init only violates on batched-admission servers
    aud = LaunchAuditor(imc_layers=5, mode="flag", batch_init=False)
    aud.begin_tick(0)
    aud._on_call("init", traced=0)
    aud._on_call("init", traced=0)
    aud.end_tick()
    assert aud.violations == []
    aud = LaunchAuditor(imc_layers=5, mode="flag", batch_init=True)
    aud.begin_tick(0)
    aud._on_call("init", traced=0)
    aud._on_call("init", traced=0)
    aud.end_tick()
    assert [v["cause"] for v in aud.violations] == ["init"]

    with pytest.raises(ValueError):
        LaunchAuditor(imc_layers=5, mode="sometimes")
    with pytest.raises(ValueError):
        LaunchAuditor(imc_layers=0)
    aud = LaunchAuditor(imc_layers=5)
    with pytest.raises(ValueError):
        with aud.region("bogus"):
            pass


def test_auditor_device_label_attribution():
    """Sharded pools label their auditors: the device rides every
    violation dict, the raise message, and stats() — so fleet rollups
    can attribute a broken launch contract to the pool that broke it."""
    aud = LaunchAuditor(imc_layers=5, mode="flag", device=1)
    aud.begin_tick(0)
    with aud.region("hop"):
        pass
    with aud.region("hop"):
        pass
    aud.end_tick()
    assert aud.violations[0]["device"] == 1
    assert aud.stats()["device"] == 1
    aud = LaunchAuditor(imc_layers=5, mode="raise", device=3)
    aud.begin_tick(0)
    with pytest.raises(LaunchAuditError, match=r"device 3"):
        aud._on_call("gate", traced=1)
    # unlabeled auditors keep the historical stats shape
    assert "device" not in LaunchAuditor(imc_layers=5).stats()


def test_auditor_history_attribution():
    aud = LaunchAuditor(imc_layers=5, mode="flag", history=2)
    for tick in range(3):
        aud.begin_tick(tick)
        with aud.region("hop"):
            pass
        if tick == 0:
            with aud.region("gate"):
                pass
        aud.end_tick()
    hist = aud.history()
    assert len(hist) == 2                        # bounded
    assert [h["tick"] for h in hist] == [1, 2]
    assert all(h["calls"]["hop"] == 1 for h in hist)
    assert all(h["launches_per_layer"] == 1 for h in hist)
    s = aud.stats()
    assert s["ticks"] == 3 and s["violations"] == 0
    assert s["calls"]["hop"] == 3 and s["calls"]["gate"] == 1


# ---------------------------------------------------------------------------
# Serving integration: bit-exactness, audit-clean traffic, snapshots
# ---------------------------------------------------------------------------


def _run(folded, obs, wavs, **kw):
    srv = StreamServer(folded, CFG, hop=HOP, slots=len(wavs),
                       use_kernel=True, vad=_VAD, seed=3, obs=obs, **kw)
    for k, v in wavs.items():
        srv.submit(k, v)
        srv.finish(k)
    return srv, srv.drain()


@pytest.mark.streaming
def test_telemetry_bitexact_gated_noise_offsets(folded):
    """Telemetry fully on — registry + recorder + auditor in raise mode +
    trace spans — must not change a single decision on the gated
    SA-noise + chip-offset configuration."""
    rng = np.random.default_rng(7)
    wavs = {f"s{i}": _gated_wav(rng) for i in range(2)}
    kw = dict(sa_noise_std=0.9, chip_offsets=_chip())
    _, ev_off = _run(folded, ObsConfig(), wavs, **kw)
    srv, ev_on = _run(folded, _OBS_ON, wavs, **kw)
    assert ev_on == ev_off
    assert len(ev_off) > 0
    s = srv.auditor.stats()
    assert s["violations"] == 0
    assert s["max_hop_calls_per_tick"] <= 1
    assert s["calls"]["gate"] > 0                # silence actually gated
    assert s["calls"]["replay"] > 0              # wake replay ran audited
    assert len(srv.recorder.events("tick")) > 0
    assert len(srv.trace) > 0


@pytest.mark.streaming
def test_telemetry_bitexact_with_faults_and_canaries(folded):
    """Same bit-identity with the fault model loaded and canary health
    windows riding the ticks — and the auditor stays clean in raise mode
    across the canary traffic."""
    rng = np.random.default_rng(8)
    wavs = {"s0": _gated_wav(rng, n_hops=14)}
    kw = dict(chip_offsets=_chip(), faults=flt.FaultConfig(seed=5),
              health=HealthConfig(interval=4))
    srv_off, ev_off = _run(folded, ObsConfig(), wavs, **kw)
    srv_on, ev_on = _run(folded, _OBS_ON, wavs, **kw)
    assert ev_on == ev_off
    assert srv_on.health.canaries >= 1           # canaries actually ran
    assert srv_on.health.canaries == srv_off.health.canaries
    assert srv_on.auditor.stats()["violations"] == 0


@pytest.mark.streaming
def test_audit_clean_mixed_learning_traffic(folded):
    """The one-launch contract holds with an enrollment session's
    learning hops sharing ticks with live inference: auditor in raise
    mode, zero violations, at most one batched hop per tick."""
    from repro.core.onchip_training import OnChipTrainConfig
    from repro.serving import CustomizeConfig

    rng = np.random.default_rng(9)
    srv = StreamServer(folded, CFG, hop=HOP, slots=3, use_kernel=True,
                       vad=_VAD, seed=3, obs=_OBS_ON)
    sess = srv.customize("u0", CustomizeConfig(
        train=OnChipTrainConfig(epochs=8, fixed_error_scale=1.375),
        epochs_per_tick=4, layers_per_tick=5))
    for c in range(2):
        sess.enroll(c, rng.uniform(-1, 1, L).astype(np.float32))
    sess.finish_enrollment()
    srv.submit("live", _gated_wav(rng))
    srv.finish("live")
    events = srv.drain()
    steps = 0
    while not sess.done and steps < 500:
        srv.step()
        steps += 1
    assert sess.done
    assert len(events) > 0
    s = srv.auditor.stats()
    assert s["violations"] == 0
    assert s["max_hop_calls_per_tick"] <= 1
    assert srv.stats()["learn_hops"] > 0
    assert srv.metrics.value("customize.sessions") == 1
    assert srv.metrics.value("customize.epochs") == sess.result.epochs


@pytest.mark.streaming
def test_server_counters_live_in_registry(folded):
    """The scheduler/health stats() counters are views over the one
    registry — no parallel hand-rolled counter lists left to drift."""
    rng = np.random.default_rng(10)
    srv, events = _run(folded, _OBS_ON, {"s0": _gated_wav(rng)})
    reg = srv.metrics
    st = srv.stats()
    assert reg.value("serving.steps") == srv._steps
    assert reg.value("serving.decisions") == len(events)
    assert reg.value("serving.batched_calls", cause="hop") == srv._hop_calls
    assert (reg.value("serving.batched_calls", cause="gate")
            == st["batched_calls"]["gate"])
    assert reg.value("serving.hops", kind="speech") == st["speech_hops"]
    assert reg.value("serving.hops", kind="gated") == st["gated_hops"]
    assert reg.value("serving.tick_uj")["count"] > 0
    assert st["obs"]["recorder"]["events"] == len(srv.recorder)
    assert st["obs"]["audit"]["violations"] == 0


@pytest.mark.streaming
def test_snapshot_v2_roundtrips_registry_and_recorder(folded, tmp_path):
    """Snapshot mid-run with telemetry on; the restored server carries
    the same registry cells and recorder ring, and its subsequent
    decisions are bit-identical."""
    rng = np.random.default_rng(11)
    wav = _gated_wav(rng, n_hops=12)
    head, tail = wav[:L + 5 * HOP], wav[L + 5 * HOP:]

    srv = StreamServer(folded, CFG, hop=HOP, slots=1, use_kernel=True,
                       vad=_VAD, seed=3, obs=_OBS_ON)
    srv.submit("s0", head)
    for _ in range(6):
        srv.step()
    path = tmp_path / "server.npz"
    srv.snapshot(path)
    srv2 = StreamServer(folded, CFG, hop=HOP, slots=1, use_kernel=True,
                        vad=_VAD, seed=3, obs=_OBS_ON)
    srv2.restore(path)
    assert srv2.metrics.snapshot() == srv.metrics.snapshot()
    assert srv2.recorder.events() == srv.recorder.events()
    assert srv2._steps == srv._steps
    ev1, ev2 = [], []
    for s, ev in ((srv, ev1), (srv2, ev2)):
        s.submit("s0", tail)
        s.finish("s0")
        ev.extend(s.drain())
    assert ev1 == ev2

    def deterministic(reg):
        # wall-clock counters legitimately differ between processes
        return [c for c in reg.snapshot()["cells"]
                if "wall" not in c[0]]

    assert deterministic(srv2.metrics) == deterministic(srv.metrics)


@pytest.mark.streaming
def test_trace_export_and_prometheus_render(folded, tmp_path):
    rng = np.random.default_rng(12)
    srv, _ = _run(folded, _OBS_ON, {"s0": _gated_wav(rng)})
    doc = srv.trace.to_chrome()
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert evs[0] == {"name": "process_name", "ph": "M", "pid": 0,
                      "args": {"name": "repro.serving"}}
    names = {e["name"] for e in evs[1:]}
    assert {"tick", "hop", "gate", "riders"} <= names
    for e in evs[1:]:
        assert e["ph"] == "X"
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert "tick" in e["args"]
    ticks = [e for e in evs[1:] if e["name"] == "tick"]
    assert all("uj" in e["args"] for e in ticks)
    path = tmp_path / "trace.json"
    n = srv.trace.dump(path)
    assert n == len(srv.trace)
    assert json.loads(path.read_text())["traceEvents"][0]["ph"] == "M"
    text = srv.metrics.prometheus_text()
    assert 'serving_batched_calls{cause="hop"}' in text
    assert "serving_tick_uj_count" in text


@pytest.mark.streaming
def test_sharded_per_device_one_launch_audit(folded, monkeypatch):
    """The one-launch-per-layer contract is PER DEVICE under sharding:
    with inference, canary health windows and an enrollment session's
    learning hops mixed across 2 device pools, every pool's auditor
    (armed via ``REPRO_OBS_AUDIT=raise``) sees at most one batched hop
    per tick, zero traced kernels on gate fills, and zero violations —
    and each auditor carries its pool's device label."""
    from repro.core.onchip_training import OnChipTrainConfig
    from repro.serving import (CustomizeConfig, HealthConfig,
                               ShardedStreamServer)

    monkeypatch.setenv("REPRO_OBS_AUDIT", "raise")
    rng = np.random.default_rng(21)
    sh = ShardedStreamServer(folded, CFG, devices=eq.pool_devices(2),
                             slots=3, hop=HOP,
                             use_kernel=True, vad=_VAD, seed=3,
                             health=HealthConfig(interval=4))
    sess = sh.customize("u0", CustomizeConfig(
        train=OnChipTrainConfig(epochs=8, fixed_error_scale=1.375),
        epochs_per_tick=4, layers_per_tick=5))
    for c in range(2):
        sess.enroll(c, rng.uniform(-1, 1, L).astype(np.float32))
    sess.finish_enrollment()
    for i in range(3):                      # live gated traffic, both pools
        sh.submit(f"live{i}", _gated_wav(rng))
        sh.finish(f"live{i}")
    events = sh.drain()
    steps = 0
    while not sess.done and steps < 500:
        sh.step()
        steps += 1
    assert sess.done and len(events) > 0
    assert {sh.where(f"live{i}") for i in range(3)} == {0, 1}
    st = sh.stats()
    assert st["audit"]["violations"] == 0
    for d, srv in enumerate(sh.pools):
        s = srv.auditor.stats()
        assert s["device"] == d
        assert s["mode"] == "raise"          # the env arming reached it
        assert s["violations"] == 0
        assert s["max_hop_calls_per_tick"] <= 1
        assert s["calls"]["hop"] > 0         # every pool actually computed
        assert s["traced_launches"] > 0      # fresh pallas traces counted
        for h in srv.auditor.history():
            assert h["launches_per_layer"] <= 3   # init+hop+replay bound
    # gate fills ran somewhere in the fleet and traced nothing (a traced
    # gate would have raised above)
    assert sum(p.auditor.stats()["calls"]["gate"] for p in sh.pools) > 0
    learn = sum(p.stats()["learn_hops"] for p in sh.pools)
    assert learn > 0                         # learning rode the batches


def test_trace_builder_relative_timestamps():
    tb = TraceBuilder(process_name="p")
    tb.span("a", 10.0, 10.5, tick=0)
    tb.span("b", 11.0, 11.25, tick=1)
    tb.counter("c", 11.5, depth=3)
    tb.instant("i", 12.0)
    evs = tb.to_chrome()["traceEvents"][1:]
    assert evs[0]["ts"] == 0.0 and evs[0]["dur"] == 5e5
    assert evs[1]["ts"] == 1e6 and evs[1]["dur"] == 2.5e5
    assert evs[2]["ph"] == "C" and evs[2]["args"] == {"depth": 3}
    assert evs[3]["ph"] == "i" and evs[3]["ts"] == 2e6
