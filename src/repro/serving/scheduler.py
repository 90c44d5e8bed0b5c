"""Multi-stream batched scheduler for always-on KWS serving.

Slot-based continuous-batching-light (the KWS analogue of
``repro.launch.serve``'s decoder slots): a fixed pool of stream slots,
each holding one live audio stream's incremental ``StreamState``.

**One-launch-per-layer invariant.**  Every ``step()`` batches ALL
hop-ready slots' fresh frames into one ``stream_step`` call — i.e. exactly
one fused ``pallas_call`` per IMC layer for the whole fleet of streams,
the M-tiling of the fused kernel amortizing the weight-stationary packs
across streams.  Slots that are not ready this step ride along masked
(their state is restored verbatim; their logits are ignored), so the
launch count is independent of readiness.  This includes learning work:
a customization session's replay hops (repro.serving.customize) are just
rows of the same batch, and hot-swapped slots' compensated biases /
fine-tuned heads ride per-slot operands of the same launch.  A wake
replay (below) adds one extra multi-hop launch per layer for the waking
slot — the whole deferred run in one call, not one per deferred hop.

**Voice-activity gating** (``vad=VADConfig(...)``): each hop of each
stream is first classified speech/silence by the cheap digital energy
detector (repro.serving.vad).  Silent hops launch NO IMC kernels:

* the last ``wake_margin`` silent hops are *deferred* — buffered host-side
  with the jax state untouched — so a speech onset replays them through
  the real IMC path (ONE multi-hop launch per layer for the whole
  deferred run, bit-identical to replaying hop by hop) and a keyword
  straddling the silence->speech edge keeps its prefix (if the silent run
  never exceeds the margin, the gated decision sequence is bit-identical
  to ungated streaming);
* silent hops older than the margin are *gated*: the state advances by a
  masked no-op column fill (``stream.gated_step`` — each layer's constant
  silence response shifts into the carries and the GAP ring), charged
  leakage-only in the energy model
  (``repro.core.energy.gated_energy_summary``);
* gated/deferred hops emit no decision events — the VAD's "silence" IS
  the decision — and the decision head stays frozen (mask-aware).

With ``VADConfig(force="speech")`` every hop computes and the server is
bit-identical to an ungated one (the CI equivalence gate).

**Dynamic hop** (``dynamic_hop=DynamicHopConfig(...)``): when every
active slot's smoothed posterior stays below ``calm_score`` for
``widen_after`` consecutive ticks, the effective hop doubles (up to
``max_multiplier`` x the base hop — any multiple of
``hop_alignment(cfg)`` keeps column reuse exact); activity (a hot
posterior or a VAD wake) snaps it back to the base hop.  A hop change
rebuilds every live slot's ``StreamState`` from its retained last window
of consumed audio (the streaming geometry — carry sizes, fresh-column
counts — is hop-dependent, so states cannot be carried across).

**Admission control / backpressure** (``admission=AdmissionConfig(...)``):
``submit`` returns ``"rejected"`` (and buffers nothing) once the wait
queue holds ``max_queue`` streams; a stream whose buffered backlog
exceeds the ``max_lag_s`` latency SLO is shed — its oldest audio is
dropped to the low-water mark and it re-initializes from the freshest
window; the slot pool autoscales between ``min_slots`` and ``max_slots``
(grow under sustained queue pressure, shrink after sustained idle slots).

Host side, each stream owns a ring buffer of pending samples
(``submit()`` appends arbitrary-sized chunks); a stream is admitted to a
free slot immediately, waits in the admission queue otherwise, and is
evicted when its producer calls ``finish()`` and its buffer drains (or
explicitly via ``evict()``).  Admission runs the stream's first full
window (``stream_init``): with ``batch_init`` (default) every slot whose
first window is ready this tick — fresh admissions and a customization
session's whole wave of feature-replay streams alike — initializes in
ONE masked batched ``stream_init`` call (one fused launch per IMC layer
for the wave, bit-identical to one-at-a-time; ``batch_init=False`` keeps
the sequential B=1 path).

**Customization** (``customize(stream_id)`` / ``install_custom``): an
enrollment/fine-tuning session (repro.serving.customize) rides the same
machinery — enrollment hops on the live stream, calibration + SGA
fine-tune as bounded background jobs per tick, feature-replay streams as
internal slots of the same batch, and the finished profile hot-swapped
into the stream's per-slot rider rows (bias delta + FC head + silence
fill) without touching other slots.

**Fault injection + health** (``faults=FaultConfig(...)`` /
``health=HealthConfig(...)``): a seeded silicon fault model
(repro.core.faults) rides the batched launches as a chip-global pre-sign
count delta added to every slot's bias-delta rider row — fault injection
launches ZERO extra kernels and the one-launch-per-layer invariant holds
under fault.  The health monitor (repro.serving.health) submits periodic
canary windows as internal streams of the same batch, localizes faulty
layers/columns from the captured carries/ring, drives the healthy ->
degraded -> quarantined -> recovering state machine, re-runs the paper's
test-mode bias compensation as a tick-resumable background job and
hot-swaps the heal through the same rider row (``_set_heal_delta``);
decision events carry ``degraded`` flags while the chip is unhealthy.

**Profiles at admission** (``profiles=ProfileStore(...)``):
``submit(stream_id, chunk, user_id=...)`` auto-installs the user's stored
profile onto the assigned slot; a per-tick staleness sweep re-installs
profiles whose store mtime moved and resets streams whose profile was
deleted.

**Crash safety**: ``snapshot()`` serializes the complete serving state —
slot carries and GAP rings, decision/VAD state, noise-field keys, fault
and health state, mid-flight customization sessions — to an atomically
written .npz (tmp+fsync+``os.replace``, the ProfileStore idiom);
``restore()`` on a freshly constructed identically-configured server
resumes bit-identically to an uninterrupted run (test-enforced).

**Two networks.**  ``cfg`` picks the engine: a ``KWSConfig`` serves the
folded IMC net (``repro.serving.stream``), a ``KWTConfig`` the Keyword
Transformer (``repro.serving.kwt_stream``: an MFCC frame ring and the
whole encoder per hop).  A KWT server refuses, at construction, every
option that only the IMC net has (``_KWT_REFUSED``).

Per-hop logits flow into the shared decision head
(repro.serving.decision): smoothing + hysteresis + refractory, batched and
mask-aware.  ``stats()`` reports per-stream and aggregate decisions/sec,
hop latency, duty cycle, shed/reject counts, the gated analytical
uJ/decision and per-session customization progress.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import json
import os
import pickle
import tempfile
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import energy
from repro.models import kws, kwt
from repro.obs import (FlightRecorder, LaunchAuditor, MetricsRegistry,
                       ObsConfig, compiles, counter_property, span)
from repro.serving import decision as dec
from repro.serving import kwt_stream
from repro.serving import stream as sv
from repro.serving import vad as vd


@dataclasses.dataclass(frozen=True)
class DynamicHopConfig:
    """Widen the hop when nothing interesting is happening.

    A tick is *calm* when no computed hop's smoothed posterior reaches
    ``calm_score`` (silence-only ticks are calm by construction).  After
    ``widen_after`` consecutive calm ticks the effective hop doubles,
    capped at ``max_multiplier`` x the base hop and at what the stream
    geometry admits; any hot posterior or VAD wake narrows back to the
    base hop immediately.

    ``calm_silence`` (duty-aware widening): a separate, typically smaller
    calm-tick threshold used when the whole tick was VAD-silent (every
    ready hop gated) — silence earns the wider hop faster than merely
    unconvincing speech.  None (the default) keeps one threshold for
    both, bit-identical to the pre-knob behavior; streams submitted with
    ``force="speech"``/``force_compute`` never count as silent, so forced
    paths are unaffected."""

    max_multiplier: int = 4
    widen_after: int = 6
    calm_score: float = 0.35
    calm_silence: Optional[int] = None

    def __post_init__(self):
        if self.max_multiplier < 1:
            raise ValueError("max_multiplier must be >= 1")
        if self.widen_after < 1:
            raise ValueError("widen_after must be >= 1")
        if self.calm_silence is not None and self.calm_silence < 1:
            raise ValueError("calm_silence must be >= 1 (or None)")


jax.tree_util.register_static(DynamicHopConfig)


@dataclasses.dataclass(frozen=True)
class AdmissionConfig:
    """Admission control, latency SLO and slot autoscaling.

    ``max_queue``: streams allowed to wait for a slot; further ``submit``s
    of new streams return ``"rejected"``.  ``max_lag_s``: per-stream
    backlog SLO in seconds of audio; a stream over it is shed to the
    low-water mark (half the SLO, never below one window) and re-admitted
    from its freshest window.  ``min_slots``/``max_slots`` bound the slot
    pool (both default to the constructor's ``slots`` — no autoscaling);
    the pool grows after ``scale_up_after`` consecutive ticks with a
    non-empty queue and shrinks after ``scale_down_after`` consecutive
    ticks with idle trailing slots."""

    max_queue: Optional[int] = 8
    max_lag_s: Optional[float] = None
    min_slots: Optional[int] = None
    max_slots: Optional[int] = None
    scale_up_after: int = 2
    scale_down_after: int = 6

    def __post_init__(self):
        if self.max_queue is not None and self.max_queue < 0:
            raise ValueError("max_queue must be >= 0 (or None)")
        if self.scale_up_after < 1 or self.scale_down_after < 1:
            raise ValueError("scale_up/down_after must be >= 1")


jax.tree_util.register_static(AdmissionConfig)


@dataclasses.dataclass
class _Stream:
    stream_id: str
    uid: int
    buf: np.ndarray                       # pending samples (host ring tail)
    slot: Optional[int] = None
    initialized: bool = False
    finished: bool = False                # producer called finish()
    hops: int = 0                         # decisions made (incl. window 0)
    triggers: List[dict] = dataclasses.field(default_factory=list)
    wall_s: float = 0.0                   # server time attributed to it
    recent: np.ndarray = dataclasses.field(     # last consumed window —
        default_factory=lambda: np.zeros((0,), np.float32))  # hop-retarget
    #                                       re-init source
    pending: List[np.ndarray] = dataclasses.field(   # deferred silent hops
        default_factory=list)                        # (<= wake_margin)
    silent_run: int = 0                   # consecutive silent hops
    gated_hops: int = 0                   # fill-advanced (no-compute) hops
    sheds: int = 0
    shed_samples: int = 0
    # -- customization (repro.serving.customize) --------------------------
    internal: bool = False                # session-owned replay stream: no
    #                                       decision events, no admission
    #                                       bookkeeping, exempt from SLO
    force_compute: bool = False           # bypass VAD gating (enrollment /
    #                                       replay hops must run the IMC
    #                                       path so captures stay exact)
    consumed: int = 0                     # samples advanced through the
    #                                       stream state (capture targets)
    custom: Optional[dict] = None         # per-stream riders: {"delta":
    #                                       {conv_i: (C_i,)}, "head":
    #                                       (fc_w, fc_b), "fills": tuple}
    # -- profile store (repro.checkpoint.profiles) ------------------------
    user_id: Optional[str] = None         # owner in the profile store
    profile_mtime: Optional[int] = None   # installed profile's st_mtime_ns
    #                                       (None: no profile installed)


def _select_state(mask: jax.Array, new, old):
    def sel(n, o):
        m = mask.reshape(mask.shape + (1,) * (n.ndim - 1))
        return jnp.where(m, n, o)
    return jax.tree_util.tree_map(sel, new, old)


def _scatter_slot(state, one, slot):
    return jax.tree_util.tree_map(lambda full, o: full.at[slot].set(o[0]),
                                  state, one)


# -- crash-safe snapshot codec ----------------------------------------------
#
# A generic tree -> (JSON spec, array table) encoder: arrays are stored
# losslessly as .npz entries (the fixed-point grids round-trip exactly,
# which is what makes restore bit-identical), registered NamedTuples
# round-trip by class name, and config dataclasses fall back to pickle
# bytes stored as uint8 arrays.  Snapshots are an own-file trust domain
# (like the profile store): only restore snapshots you wrote.

def _snap_class(name: str):
    if name == "HeadState":
        from repro.core.onchip_training import HeadState
        return HeadState
    return {"StreamState": sv.StreamState,
            "WindowState": sv.WindowState,
            "KWTState": kwt_stream.KWTState,
            "DecisionState": dec.DecisionState,
            "VADState": vd.VADState}[name]


def _snap_encode(obj, arrays: Dict[str, np.ndarray]) -> dict:
    if obj is None:
        return {"t": "none"}
    if isinstance(obj, (bool, int, float, str)):
        return {"t": "v", "v": obj}
    if isinstance(obj, np.integer):
        return {"t": "v", "v": int(obj)}
    if isinstance(obj, np.floating):
        return {"t": "v", "v": float(obj)}
    if isinstance(obj, (np.ndarray, jax.Array)):
        k = f"a{len(arrays)}"
        arrays[k] = np.asarray(obj)
        return {"t": "arr", "k": k}
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):
        return {"t": "nt", "c": type(obj).__name__,
                "items": [_snap_encode(x, arrays) for x in obj]}
    if isinstance(obj, tuple):
        return {"t": "tuple", "items": [_snap_encode(x, arrays)
                                        for x in obj]}
    if isinstance(obj, list):
        return {"t": "list", "items": [_snap_encode(x, arrays)
                                       for x in obj]}
    if isinstance(obj, dict):
        keys = list(obj.keys())
        if not all(isinstance(k, str) for k in keys):
            raise TypeError(f"snapshot dicts need str keys: {keys!r}")
        return {"t": "dict", "keys": keys,
                "items": [_snap_encode(obj[k], arrays) for k in keys]}
    k = f"a{len(arrays)}"
    arrays[k] = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    return {"t": "pkl", "k": k}


def _snap_decode(spec: dict, arrays: Dict[str, np.ndarray]):
    t = spec["t"]
    if t == "none":
        return None
    if t == "v":
        return spec["v"]
    if t == "arr":
        return np.asarray(arrays[spec["k"]])
    if t == "pkl":
        return pickle.loads(bytes(np.asarray(arrays[spec["k"]])))
    if t == "nt":
        cls = _snap_class(spec["c"])
        return cls(*[_snap_decode(x, arrays) for x in spec["items"]])
    if t == "tuple":
        return tuple(_snap_decode(x, arrays) for x in spec["items"])
    if t == "list":
        return [_snap_decode(x, arrays) for x in spec["items"]]
    if t == "dict":
        return {k: _snap_decode(x, arrays)
                for k, x in zip(spec["keys"], spec["items"])}
    raise ValueError(f"unknown snapshot node type {t!r}")


_KWT_REFUSED = {
    "chip_offsets": "chip offsets model the IMC macro",
    "sa_noise_std": "SA noise models the IMC macro (sa_noise_std > 0)",
    "use_kernel": "the fused IMC kernel runs the folded net only "
                  "(use_kernel=False)",
    "streaming": "the frame ring is KWT's one engine (streaming=False)",
    "vad": "VAD gating shifts the folded net's silence columns",
    "dynamic_hop": "a hop retarget re-inits the folded net's geometry",
    "faults": "fault injection rides the IMC bias-delta riders",
    "health": "canaries localize IMC layer faults",
    "profiles": "profiles customize the folded net",
    "obs": "the launch auditor counts IMC kernel launches and the flight "
           "recorder charges the IMC energy model",
}


def _refuse_kwt_options(**given) -> None:
    """``ValueError`` naming every option a KWT server cannot take."""
    bad = [f"{k} ({_KWT_REFUSED[k]})" for k, on in given.items() if on]
    if bad:
        raise ValueError("a KWTConfig server cannot take: " + "; ".join(bad))


class StreamServer:
    """Admit / batch / gate / decide / evict over an autoscaling slot pool."""

    # Every counter lives in the metrics registry (repro.obs.metrics); the
    # historical attribute API (``srv._steps += 1`` and external readers
    # like the concurrent-session bench's per-tick call deltas) is kept by
    # registry-backed properties.  snapshot()/restore() round-trip the
    # whole registry, so there is no hand-maintained key list to drift.
    _steps = counter_property("serving.steps")
    _hop_wall_s = counter_property("serving.hop_wall_s")
    _decisions = counter_property("serving.decisions")
    _speech_hops = counter_property("serving.hops", kind="speech")
    _gated_hops = counter_property("serving.hops", kind="gated")
    _learn_hops = counter_property("serving.hops", kind="learn")
    _rejected = counter_property("serving.rejected_streams")
    _shed_events = counter_property("serving.shed", what="events")
    _shed_samples = counter_property("serving.shed", what="samples")
    _calm_ticks = counter_property("serving.dynhop.calm_ticks")
    _pressure_ticks = counter_property("serving.autoscale.pressure_ticks")
    _idle_ticks = counter_property("serving.autoscale.idle_ticks")
    _hop_retargets = counter_property("serving.hop_retargets")
    _init_calls = counter_property("serving.batched_calls", cause="init")
    _hop_calls = counter_property("serving.batched_calls", cause="hop")
    _replay_calls = counter_property("serving.batched_calls",
                                     cause="replay")
    _gate_calls = counter_property("serving.batched_calls", cause="gate")
    _profile_swaps = counter_property("serving.profile_swaps")
    # compiled fast-path accounting (repro.serving.compiled): dispatch
    # counts only — every Python-tick counter above is replayed exactly,
    # so these are the ONLY registry keys that differ between a compiled
    # and an interpreted run (tests/_equiv.py excludes them)
    _compiled_blocks = counter_property("serving.compiled", what="blocks")
    _compiled_ticks = counter_property("serving.compiled", what="ticks")
    # MFCC frames computed (KWT only): a window's frames per init, the
    # hop's fresh frames per computed hop — the frame ring's evidence
    _mfcc_frames = counter_property("serving.mfcc_frames")

    def __init__(self, hw, cfg: kws.KWSConfig | kwt.KWTConfig, *, hop: int,
                 slots: int = 4,
                 chip_offsets: Optional[Dict[str, jax.Array]] = None,
                 sa_noise_std: float = 0.0, use_kernel: bool = True,
                 streaming: bool = True,
                 decision: dec.DecisionConfig = dec.DecisionConfig(),
                 vad: Optional[vd.VADConfig] = None,
                 dynamic_hop: Optional[DynamicHopConfig] = None,
                 admission: Optional[AdmissionConfig] = None,
                 batch_init: bool = True,
                 faults=None, health=None, profiles=None,
                 silence_fill: str = "constant",
                 obs: Optional[ObsConfig] = None,
                 device_label: Optional[int] = None,
                 compiled=None,
                 seed: int = 0):
        # the registry backs every counter attribute — create it before
        # the first counter write below
        self._metrics = MetricsRegistry()
        self.obs = obs if obs is not None else ObsConfig.from_env()
        # a KWTConfig serves the Keyword Transformer (repro.serving.
        # kwt_stream): no IMC macro, so every option of the folded net's
        # silicon, gating and learning is refused rather than ignored
        self._kwt = isinstance(cfg, kwt.KWTConfig)
        if self._kwt:
            _refuse_kwt_options(
                chip_offsets=chip_offsets is not None,
                sa_noise_std=sa_noise_std > 0.0, use_kernel=use_kernel,
                streaming=not streaming, vad=vad is not None,
                dynamic_hop=dynamic_hop is not None,
                faults=faults is not None, health=health is not None,
                profiles=profiles is not None,
                obs=self.obs.audit != "off" or self.obs.recorder > 0)
        # ``device_label`` names this server's device pool in a sharded
        # deployment (repro.serving.shard): the launch auditor and fleet
        # stats rollups attribute per-device launches through it
        self.device_label = device_label
        self._rec = (FlightRecorder(self.obs.recorder)
                     if self.obs.recorder else None)
        self._audit = (LaunchAuditor(cfg.num_conv_layers - 1,
                                     mode=self.obs.audit,
                                     batch_init=batch_init,
                                     device=device_label)
                       if self.obs.audit != "off" else None)
        # serving.step's span arguments beyond ticks/slots (a sharded
        # pool names its device)
        self._span_args = ({} if device_label is None
                           else {"device": device_label})
        compiles.install()
        self._uj_consts: Dict[int, tuple] = {}   # mult -> (speech, gated)
        self.cfg = cfg
        self.streaming = streaming
        self.base_hop = hop
        self.batch_init = batch_init
        self.dcfg = decision
        self.vcfg = vad
        self.hcfg = dynamic_hop
        self.acfg = admission
        self._hw = hw
        self._engine_kw = dict(chip_offsets=chip_offsets,
                               sa_noise_std=sa_noise_std,
                               use_kernel=use_kernel, streaming=streaming)
        self.min_slots = slots
        self.max_slots = slots
        if admission is not None:
            if admission.min_slots is not None:
                self.min_slots = admission.min_slots
            if admission.max_slots is not None:
                self.max_slots = admission.max_slots
            if not (1 <= self.min_slots <= slots <= self.max_slots):
                raise ValueError(
                    f"need 1 <= min_slots ({self.min_slots}) <= slots "
                    f"({slots}) <= max_slots ({self.max_slots})")
        self.slots = slots

        if silence_fill not in ("constant", "retention"):
            raise ValueError(f"silence_fill={silence_fill!r}: use "
                             f"'constant' or 'retention'")
        self.silence_fill = silence_fill
        self._fills = None
        if vad is not None and streaming:
            if silence_fill == "retention":
                # chip-accurate gated fill: hold one *noisy* SA read per
                # column (what the retained array actually latched) instead
                # of the noiseless silence response
                self._fills = sv.retention_fills(
                    hw, cfg,
                    key=jax.random.fold_in(jax.random.PRNGKey(seed), 0x517),
                    sa_noise_std=sa_noise_std, chip_offsets=chip_offsets)
            else:
                sils = kws.silence_columns(hw, cfg,
                                           chip_offsets=chip_offsets)
                self._fills = sv.silence_fills(cfg, sils)

        # customization (repro.serving.customize): once enabled, batched
        # hops route through the per-slot (bias delta, FC head) variant so
        # hot-swapped and learning slots share the one-launch-per-layer
        # batch with everyone else
        self._cust = None                 # CustomizationManager
        self._cust_on = False
        self._slot_delta = None           # {conv_i: (slots, C_i)}
        self._slot_head_w = None          # (slots, D, num_classes)
        self._slot_head_b = None          # (slots, num_classes)
        self._slot_fills = None           # per-layer (slots, C_i) if VAD

        self._mult = 1
        self._mults: Dict[int, dict] = {}
        bundle = self._bundle(1)
        self._state = bundle["engine"].zeros_state(slots)
        self._dstate = dec.decision_init(slots, cfg.num_classes, decision)
        self._vstate = vd.vad_init(slots) if vad is not None else None
        self._slots: List[Optional[_Stream]] = [None] * slots
        self._queue: collections.deque[_Stream] = collections.deque()
        self._streams: Dict[str, _Stream] = {}
        self._base_key = jax.random.PRNGKey(seed)
        self._uid = 0
        self._steps = 0
        self._hop_wall_s = 0.0
        self._decisions = 0
        self._speech_hops = 0
        self._gated_hops = 0
        self._learn_hops = 0
        self._rejected = 0
        self._shed_events = 0
        self._shed_samples = 0
        self._calm_ticks = 0
        self._pressure_ticks = 0
        self._idle_ticks = 0
        self._hop_retargets = 0
        # batched-compute accounting: each counter is one batched jax call
        # = one fused-kernel launch per IMC layer (however many slots /
        # sessions ride it) — zero IMC launches for gate calls.  The
        # concurrent-session bench derives its one-launch-per-layer-per-
        # tick assertion from per-tick deltas of these.
        self._init_calls = 0               # batched stream_init waves
        self._hop_calls = 0                # batched single-hop calls
        self._replay_calls = 0             # multi-hop wake-replay calls
        self._gate_calls = 0               # masked no-op fill calls
        self._compiled_blocks = 0
        self._compiled_ticks = 0
        if self._kwt:
            self._mfcc_frames = 0

        # compiled whole-tick fast path (repro.serving.compiled):
        # ``compiled=True`` (defaults) or a CompiledTickConfig turns
        # steady-state ticks into single-dispatch blocks — ``step()``
        # serves one-tick blocks, ``step_block()`` up to ``block`` ticks
        # per dispatch; any tick the block cannot model exactly falls back
        # to the interpreted path, bit-identically.  Imported lazily
        # (compiled.py imports _select_state from this module).
        self._compiled = None
        if compiled:
            from repro.serving.compiled import (CompiledTick,
                                                CompiledTickConfig)
            ccfg = (compiled if isinstance(compiled, CompiledTickConfig)
                    else CompiledTickConfig())
            self._compiled = CompiledTick(self, ccfg)

        self._decide = jax.jit(
            lambda dstate, logits, active: dec.decision_step(
                self.dcfg, dstate, logits, active))
        self._scatter = jax.jit(_scatter_slot)
        if vad is not None:
            vcfg = vad
            self._vad_fn = jax.jit(
                lambda vs, audio, active: vd.vad_step(vcfg, vs, audio,
                                                      active))

        # -- robustness: faults, health monitoring, profile store ----------
        self._profiles = profiles              # ProfileStore or None
        self._profile_swaps = 0
        self._heal_delta = None                # {conv_i: np (C_i,)} healing
        #                                        bias correction (counts)
        self._chip_delta_j = None              # cached jnp fault+heal sum
        self._faults = None
        if faults is not None:
            from repro.core import faults as flt
            self._faults = (faults if isinstance(faults, flt.FaultModel)
                            else flt.FaultModel.for_config(cfg, faults))
            # route every batched call through the rider variant up front
            # so fault deltas can hot-swap in without a mid-run mode flip
            self._enable_customization()
            if self._faults.pop_dirty():
                self._refresh_chip_delta()
        self._health = None
        if health is not None:
            from repro.serving import health as hl
            self._health = hl.HealthMonitor(self, health)

    # -- hop-multiplier engine table ----------------------------------------

    def _bundle(self, mult: int) -> dict:
        """Engine + jitted masked hop/gate functions for one hop multiple."""
        if mult not in self._mults:
            if self._kwt:
                eng = kwt_stream.KWTEngine(self._hw, self.cfg,
                                           self.base_hop * mult)
            else:
                eng = sv.StreamEngine(self._hw, self.cfg,
                                      self.base_hop * mult,
                                      **self._engine_kw)

            def hop_masked(state, audio, mask, _step=eng._step):
                logits, new_state = _step(state, audio)
                return logits, _select_state(mask, new_state, state)

            # masked batched init: a whole admission wave — live streams
            # and session replay streams alike — runs its first full
            # window in ONE stream_init call (one fused launch per IMC
            # layer for the wave) instead of a B=1 launch per admission;
            # rows not in the mask keep their state verbatim
            def init_masked(state, windows, keys, mask, _init=eng._init):
                logits, new_state = _init(windows, keys)
                return logits, _select_state(mask, new_state, state)

            if self._kwt:      # no riders, gating or replays to build
                self._mults[mult] = {"engine": eng,
                                     "hop": jax.jit(hop_masked),
                                     "init": jax.jit(init_masked)}
                return self._mults[mult]

            step_fn = sv.stream_step if self.streaming else sv.window_step
            init_fn = sv.stream_init if self.streaming else sv.window_init

            def init_cust_masked(state, windows, keys, mask, deltas, hw_,
                                 hb_, _kw=eng._kw, _geom=eng.geom):
                logits, new_state = init_fn(self._hw, windows, keys,
                                            self.cfg, _geom, **_kw,
                                            bias_delta=deltas, head_w=hw_,
                                            head_b=hb_)
                return logits, _select_state(mask, new_state, state)

            def hop_cust_masked(state, audio, mask, deltas, hw_, hb_,
                                _kw=eng._kw, _geom=eng.geom):
                logits, new_state = step_fn(self._hw, state, audio, self.cfg,
                                            _geom, **_kw, bias_delta=deltas,
                                            head_w=hw_, head_b=hb_)
                return logits, _select_state(mask, new_state, state)

            if self.streaming:
                def gate_masked(state, mask, _geom=eng.geom):
                    new = sv.gated_step(state, self.cfg, _geom, self._fills)
                    return _select_state(mask, new, state)

                def gate_cust_masked(state, mask, fills, _geom=eng.geom):
                    new = sv.gated_step(state, self.cfg, _geom, fills)
                    return _select_state(mask, new, state)
            else:
                def gate_masked(state, mask, _geom=eng.geom):
                    new = sv.gated_window_step(state, _geom)
                    return _select_state(mask, new, state)

                def gate_cust_masked(state, mask, fills, _geom=eng.geom):
                    new = sv.gated_window_step(state, _geom)
                    return _select_state(mask, new, state)

            self._mults[mult] = {"engine": eng, "hop": jax.jit(hop_masked),
                                 "hop_cust": jax.jit(hop_cust_masked),
                                 "init": jax.jit(init_masked),
                                 "init_cust": jax.jit(init_cust_masked),
                                 "gate": jax.jit(gate_masked),
                                 "gate_cust": jax.jit(gate_cust_masked),
                                 "replay": {}, "replay_cust": {}}
        return self._mults[mult]

    def _replay_fn(self, bundle: dict, n_hops: int, cust: bool):
        """Masked multi-hop replay for one deferred-run length: ONE fused
        launch per IMC layer for the whole n-hop run (streaming mode; the
        recompute fallback loops internally) instead of one launch per
        deferred hop.  Jitted per (hop-multiple, n_hops, cust)."""
        cache = bundle["replay_cust" if cust else "replay"]
        if n_hops not in cache:
            eng = bundle["engine"]
            multi_fn = (sv.stream_multi_step if self.streaming
                        else sv.window_multi_step)
            if cust:
                def replay(state, audio, mask, deltas, hw_, hb_,
                           _kw=eng._kw, _geom=eng.geom):
                    logits, new_state = multi_fn(
                        self._hw, state, audio, self.cfg, _geom, n_hops,
                        **_kw, bias_delta=deltas, head_w=hw_, head_b=hb_)
                    return logits, _select_state(mask, new_state, state)
            else:
                def replay(state, audio, mask, _kw=eng._kw, _geom=eng.geom):
                    logits, new_state = multi_fn(self._hw, state, audio,
                                                 self.cfg, _geom, n_hops,
                                                 **_kw)
                    return logits, _select_state(mask, new_state, state)
            cache[n_hops] = jax.jit(replay)
        return cache[n_hops]

    @property
    def engine(self):
        """The ``StreamEngine`` (or, for a KWTConfig, the
        ``kwt_stream.KWTEngine``) at the current hop."""
        return self._bundle(self._mult)["engine"]

    @property
    def geom(self):
        return self.engine.geom

    @property
    def hop(self) -> int:
        """Current effective hop (base_hop x dynamic multiplier)."""
        return self.base_hop * self._mult

    @property
    def hop_multiplier(self) -> int:
        return self._mult

    # -- observability helpers ----------------------------------------------

    @property
    def metrics(self) -> MetricsRegistry:
        """The server's metrics registry (always on: it backs stats())."""
        return self._metrics

    @property
    def recorder(self):
        """The flight recorder (None unless ``obs.recorder > 0``)."""
        return self._rec

    @property
    def auditor(self):
        """The launch auditor (None unless ``obs.audit != 'off'``)."""
        return self._audit

    def _region(self, cause: str):
        """Launch-auditor region around one batched call site (no-op
        context when the auditor is off)."""
        if self._audit is None:
            return contextlib.nullcontext()
        return self._audit.region(cause)

    def _tick_uj(self, computed: int, gated: int) -> float:
        """Analytical uJ for one tick's hop composition, from constants
        precomputed per hop-multiplier (computed hops are charged the
        full ungated per-decision energy, gated fills leakage only)."""
        consts = self._uj_consts.get(self._mult)
        if consts is None:
            offline = kws.layer_stats(self.cfg)
            streaming = sv.streaming_layer_stats(self.cfg, self.geom)
            g = energy.gated_energy_summary(offline, streaming,
                                            hop_samples=self.hop,
                                            duty_cycle=1.0)
            consts = (g["ungated_uj_per_decision"], g["idle_uj_per_hop"])
            self._uj_consts[self._mult] = consts
        return computed * consts[0] + gated * consts[1]

    # -- customization: per-slot riders + session manager -------------------

    def _base_head(self):
        hwp, _ = kws.as_hw_params(self._hw)
        return hwp.fc_w, hwp.fc_b

    def _refuse_customization(self) -> None:
        if self._kwt:
            raise ValueError("a KWTConfig server cannot take customization "
                             "(it refolds the IMC net's biases and head)")

    def _enable_customization(self) -> None:
        """Materialize the per-slot customization arrays (zero bias deltas,
        the base FC head in every row) and route batched hops through the
        per-slot variant from now on.  Rows with base values are bit-exact
        no-ops, so uncustomized slots are unaffected."""
        if self._cust_on:
            return
        self._cust_on = True
        n = self.slots
        cfg = self.cfg
        fw, fb = self._base_head()
        self._slot_delta = {
            f"conv{i}": jnp.zeros((n, cfg.channels[i]))
            for i in range(1, cfg.num_conv_layers)}
        self._slot_head_w = jnp.broadcast_to(fw, (n,) + fw.shape)
        self._slot_head_b = jnp.broadcast_to(fb, (n,) + fb.shape)
        if self._fills is not None:
            self._slot_fills = tuple(
                jnp.broadcast_to(f, (n,) + f.shape) for f in self._fills)
        for s, rec in enumerate(self._slots):
            if rec is not None and rec.custom is not None:
                self._write_slot_custom(s, rec.custom)

    def _write_slot_custom(self, s: int, custom: Optional[dict]) -> None:
        """Sync slot ``s``'s rider rows with a stream's customization
        (``None`` resets to base).  Called on admission, eviction and
        hot-swap — the swap touches only row ``s``, other slots' rows (and
        their carries/rings) are untouched."""
        if not self._cust_on:
            return
        fw, fb = self._base_head()
        if custom is None:
            for name in self._slot_delta:
                self._slot_delta[name] = self._slot_delta[name].at[s].set(0.0)
            self._slot_head_w = self._slot_head_w.at[s].set(fw)
            self._slot_head_b = self._slot_head_b.at[s].set(fb)
            if self._slot_fills is not None:
                self._slot_fills = tuple(
                    t.at[s].set(f) for t, f in zip(self._slot_fills,
                                                   self._fills))
            return
        for name in self._slot_delta:
            self._slot_delta[name] = self._slot_delta[name].at[s].set(
                jnp.asarray(custom["delta"][name]))
        self._slot_head_w = self._slot_head_w.at[s].set(
            jnp.asarray(custom["head"][0]))
        self._slot_head_b = self._slot_head_b.at[s].set(
            jnp.asarray(custom["head"][1]))
        if self._slot_fills is not None and custom.get("fills") is not None:
            self._slot_fills = tuple(
                t.at[s].set(jnp.asarray(f))
                for t, f in zip(self._slot_fills, custom["fills"]))

    def _slot_custom_args(self):
        delta = self._slot_delta
        chip = self._chip_delta_j
        if chip is not None:
            # the chip-global fault+heal offset rides every slot's existing
            # bias-delta row — same operands, same launches: injection and
            # healing are free at serve time
            delta = {k: v + chip[k][None] for k, v in delta.items()}
        return (delta, self._slot_head_w, self._slot_head_b)

    def _row_custom(self, rec: "_Stream"):
        """Rider args for the sequential B=1 init paths (``batch_init``
        off, hop-retarget re-inits), combining the stream's own
        customization with the chip-global fault/heal delta.  None when
        neither applies (base init path)."""
        chip = self._chip_delta_j
        if not self._cust_on or (rec.custom is None and chip is None):
            return None
        cfg = self.cfg
        if rec.custom is not None:
            delta = {name: jnp.asarray(rec.custom["delta"][name])
                     for name in cfg.imc_layer_names()}
            hw1, hb1 = (jnp.asarray(rec.custom["head"][0]),
                        jnp.asarray(rec.custom["head"][1]))
        else:
            delta = {name: jnp.zeros((cfg.channels[int(name[4:])],))
                     for name in cfg.imc_layer_names()}
            hw1, hb1 = self._base_head()
        if chip is not None:
            delta = {k: v + chip[k] for k, v in delta.items()}
        return ({k: v[None] for k, v in delta.items()},
                hw1[None], hb1[None])

    # -- fault injection + self-healing -------------------------------------

    @property
    def faults(self):
        """The live FaultModel (None unless constructed with ``faults=``).
        Inject through it between ticks — the next ``step()`` notices the
        dirty flag and refreshes the rider operands."""
        return self._faults

    @property
    def health(self):
        """The HealthMonitor (None unless constructed with ``health=``)."""
        return self._health

    def _refresh_chip_delta(self) -> None:
        """Rebuild the cached chip-global per-layer count delta = injected
        faults + healing correction.  None when the chip is pristine and
        unhealed, which keeps the rider rows bit-exact base values."""
        fault = (self._faults.deltas()
                 if self._faults is not None and self._faults.active
                 else None)
        if fault is None and self._heal_delta is None:
            self._chip_delta_j = None
            return
        out = {}
        for name in self.cfg.imc_layer_names():
            v = np.zeros((self.cfg.channels[int(name[4:])],), np.float32)
            if fault is not None:
                v = v + fault[name]
            if self._heal_delta is not None and name in self._heal_delta:
                v = v + self._heal_delta[name]
            out[name] = jnp.asarray(v)
        self._chip_delta_j = out

    def _set_heal_delta(self, heal: Dict[str, np.ndarray]) -> None:
        """Hot-swap a healing bias correction (per-layer pre-sign count
        deltas, from the health monitor's background recompensation) into
        every batched launch.  Entries replace any previous heal for the
        same layer — recoveries are recomputed from the pristine stored
        bias, so repeated heals never stack."""
        self._enable_customization()
        cur = dict(self._heal_delta or {})
        cur.update({k: np.asarray(v, np.float32) for k, v in heal.items()})
        self._heal_delta = cur
        self._refresh_chip_delta()

    def customize(self, stream_id: str, ccfg=None):
        """Open an enrollment/fine-tuning session attached to a live
        stream (created empty if absent): labeled utterances submitted via
        ``session.enroll`` ride the stream's normal batched hops, then the
        paper's on-chip loop (bias compensation -> error-scaled + SGA
        fine-tune) runs as bounded background jobs inside ``step()``.  See
        repro.serving.customize.  Returns the CustomizationSession."""
        from repro.serving import customize as cz
        self._refuse_customization()
        if self.hcfg is not None:
            raise ValueError("customization requires a fixed hop "
                             "(dynamic_hop retargets would break the "
                             "enrollment capture alignment)")
        if self._cust is None:
            self._cust = cz.CustomizationManager(self)
        self._enable_customization()
        return self._cust.start(stream_id, ccfg)

    def install_custom(self, stream_id: str, result) -> None:
        """Hot-swap a finished customization (a CustomizationResult — e.g.
        a persisted user profile) into a stream: its slot's bias-delta /
        FC-head / silence-fill rows are reprogrammed in place; every other
        slot's rows and states are untouched.  The stream is created
        (empty) if it does not exist yet, so a profile can be installed
        before its first audio arrives."""
        from repro.serving import customize as cz
        self._refuse_customization()
        self._enable_customization()
        rec = self._streams.get(stream_id)
        if rec is None:
            rec = _Stream(stream_id=stream_id, uid=self._uid,
                          buf=np.zeros((0,), np.float32))
            self._uid += 1
            self._streams[stream_id] = rec
            self._queue.append(rec)
            self._try_admit()
        rec.custom = cz.result_riders(result, self._hw, self.cfg,
                                      chip_offsets=self._engine_kw
                                      ["chip_offsets"],
                                      with_fills=self._fills is not None)
        if rec.slot is not None:
            self._write_slot_custom(rec.slot, rec.custom)

    def _submit_internal(self, stream_id: str, wav: np.ndarray,
                         custom: Optional[dict] = None,
                         uid: Optional[int] = None) -> "_Stream":
        """Enqueue a session-owned replay stream: rides the normal slot
        machinery and the SAME batched launches, but emits no decision
        events, bypasses the admission-queue bound and is exempt from SLO
        shedding.  Finished on arrival — it retires as soon as its audio
        drains (the session captures its features first).  ``uid`` pins
        the stream's noise-field key to a reserved uid (health canaries
        reuse one key so every canary sees the identical field)."""
        rec = _Stream(stream_id=stream_id,
                      uid=self._uid if uid is None else uid,
                      buf=np.asarray(wav, np.float32), internal=True,
                      force_compute=True, custom=custom, finished=True)
        if uid is None:
            self._uid += 1
        self._streams[stream_id] = rec
        self._queue.append(rec)
        self._try_admit()
        return rec

    def _drop_internal(self, stream_id: str) -> None:
        rec = self._streams.pop(stream_id, None)
        if rec is None:
            return
        rec.finished = True
        rec.buf = rec.buf[:0]
        rec.pending = []
        if rec.slot is not None:
            self._free_slot(rec)
        elif rec in self._queue:
            self._queue.remove(rec)

    # -- stream lifecycle ---------------------------------------------------

    def submit(self, stream_id: str, chunk: np.ndarray,
               user_id: Optional[str] = None,
               uid: Optional[int] = None) -> str:
        """Append audio to a stream (created on first submit).  Returns the
        stream's placement: 'slot' (live), 'queued' (awaiting a slot) or
        'rejected' (admission queue full — nothing was buffered; the
        caller may retry later).

        ``user_id`` (needs ``profiles=`` at construction) associates the
        stream with a profile-store user: their stored customization is
        auto-installed onto whichever slot the stream lands on, and the
        per-tick staleness sweep re-installs it if the store's copy
        changes (or resets to base if it is deleted).

        ``uid`` pins the stream's noise-field identity instead of drawing
        from this server's counter — the sharded router
        (repro.serving.shard) assigns GLOBAL uids in submission order so
        a stream's per-absolute-column SA-noise field is the same no
        matter which device pool it lands on (and identical to what a
        single-device server would have drawn).  The local counter jumps
        past any pinned uid so internally spawned streams (canaries,
        session replays) never collide with routed ones."""
        rec = self._streams.get(stream_id)
        if rec is None:
            if (self.acfg is not None and self.acfg.max_queue is not None
                    and all(r is not None for r in self._slots)
                    and len(self._queue) >= self.acfg.max_queue):
                self._rejected += 1
                if self._rec is not None:
                    self._rec.record(self._steps, "reject",
                                     stream=stream_id)
                return "rejected"
            rec = _Stream(stream_id=stream_id,
                          uid=self._uid if uid is None else int(uid),
                          buf=np.zeros((0,), np.float32))
            self._uid = (self._uid + 1 if uid is None
                         else max(self._uid, int(uid) + 1))
            self._streams[stream_id] = rec
            self._queue.append(rec)
            self._try_admit()
        if rec.finished:
            raise ValueError(f"stream {stream_id} already finished")
        if user_id is not None and user_id != rec.user_id:
            if self._profiles is None:
                raise ValueError("submit(user_id=...) needs a profile "
                                 "store: construct with profiles=")
            self._attach_profile(rec, user_id)
        rec.buf = np.concatenate([rec.buf, np.asarray(chunk, np.float32)])
        return "slot" if rec.slot is not None else "queued"

    # -- profile store: auto-install + staleness sweep ----------------------

    def _attach_profile(self, rec: "_Stream", user_id: str) -> None:
        """Associate ``rec`` with a store user and install their profile
        if one exists.  A user with no stored profile serves the base
        model but stays associated — a later enrollment save is picked up
        by the staleness sweep."""
        rec.user_id = user_id
        rec.profile_mtime = None
        if self._profiles.mtime(user_id) is not None:
            self._install_profile(rec)

    def _install_profile(self, rec: "_Stream") -> None:
        """(Re)load ``rec.user_id``'s stored profile and program its rider
        rows.  The mtime is read *before* the load: if the file is
        replaced mid-install the recorded stamp is stale and the next
        sweep simply reinstalls."""
        from repro.serving import customize as cz
        rec.profile_mtime = self._profiles.mtime(rec.user_id)
        result = self._profiles.load(rec.user_id)
        self._enable_customization()
        rec.custom = cz.result_riders(result, self._hw, self.cfg,
                                      chip_offsets=self._engine_kw
                                      ["chip_offsets"],
                                      with_fills=self._fills is not None)
        if rec.slot is not None:
            self._write_slot_custom(rec.slot, rec.custom)

    def _reset_profile(self, rec: "_Stream") -> None:
        rec.custom = None
        rec.profile_mtime = None
        if rec.slot is not None:
            self._write_slot_custom(rec.slot, None)

    def _check_profiles(self) -> None:
        """Stale-profile eviction (once per tick): any live stream whose
        stored profile changed under it (``st_mtime_ns`` moved — every
        ``ProfileStore.save`` is a fresh inode) is re-installed from the
        fresh file; a stream whose profile was deleted drops back to the
        base model."""
        if self._profiles is None:
            return
        for rec in self._streams.values():
            if rec.user_id is None:
                continue
            m = self._profiles.mtime(rec.user_id)
            if m == rec.profile_mtime:
                continue
            self._profile_swaps += 1
            if m is None:
                self._reset_profile(rec)
            else:
                try:
                    self._install_profile(rec)
                except FileNotFoundError:  # deleted between stat and load
                    self._reset_profile(rec)

    def finish(self, stream_id: str) -> None:
        """Producer signals end-of-stream: the slot is freed once the
        buffered audio drains below one hop."""
        self._streams[stream_id].finished = True

    def evict(self, stream_id: str) -> None:
        """Drop a stream immediately, freeing its slot."""
        rec = self._streams[stream_id]
        rec.finished = True
        rec.buf = rec.buf[:0]
        rec.pending = []
        if rec.slot is not None:
            self._free_slot(rec)
        elif rec in self._queue:
            self._queue.remove(rec)

    def _free_slot(self, rec: _Stream) -> None:
        s = rec.slot
        self._slots[s] = None
        rec.slot = None
        self._write_slot_custom(s, None)
        if self._rec is not None:
            self._rec.record(self._steps, "evict", stream=rec.stream_id,
                             slot=s, internal=rec.internal)
        self._try_admit()

    def _try_admit(self) -> None:
        for s in range(self.slots):
            if self._slots[s] is None and self._queue:
                rec = self._queue.popleft()
                rec.slot = s
                rec.initialized = False
                self._slots[s] = rec
                self._write_slot_custom(s, rec.custom)

    # -- backpressure: latency SLO shedding + slot autoscaling --------------

    def _enforce_slo(self) -> None:
        """Shed streams whose buffered backlog exceeds the latency SLO:
        drop the oldest audio down to the low-water mark (half the SLO,
        never below one window) and re-initialize from the freshest
        window.  Continuity across the cut is gone anyway, so the state is
        rebuilt rather than fed stale audio late."""
        if self.acfg is None or self.acfg.max_lag_s is None:
            return
        max_lag = int(self.acfg.max_lag_s * self.cfg.sample_rate)
        keep = max(self.geom.window, max_lag // 2)
        for rec in self._streams.values():
            if rec.finished or rec.internal or rec.force_compute:
                # learning work is exempt: shedding an enrollment utterance
                # would silently corrupt the captured feature buffer
                continue
            backlog = sum(map(len, rec.pending)) + len(rec.buf)
            if backlog <= max_lag:
                continue
            total = (np.concatenate(rec.pending + [rec.buf])
                     if rec.pending else rec.buf)
            dropped = backlog - keep
            rec.buf = total[-keep:]
            rec.pending = []
            rec.silent_run = 0
            rec.initialized = False
            rec.sheds += 1
            rec.shed_samples += dropped
            self._shed_events += 1
            self._shed_samples += dropped
            if self._rec is not None:
                self._rec.record(self._steps, "shed",
                                 stream=rec.stream_id, samples=dropped)

    def _autoscale(self) -> None:
        if self.acfg is None or self.max_slots <= self.min_slots:
            return
        if self._queue and self.slots < self.max_slots:
            self._idle_ticks = 0
            self._pressure_ticks += 1
            if self._pressure_ticks >= self.acfg.scale_up_after:
                self._resize(min(self.max_slots,
                                 self.slots + len(self._queue)))
                self._pressure_ticks = 0
            return
        self._pressure_ticks = 0
        free_tail = 0
        for rec in reversed(self._slots):
            if rec is None:
                free_tail += 1
            else:
                break
        if free_tail and not self._queue and self.slots > self.min_slots:
            self._idle_ticks += 1
            if self._idle_ticks >= self.acfg.scale_down_after:
                self._resize(max(self.min_slots, self.slots - free_tail))
                self._idle_ticks = 0
        else:
            self._idle_ticks = 0

    def _resize(self, n: int) -> None:
        """Grow (append zero rows) or shrink (crop trailing free slots) the
        batched state pytrees.  Jitted functions re-trace on the new batch
        shape automatically."""
        if n == self.slots:
            return
        if n > self.slots:
            grow = n - self.slots

            def pad(a):
                return jnp.concatenate(
                    [a, jnp.zeros((grow,) + a.shape[1:], a.dtype)])

            def pad_rows(a, row):
                return jnp.concatenate(
                    [a, jnp.broadcast_to(row, (grow,) + row.shape)])

            self._state = jax.tree_util.tree_map(pad, self._state)
            self._dstate = jax.tree_util.tree_map(pad, self._dstate)
            if self._vstate is not None:
                self._vstate = jax.tree_util.tree_map(pad, self._vstate)
            if self._cust_on:
                fw, fb = self._base_head()
                self._slot_delta = {k: pad(v)
                                    for k, v in self._slot_delta.items()}
                self._slot_head_w = pad_rows(self._slot_head_w, fw)
                self._slot_head_b = pad_rows(self._slot_head_b, fb)
                if self._slot_fills is not None:
                    self._slot_fills = tuple(
                        pad_rows(t, f) for t, f in zip(self._slot_fills,
                                                       self._fills))
            self._slots.extend([None] * grow)
        else:
            assert all(r is None for r in self._slots[n:]), \
                "only trailing free slots can be cropped"
            self._state = jax.tree_util.tree_map(lambda a: a[:n],
                                                 self._state)
            self._dstate = jax.tree_util.tree_map(lambda a: a[:n],
                                                  self._dstate)
            if self._vstate is not None:
                self._vstate = jax.tree_util.tree_map(lambda a: a[:n],
                                                      self._vstate)
            if self._cust_on:
                self._slot_delta = {k: v[:n]
                                    for k, v in self._slot_delta.items()}
                self._slot_head_w = self._slot_head_w[:n]
                self._slot_head_b = self._slot_head_b[:n]
                if self._slot_fills is not None:
                    self._slot_fills = tuple(t[:n]
                                             for t in self._slot_fills)
            self._slots = self._slots[:n]
        self.slots = n
        self._try_admit()

    # -- dynamic hop --------------------------------------------------------

    def _feasible_mult(self, mult: int) -> bool:
        try:
            sv.make_stream_geometry(self.cfg, self.base_hop * mult)
            return True
        except ValueError:
            return False

    def _set_mult(self, mult: int) -> None:
        """Retarget the effective hop.  The streaming geometry (carry
        sizes, fresh-column counts) is hop-dependent, so every live slot's
        ``StreamState`` is rebuilt from its retained last window of
        consumed audio via ``stream_init`` on the new-hop engine; deferred
        silent hops are pushed back into the buffer for re-consumption at
        the new hop size.  With SA noise enabled the rebuilt stream's
        noise field restarts at window 0 (a re-init is a fresh programming
        of the array), so the bit-exactness contract is scoped to a fixed
        hop."""
        if mult == self._mult:
            return
        bundle = self._bundle(mult)
        eng = bundle["engine"]
        window = self.geom.window
        new_state = eng.zeros_state(self.slots)
        for s, rec in enumerate(self._slots):
            if rec is None or not rec.initialized:
                continue
            if rec.pending:
                rec.buf = np.concatenate(rec.pending + [rec.buf])
                rec.pending = []
            rec.silent_run = 0
            if len(rec.recent) >= window:
                key = jax.random.fold_in(self._base_key, rec.uid)[None]
                t0 = time.perf_counter()
                d1 = self._row_custom(rec)
                if d1 is not None:
                    _, one = eng.init_custom(
                        jnp.asarray(rec.recent[None, -window:]), key, *d1)
                else:
                    _, one = eng.init(
                        jnp.asarray(rec.recent[None, -window:]), key)
                new_state = self._scatter(new_state, one, s)
                dt = time.perf_counter() - t0
                rec.wall_s += dt
                self._hop_wall_s += dt
            else:
                rec.initialized = False     # re-admit from the buffer
        self._state = new_state
        self._mult = mult
        self._hop_retargets += 1
        if self._rec is not None:
            self._rec.record(self._steps, "hop_retarget", mult=mult,
                             hop=self.base_hop * mult)

    def _retarget_hop(self, events: List[dict], woke: bool,
                      silent: bool = False) -> None:
        if self.hcfg is None:
            return
        max_score = max((e["score"] for e in events), default=0.0)
        if woke or max_score >= self.hcfg.calm_score:
            self._calm_ticks = 0
            if self._mult != 1:
                self._set_mult(1)
            return
        self._calm_ticks += 1
        after = self.hcfg.widen_after
        if silent and self.hcfg.calm_silence is not None:
            after = self.hcfg.calm_silence   # duty-aware: silence widens
            #                                  faster than low-score speech
        if self._calm_ticks >= after:
            self._calm_ticks = 0
            # clamp to the cap so non-power-of-two max_multipliers are
            # still reachable (any integer multiple of the base hop keeps
            # hop_alignment, so mult=3 etc. is geometrically fine)
            nxt = min(self._mult * 2, self.hcfg.max_multiplier)
            if nxt != self._mult and self._feasible_mult(nxt):
                self._set_mult(nxt)

    # -- the batched hop ----------------------------------------------------

    def _admit_ready(self):
        """Initialize any slotted stream whose buffer holds a full window.
        Returns (init_mask, init_logits) rows for this step's decisions.

        With ``batch_init`` (the default) the whole wave of ready slots —
        fresh admissions and session feature-replay streams alike — runs
        its first windows in ONE masked ``stream_init`` call: one fused
        launch per IMC layer for the wave, instead of a B=1 launch per
        admission (the enrollment-phase launch saving; bit-identical, the
        init math is row-parallel and exact on the fixed-point grids)."""
        window = self.geom.window
        init_mask = np.zeros((self.slots,), bool)
        init_logits = np.zeros((self.slots, self.cfg.num_classes),
                               np.float32)
        todo = [(s, rec) for s, rec in enumerate(self._slots)
                if rec is not None and not rec.initialized
                and len(rec.buf) >= window]
        if not todo:
            return init_mask, init_logits

        def _book(rec, s, first, dt):
            rec.wall_s += dt
            rec.initialized = True
            rec.hops += 1
            rec.consumed += window
            rec.recent = first.copy()
            rec.pending = []
            rec.silent_run = 0
            self._dstate = dec.reset_slot(self._dstate, s)
            if self._vstate is not None:
                self._vstate = vd.vad_reset_slot(self._vstate, s)
            init_mask[s] = True
            if self._rec is not None:
                self._rec.record(self._steps, "admit",
                                 stream=rec.stream_id, slot=s,
                                 internal=rec.internal)

        if self.batch_init:
            windows = np.zeros((self.slots, window), np.float32)
            keys = np.zeros((self.slots, 2), np.uint32)
            for s, rec in todo:
                windows[s] = rec.buf[:window]
                rec.buf = rec.buf[window:]   # the state carries the
                #                              overlap; later hops feed
                #                              fresh samples only
                keys[s] = np.asarray(
                    jax.random.fold_in(self._base_key, rec.uid))
            bundle = self._bundle(self._mult)
            mask = np.zeros((self.slots,), bool)
            for s, _ in todo:
                mask[s] = True
            mask_j = jnp.asarray(mask)
            t0 = time.perf_counter()
            with self._region("init"):
                if self._cust_on:
                    logits, self._state = bundle["init_cust"](
                        self._state, jnp.asarray(windows),
                        jnp.asarray(keys), mask_j,
                        *self._slot_custom_args())
                else:
                    logits, self._state = bundle["init"](
                        self._state, jnp.asarray(windows),
                        jnp.asarray(keys), mask_j)
                logits.block_until_ready()
            dt = time.perf_counter() - t0
            self._hop_wall_s += dt
            self._init_calls += 1
            if self._kwt:
                self._mfcc_frames += len(todo) * self.geom.frames
            for s, rec in todo:
                _book(rec, s, windows[s], dt / len(todo))
                init_logits[s] = np.asarray(logits[s])
            return init_mask, init_logits

        for s, rec in todo:
            first = rec.buf[:window]
            rec.buf = rec.buf[window:]
            key = jax.random.fold_in(self._base_key, rec.uid)[None]
            t0 = time.perf_counter()
            d1 = self._row_custom(rec)
            with self._region("init"):
                if d1 is not None:
                    logits, one = self.engine.init_custom(
                        jnp.asarray(first[None]), key, *d1)
                else:
                    logits, one = self.engine.init(jnp.asarray(first[None]),
                                                   key)
            self._state = self._scatter(self._state, one, s)
            dt = time.perf_counter() - t0
            # the window-0 decision counts toward throughput, so its time
            # must count too (decisions_per_sec = decisions / hop_wall_s)
            self._hop_wall_s += dt
            self._init_calls += 1
            if self._kwt:
                self._mfcc_frames += self.geom.frames
            _book(rec, s, first, dt)
            init_logits[s] = np.asarray(logits[0])
        return init_mask, init_logits

    def step(self) -> List[dict]:
        """One scheduler tick.  Returns this tick's decision events (one
        per deciding stream; gated hops emit none).

        With ``compiled=`` a steady-state tick runs as a one-tick
        compiled block (one VAD dispatch + one fused scan dispatch,
        repro.serving.compiled) and any structural tick — admissions,
        sheds, resizes, session/health traffic — falls back to the
        interpreted path; both produce bit-identical events, state and
        counters (dispatch accounting aside)."""
        c0 = compiles.tally()
        with span("step", ticks=1, slots=self.slots, **self._span_args):
            if (self._compiled is not None
                    and self._compiled.horizon(1) == 1):
                events = self._compiled.run(1)
            else:
                events = self._step_python()
        self._book_compiles(c0)
        return events

    def step_block(self, max_ticks: Optional[int] = None) -> List[dict]:
        """Serve up to ``max_ticks`` steady-state ticks in ONE compiled
        dispatch, returning their concatenated decision events in tick
        order — bit-identical to calling ``step()`` that many times.
        The compiled config's ``block`` is a hard per-dispatch cap (it
        bounds the padded scan length, so jit retraces stay bounded no
        matter what callers pass); the block also ends early at any
        structural boundary (``CompiledTick.horizon``).  A tick the
        compiled path cannot model at all runs interpreted.  Without
        ``compiled=`` this is exactly one interpreted ``step()``."""
        c0 = compiles.tally()
        with span("step", slots=self.slots, **self._span_args) as sp:
            k = 0
            if self._compiled is not None:
                cap = self._compiled.cfg.block
                k = self._compiled.horizon(cap if max_ticks is None
                                           else min(max_ticks, cap))
            sp.set_metadata(ticks=max(k, 1))
            events = (self._compiled.run(k) if k >= 1
                      else self._step_python())
        self._book_compiles(c0)
        return events

    def _book_compiles(self, before) -> None:
        """Book the process's jit traces / compiles / cache loads since
        ``before`` (a ``compiles.tally()``) as this step's
        ``serving.compiles{kind}``."""
        for kind, a, b in zip(compiles.KINDS, compiles.tally(), before):
            if a != b:
                self._metrics.inc("serving.compiles", a - b, kind=kind)

    def _step_python(self) -> List[dict]:
        """One interpreted scheduler tick: SLO shedding, autoscaling,
        admissions, VAD classification, wake replays, then ONE batched hop
        over every speech-ready slot and ONE masked no-op fill over every
        gated slot, then the batched decision update.  This is the
        reference semantics the compiled fast path is proven against."""
        tick = self._steps
        if self._audit is not None:
            self._audit.begin_tick(tick)
        self._check_profiles()
        if self._faults is not None:
            self._faults.tick()                 # advance offset drift
            if self._faults.pop_dirty():
                self._refresh_chip_delta()      # riders pick up new deltas
        self._enforce_slo()
        self._autoscale()
        bundle = self._bundle(self._mult)
        hop = self.geom.hop
        window = self.geom.window
        with span("admit"):
            init_mask, init_logits = self._admit_ready()

        ready = np.zeros((self.slots,), bool)
        audio = np.zeros((self.slots, hop), np.float32)
        for s, rec in enumerate(self._slots):
            if (rec is not None and rec.initialized and not init_mask[s]
                    and len(rec.buf) >= hop):
                ready[s] = True
                audio[s] = rec.buf[:hop]
                rec.buf = rec.buf[hop:]

        if self.vcfg is None:
            speech = ready.copy()
        else:
            self._vstate, sp = self._vad_fn(self._vstate,
                                            jnp.asarray(audio),
                                            jnp.asarray(ready))
            speech = np.asarray(sp) & ready
            for s, rec in enumerate(self._slots):
                # enrollment/replay hops must run the real IMC path — a
                # gated (fill-advanced) hop would corrupt the captured
                # feature buffer, so learning streams bypass the VAD gate
                if ready[s] and rec is not None and rec.force_compute:
                    speech[s] = True
        # a tick is *silent* when hops ran but none carried speech — the
        # duty-aware dynamic hop widens faster on these (force_compute
        # streams count as speech, so forced paths never look silent)
        silent_tick = bool(ready.any()) and not bool((speech & ready).any())

        compute_mask = np.zeros((self.slots,), bool)
        fill_mask = np.zeros((self.slots,), bool)
        replays: List[tuple] = []
        for s, rec in enumerate(self._slots):
            if not ready[s]:
                continue
            chunk = audio[s]
            if speech[s]:
                rec.silent_run = 0
                if rec.pending:           # wake: replay the deferred hops
                    replays.append((s, rec.pending + [chunk]))
                    rec.pending = []
                else:
                    compute_mask[s] = True
            else:
                rec.silent_run += 1
                rec.pending.append(chunk)
                if len(rec.pending) > self.vcfg.wake_margin:
                    aged = rec.pending.pop(0)
                    fill_mask[s] = True   # advance by the no-op fill
                    rec.recent = np.concatenate([rec.recent,
                                                 aged])[-window:]
                    rec.consumed += hop
                    rec.gated_hops += 1
                    self._gated_hops += 1

        events: List[dict] = []

        # wake replays: the deferred silent hops plus the onset hop run the
        # real IMC path for this slot in ONE multi-hop launch per IMC layer
        # (the tail just extends by the deferred hops' fresh columns), so
        # the keyword prefix the VAD latency would have cut is decided
        # exactly as if ungated — bit-identical to replaying hop by hop
        for s, chunks in replays:
            rec = self._slots[s]
            n = len(chunks)
            mask = np.zeros((self.slots,), bool)
            mask[s] = True
            mask_j = jnp.asarray(mask)
            a = np.zeros((self.slots, n * hop), np.float32)
            a[s] = np.concatenate(chunks)
            t0 = time.perf_counter()
            with span("replay"):
                with self._region("replay"):
                    if self._cust_on:
                        fn = self._replay_fn(bundle, n, cust=True)
                        lg, self._state = fn(self._state, jnp.asarray(a),
                                             mask_j,
                                             *self._slot_custom_args())
                    else:
                        fn = self._replay_fn(bundle, n, cust=False)
                        lg, self._state = fn(self._state, jnp.asarray(a),
                                             mask_j)
                self._replay_calls += 1
                outs = []
                for j in range(n):
                    self._dstate, out = self._decide(self._dstate,
                                                     lg[:, j], mask_j)
                    outs.append(out)
                outs[-1].score.block_until_ready()
            dt = time.perf_counter() - t0
            rec.wall_s += dt
            self._hop_wall_s += dt
            for j, (ch, out) in enumerate(zip(chunks, outs)):
                self._decisions += 1
                self._speech_hops += 1
                rec.recent = np.concatenate([rec.recent, ch])[-window:]
                rec.consumed += hop
                rec.hops += 1
                ev = {"stream": rec.stream_id, "hop": rec.hops - 1,
                      "keyword": int(out.keyword[s]),
                      "score": float(out.score[s]),
                      "trigger": bool(out.trigger[s])}
                events.append(ev)
                if ev["trigger"]:
                    rec.triggers.append(ev)

        logits = init_logits
        if compute_mask.any():
            t0 = time.perf_counter()
            mask_j = jnp.asarray(compute_mask)
            with span("hop"), self._region("hop"):
                if self._cust_on:
                    hop_logits, self._state = bundle["hop_cust"](
                        self._state, jnp.asarray(audio), mask_j,
                        *self._slot_custom_args())
                else:
                    hop_logits, self._state = bundle["hop"](
                        self._state, jnp.asarray(audio), mask_j)
                hop_logits.block_until_ready()
            dt = time.perf_counter() - t0
            self._hop_wall_s += dt
            self._hop_calls += 1
            n_active = int(compute_mask.sum())
            if self._kwt:
                self._mfcc_frames += n_active * self.geom.frames_per_hop
            for s, rec in enumerate(self._slots):
                if compute_mask[s]:
                    if rec.internal:
                        self._learn_hops += 1
                    else:
                        self._speech_hops += 1
                    rec.hops += 1
                    rec.wall_s += dt / n_active
                    rec.consumed += hop
                    rec.recent = np.concatenate([rec.recent,
                                                 audio[s]])[-window:]
            logits = np.where(compute_mask[:, None], np.asarray(hop_logits),
                              init_logits)

        if fill_mask.any():
            t0 = time.perf_counter()
            with span("gate"), self._region("gate"):
                if self._cust_on and self._slot_fills is not None:
                    self._state = bundle["gate_cust"](
                        self._state, jnp.asarray(fill_mask),
                        self._slot_fills)
                else:
                    self._state = bundle["gate"](self._state,
                                                 jnp.asarray(fill_mask))
                jax.block_until_ready(self._state)
            dt = time.perf_counter() - t0
            self._hop_wall_s += dt
            self._gate_calls += 1

        internal = np.asarray([rec is not None and rec.internal
                               for rec in self._slots])
        decide_mask = (init_mask | compute_mask) & ~internal
        if bool(decide_mask.any()):
            with span("decide"):
                self._dstate, out = self._decide(self._dstate,
                                                 jnp.asarray(logits),
                                                 jnp.asarray(decide_mask))
                trig = np.asarray(out.trigger)
                kwd = np.asarray(out.keyword)
                score = np.asarray(out.score)
            self._decisions += int(decide_mask.sum())
            for s, rec in enumerate(self._slots):
                if rec is None or not decide_mask[s]:
                    continue
                ev = {"stream": rec.stream_id, "hop": rec.hops - 1,
                      "keyword": int(kwd[s]), "score": float(score[s]),
                      "trigger": bool(trig[s])}
                events.append(ev)
                if ev["trigger"]:
                    rec.triggers.append(ev)

        # feature captures must see the post-hop states before slots retire
        with span("riders"):
            if self._cust is not None:
                self._cust.on_step(self)
            if self._health is not None:
                self._health.on_step(self)      # canary carry/ring capture

            # decisions emitted while the chip is not healthy are flagged
            # so downstream consumers can discount (or re-request) them
            if self._health is not None:
                degraded = self._health.state != "healthy"
                for ev in events:
                    ev["degraded"] = degraded

            # retire drained finished streams
            for rec in list(self._slots):
                if (rec is not None and rec.finished
                        and len(rec.buf) < (hop if rec.initialized
                                            else window)):
                    self._free_slot(rec)
            self._steps += 1
            self._retarget_hop(events, woke=bool(replays),
                               silent=silent_tick)
            # background learning jobs: calibration layers, feature-replay
            # spawns, bounded fine-tune epochs, hot swaps
            if self._cust is not None:
                self._cust.tick(self)
            # health background work: canary spawns + tick-resumable
            # recompensation (calibration layers, heal hot-swap)
            if self._health is not None:
                self._health.tick(self)

            # -- per-tick telemetry (composition, analytical uJ) ----------
            n_replay_hops = sum(len(chunks) for _, chunks in replays)
            computed = (int(init_mask.sum()) + int(compute_mask.sum())
                        + n_replay_hops)
            gated = int(fill_mask.sum())
            if self._rec is not None and (computed or gated or events):
                uj = self._tick_uj(computed, gated)
                self._rec.record(tick, "tick", init=int(init_mask.sum()),
                                 computed=computed, gated=gated,
                                 replays=len(replays), decisions=len(events),
                                 uj=round(uj, 4))
                self._metrics.observe("serving.tick_uj", uj)
        if self._audit is not None:
            self._audit.end_tick()
        return events

    def drain(self, max_steps: int = 10_000) -> List[dict]:
        """Step until no slot can make progress and the queue is empty."""
        events: List[dict] = []
        for _ in range(max_steps):
            before = (len(self._queue),
                      [None if r is None else len(r.buf)
                       for r in self._slots])
            # compiled servers drain in whole blocks; tick count and all
            # serving state stay bit-identical to one-step draining
            events.extend(self.step() if self._compiled is None
                          else self.step_block())
            after = (len(self._queue),
                     [None if r is None else len(r.buf)
                      for r in self._slots])
            if after == before:
                break
        return events

    # -- crash-safe snapshots ------------------------------------------------

    def snapshot(self, path: Optional[str] = None):
        """Serialize the complete serving state — slot carries and GAP
        rings, decision/VAD state, per-stream buffers and noise-field
        keys, queue order, fault/health state, the healing delta and
        every mid-flight customization session — so a restarted process
        can ``restore()`` and continue **bit-identically** to an
        uninterrupted run (test-enforced).

        Take snapshots at tick boundaries (between ``step()`` calls —
        that is the only consistent cut).  With ``path`` the snapshot is
        written as one .npz, atomically (tmp + fsync + ``os.replace``,
        the ProfileStore idiom): a crash mid-save leaves the previous
        snapshot intact.  Without ``path`` the in-memory snapshot dict is
        returned (useful for tests and warm standbys)."""
        arrays: Dict[str, np.ndarray] = {}
        spec = {
            "version": 2,
            "config": {"sample_len": self.cfg.sample_len,
                       "base_hop": self.base_hop,
                       "streaming": self.streaming,
                       "sa_noise_std": float(
                           self._engine_kw["sa_noise_std"]),
                       "vad": self.vcfg is not None},
            "slots_n": self.slots,
            "mult": self._mult,
            "uid": self._uid,
            "base_key": _snap_encode(np.asarray(self._base_key), arrays),
            "state": _snap_encode(self._state, arrays),
            "dstate": _snap_encode(self._dstate, arrays),
            "vstate": _snap_encode(self._vstate, arrays),
            "streams": {sid: _snap_encode(dict(vars(rec)), arrays)
                        for sid, rec in self._streams.items()},
            "queue": [rec.stream_id for rec in self._queue],
            "slot_ids": [None if rec is None else rec.stream_id
                         for rec in self._slots],
            # v2: the whole metrics registry rides along — every counter
            # (serving, health, customization) round-trips without a
            # hand-maintained key list
            "counters": self._metrics.snapshot(),
            "recorder": (self._rec.snapshot()
                         if self._rec is not None else None),
            "cust_on": self._cust_on,
            "heal": _snap_encode(self._heal_delta, arrays),
            "faults": _snap_encode(
                self._faults.snapshot() if self._faults is not None
                else None, arrays),
            "health": _snap_encode(
                self._health.snapshot() if self._health is not None
                else None, arrays),
            "cust": self._snap_sessions(arrays),
        }
        if path is None:
            return {"spec": spec, "arrays": arrays}
        payload = dict(arrays)
        payload["meta"] = np.frombuffer(
            json.dumps(spec).encode("utf-8"), dtype=np.uint8)
        parent = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(parent, exist_ok=True)
        fd, tmp = tempfile.mkstemp(prefix=".tmp.snapshot.", suffix=".npz",
                                   dir=parent)
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)                  # atomic commit
        except Exception:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        return path

    _SESS_SKIP = ("_mgr", "_grads_fn")   # back-ref / jit closure: rebuilt

    def _snap_sessions(self, arrays):
        if self._cust is None:
            return None
        sessions = []
        for sess in self._cust.sessions:
            d = {k: v for k, v in vars(sess).items()
                 if k not in self._SESS_SKIP}
            sessions.append(_snap_encode(d, arrays))
        return {"next_sid": self._cust._next_sid, "sessions": sessions}

    def restore(self, snap) -> None:
        """Restore a snapshot (a path or an in-memory snapshot dict) into
        THIS server, which must be freshly constructed with the same
        configuration — model/hw, hop, slot bounds, noise std and chip
        offsets, decision/VAD/admission configs and the same ``faults=``
        / ``health=`` / ``profiles=`` wiring.  (The snapshot stores
        serving *state*; the configuration is code.)  After restore the
        server continues bit-identically to the uninterrupted original,
        including SA-noise fields (per-stream keys are restored verbatim)
        and in-flight enrollment sessions."""
        if isinstance(snap, (str, os.PathLike)):
            with np.load(snap, allow_pickle=False) as data:
                spec = json.loads(bytes(data["meta"]).decode("utf-8"))
                arrays = {k: data[k] for k in data.files if k != "meta"}
        else:
            spec, arrays = snap["spec"], snap["arrays"]
        if spec.get("version") not in (1, 2):
            raise ValueError(f"unknown snapshot version: "
                             f"{spec.get('version')!r}")
        c = spec["config"]
        if (c["sample_len"] != self.cfg.sample_len
                or c["base_hop"] != self.base_hop
                or bool(c["streaming"]) != self.streaming
                or bool(c["vad"]) != (self.vcfg is not None)):
            raise ValueError(f"snapshot/server configuration mismatch: "
                             f"snapshot has {c}")
        n = int(spec["slots_n"])
        if not (self.min_slots <= n <= self.max_slots):
            raise ValueError(f"snapshot slot count {n} outside this "
                             f"server's [{self.min_slots}, "
                             f"{self.max_slots}]")
        self.slots = n
        self._mult = int(spec["mult"])
        self._bundle(self._mult)                  # engine for this hop
        self._uid = int(spec["uid"])
        self._base_key = jnp.asarray(_snap_decode(spec["base_key"],
                                                  arrays))

        def jaxify(tree):
            return jax.tree_util.tree_map(jnp.asarray, tree)

        self._state = jaxify(_snap_decode(spec["state"], arrays))
        self._dstate = jaxify(_snap_decode(spec["dstate"], arrays))
        v = _snap_decode(spec["vstate"], arrays)
        self._vstate = jaxify(v) if v is not None else None
        self._streams = {}
        for sid, s_spec in spec["streams"].items():
            self._streams[sid] = _Stream(**_snap_decode(s_spec, arrays))
        self._queue = collections.deque(self._streams[sid]
                                        for sid in spec["queue"])
        self._slots = [None if sid is None else self._streams[sid]
                       for sid in spec["slot_ids"]]
        counters = spec["counters"]
        if spec["version"] >= 2:
            self._metrics.restore(counters)
        else:                       # v1: per-attribute dict; the setattrs
            for k, val in counters.items():   # write through the registry
                setattr(self, k, val)         # properties
        if spec.get("recorder") is not None and self._rec is not None:
            self._rec.restore(spec["recorder"])
        # riders rebuild from scratch at the restored slot count; per-slot
        # rows re-materialize deterministically from each stream's
        # ``custom`` dict, the chip-global row from heal + fault state
        self._cust_on = False
        self._slot_delta = None
        self._slot_head_w = None
        self._slot_head_b = None
        self._slot_fills = None
        self._heal_delta = _snap_decode(spec["heal"], arrays)
        f = _snap_decode(spec["faults"], arrays)
        if (f is None) != (self._faults is None):
            raise ValueError("snapshot fault-model mismatch: construct "
                             "the server with the same faults= wiring")
        if f is not None:
            self._faults.restore(f)
            self._faults.pop_dirty()
        h = _snap_decode(spec["health"], arrays)
        if (h is None) != (self._health is None):
            raise ValueError("snapshot health mismatch: construct the "
                             "server with the same health= wiring")
        if h is not None:
            self._health.restore(h)
        if spec["cust_on"]:
            self._enable_customization()
        self._refresh_chip_delta()
        cust = spec["cust"]
        if cust is None:
            self._cust = None
        else:
            from repro.serving import customize as cz
            self._cust = cz.CustomizationManager(self)
            self._cust._next_sid = int(cust["next_sid"])
            for s_spec in cust["sessions"]:
                d = _snap_decode(s_spec, arrays)
                sess = cz.CustomizationSession.__new__(
                    cz.CustomizationSession)
                sess._mgr = self._cust
                sess._grads_fn = None             # jit closure: re-traced
                for k, val in d.items():
                    setattr(sess, k, val)
                if sess._head is not None:
                    sess._head = jaxify(sess._head)
                self._cust.sessions.append(sess)

    # -- accounting ---------------------------------------------------------

    def active_streams(self) -> List[str]:
        return [r.stream_id for r in self._slots if r is not None]

    def stats(self) -> dict:
        if self._kwt:
            macs_off = kwt.decision_macs(self.cfg, self.geom.frames)
            macs_str = kwt.decision_macs(self.cfg, self.geom.frames_per_hop)
        else:
            offline = kws.layer_stats(self.cfg)
            streaming = sv.streaming_layer_stats(self.cfg, self.geom)
            macs_off = sum(s["macs"] for s in offline)
            macs_str = sum(s["macs"] for s in streaming)
        per_stream = {
            rec.stream_id: {
                "hops": rec.hops,
                "gated_hops": rec.gated_hops,
                "triggers": len(rec.triggers),
                "sheds": rec.sheds,
                "wall_s": round(rec.wall_s, 4),
            }
            for rec in self._streams.values() if not rec.internal
        }
        total_hops = self._speech_hops + self._gated_hops
        duty = (self._speech_hops / total_hops) if total_hops else None
        out = {
            "mode": "streaming" if self.streaming else "recompute",
            "silence_fill": self.silence_fill,
            "slots": self.slots,
            "slot_range": [self.min_slots, self.max_slots],
            "queue_depth": len(self._queue),
            "rejected_streams": self._rejected,
            "shed": {"events": self._shed_events,
                     "samples": self._shed_samples},
            "steps": self._steps,
            "decisions": self._decisions,
            "base_hop": self.base_hop,
            "hop": self.hop,
            "hop_multiplier": self._mult,
            "hop_retargets": self._hop_retargets,
            "speech_hops": self._speech_hops,
            "gated_hops": self._gated_hops,
            "learn_hops": self._learn_hops,
            # each entry is one batched jax call; init/hop/replay calls
            # cost one fused-kernel launch per IMC layer (any number of
            # slots per call), gate calls launch nothing
            "batched_calls": {
                "init": self._init_calls,
                "hop": self._hop_calls,
                "replay": self._replay_calls,
                "gate": self._gate_calls,
            },
            "duty_cycle": round(duty, 4) if duty is not None else None,
            "hop_wall_s": round(self._hop_wall_s, 4),
            "decisions_per_sec": round(
                self._decisions / self._hop_wall_s, 2)
                if self._hop_wall_s > 0 else None,
            "macs_per_decision": {
                "offline": macs_off,
                "streaming": macs_str,
                "ratio": round(macs_str / macs_off, 4),
            },
            "per_stream": per_stream,
        }
        if self._cust is not None:
            out["customization"] = self._cust.stats()
        if self._compiled is not None:
            out["compiled"] = {"block": self._compiled.cfg.block,
                               "blocks": self._compiled_blocks,
                               "ticks": self._compiled_ticks}
        out["obs"] = {"metrics": len(self._metrics._cells)}
        if self._rec is not None:
            out["obs"]["recorder"] = {"events": len(self._rec),
                                      "capacity": self._rec.capacity,
                                      "dropped": self._rec.dropped()}
        if self._audit is not None:
            out["obs"]["audit"] = self._audit.stats()
        if self._profiles is not None:
            out["profile_swaps"] = self._profile_swaps
        if self._faults is not None:
            out["faults"] = self._faults.stats()
        if self._health is not None:
            out["health"] = self._health.stats()
        if self.vcfg is not None:
            out["gated_energy"] = {
                k: round(v, 4) if isinstance(v, float) else v
                for k, v in energy.gated_energy_summary(
                    offline, streaming, hop_samples=self.hop,
                    duty_cycle=duty if duty is not None else 1.0).items()
            }
        return out
