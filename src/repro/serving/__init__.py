"""Always-on streaming KWS serving over the hardware-folded model.

The deployment shape of the paper's accelerator: a sliding decision window
advanced by a hop, with frame-incremental reuse of every IMC layer's
activation columns between hops (the per-decision work drops to roughly
hop/window of a full forward), a voice-activity gate in front of the
compute (silent hops advance state by a no-op fill and are charged
leakage-only), a smoothed/hysteresis decision head, and a slot-based
scheduler that batches many live streams into one fused-kernel launch per
layer with dynamic hop widening and admission control.

  stream.py     — hop geometry, per-stream ring state, init/step (+ the
                  multi-hop step and the per-stream bias-delta/head
                  riders), the per-absolute-column SA-noise field, the
                  gated (no-IMC) state advance, work accounting
  kwt_stream.py — the Keyword Transformer's engine (repro.models.kwt):
                  an MFCC frame ring and the whole encoder every hop;
                  StreamServer builds it for a KWTConfig
  vad.py        — log-energy EMA + hysteresis voice-activity detector
  decision.py   — posterior smoothing + hysteresis + refractory triggers
  scheduler.py  — StreamServer: slots, admission queue + backpressure,
                  batched hops, VAD gating + wake replay, dynamic hop,
                  slot autoscaling, eviction, latency/throughput stats
  compiled.py   — whole-tick compiled fast path: K steady-state ticks
                  (VAD gate -> batched hop -> decision -> rider updates)
                  fused into one jitted lax.scan dispatch, bit-identical
                  to K interpreted ticks; structural events break out
                  to the Python tick
  shard.py      — ShardedStreamServer: N per-device slot pools (one
                  StreamServer per device) behind a deterministic
                  host-side placement router (repro.sharding); global
                  uid assignment keeps sharded serving bit-identical to
                  single-device per stream
  customize.py  — on-device customization as a serving workload:
                  enrollment sessions, scheduler-ticked bias compensation
                  + SGA fine-tuning, hot-swapped per-stream profiles
  health.py     — canary-based health monitoring and self-healing over
                  the fault models in repro.core.faults: periodic known
                  windows ride the batched tick, divergence localizes the
                  faulty layer/columns, background recompensation heals
                  drift/flip faults, unrecoverable columns are masked

Bit-exactness contracts: N hops of the streaming path equal ``hw_forward``
on each full window — noise and chip-offset configurations included;
``streaming=False`` falls back to exactly that recompute path; gated
serving with the VAD forced to "speech" is bit-identical to ungated
serving (silence never computes, so all-speech audio never gates); a
customization session driven through scheduler ticks equals the offline
customize loop on the same utterances (compensated biases + fine-tuned
head) — chip offsets AND SA-noise fields included, the offline oracle
evaluating the session's recorded per-absolute-column field
(``repro.core.sa_noise``); batched admission waves equal sequential B=1
admissions; and a profile persisted via
``repro.checkpoint.profiles.ProfileStore`` restores bit-identically
after a restart.
"""

from repro.core.faults import FaultConfig, FaultModel
from repro.core.sa_noise import SANoiseField
from repro.serving.compiled import CompiledTick, CompiledTickConfig
from repro.obs import (FlightRecorder, LaunchAuditError, LaunchAuditor,
                       MetricsRegistry, ObsConfig)
from repro.serving.customize import (CustomizationResult,
                                     CustomizationSession, CustomizeConfig)
from repro.serving.health import HealthConfig, HealthMonitor
from repro.serving.kwt_stream import KWTEngine, KWTState
from repro.serving.decision import (DecisionConfig, DecisionOut,
                                    DecisionState, decision_init,
                                    decision_step)
from repro.serving.scheduler import (AdmissionConfig, DynamicHopConfig,
                                     StreamServer)
from repro.serving.shard import ShardedStreamServer
from repro.serving.stream import (StreamEngine, StreamGeometry, StreamState,
                                  gated_step, gated_window_step,
                                  hop_alignment, hop_sa_noise_fields,
                                  make_stream_geometry, retention_fills,
                                  sa_noise_columns, silence_fills,
                                  stream_init, stream_multi_step,
                                  stream_step, streaming_layer_stats,
                                  window_sa_noise)
from repro.serving.vad import (VADConfig, VADState, frame_energy_db,
                               vad_init, vad_step)

__all__ = [
    "AdmissionConfig", "CompiledTick", "CompiledTickConfig",
    "CustomizationResult", "CustomizationSession",
    "CustomizeConfig", "DecisionConfig", "DecisionOut", "DecisionState",
    "DynamicHopConfig", "FaultConfig", "FaultModel", "FlightRecorder",
    "HealthConfig", "HealthMonitor", "KWTEngine", "KWTState",
    "LaunchAuditError", "LaunchAuditor",
    "MetricsRegistry", "ObsConfig", "SANoiseField", "ShardedStreamServer",
    "StreamServer",
    "StreamEngine", "StreamGeometry", "StreamState",
    "VADConfig", "VADState", "decision_init",
    "decision_step", "frame_energy_db", "gated_step", "gated_window_step",
    "hop_alignment", "hop_sa_noise_fields", "make_stream_geometry",
    "retention_fills", "sa_noise_columns", "silence_fills", "stream_init",
    "stream_multi_step", "stream_step", "streaming_layer_stats", "vad_init",
    "vad_step", "window_sa_noise",
]
