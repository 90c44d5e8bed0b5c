"""Always-on streaming KWS quickstart: a few live audio streams through the
multi-stream serving engine.

  1. fold a seeded, untrained model to the hardware path (the serving
     mechanics are those of a trained one),
  2. synthesize a few "microphone" streams: keyword utterances embedded in
     noise at random offsets,
  3. run the slot-based StreamServer with voice-activity gating: every
     step batches all live streams' fresh frames into ONE fused-kernel
     launch per IMC layer, each stream advancing a sliding decision window
     by `hop` samples at ~hop/window of the full per-decision work
     (frame-incremental reuse) — and hops the VAD classifies as silence
     skip the IMC stack entirely (no-op fill advance, leakage-only in the
     energy model), with a wake margin replaying the hops right before a
     speech onset so no keyword prefix is lost,
  4. print trigger events (posterior-smoothed + hysteresis + refractory)
     and the server's throughput / duty-cycle / per-decision MAC and
     energy accounting.

Run:  PYTHONPATH=src python examples/stream_kws.py
      REPRO_EXAMPLES_SMOKE=1 ... for a seconds-scale smoke run (used by
      tests/test_examples.py)
"""
import os

import jax
import numpy as np

from repro.data import audio
from repro.models import kws as m
from repro.serving import DecisionConfig, StreamServer, VADConfig

SMOKE = os.environ.get("REPRO_EXAMPLES_SMOKE") == "1"
L, HOP = (640, 64) if SMOKE else (2000, 256)   # hop/window = 0.1 / 0.128
N_STREAMS = 1 if SMOKE else 3
TAIL_HOPS = 8 if SMOKE else 24
cfg = m.KWSConfig(sample_len=L)

params = m.init_params(jax.random.PRNGKey(0), cfg)
state = m.init_state(cfg)
hw = m.fold_params(params, state, cfg, pack=True)   # pack once, serve many

# synth streams: keyword clips at random offsets in low noise
rng = np.random.default_rng(0)
(clips, labels), _ = audio.make_gscd_like(train_per_class=1,
                                          test_per_class=1, length=L)
streams = {}
for i in range(N_STREAMS):
    # long stream, keyword early: the silent tail is what the VAD gates
    wav = 0.01 * rng.standard_normal(L + TAIL_HOPS * HOP).astype(np.float32)
    j = rng.integers(len(labels))
    at = int(rng.integers(0, 4 * HOP))
    wav[at:at + L] += clips[j].astype(np.float32)
    streams[f"mic{i}"] = (wav, int(labels[j]), at)

srv = StreamServer(hw, cfg, hop=HOP, slots=4, use_kernel=True,
                   decision=DecisionConfig(smooth=4, threshold_on=0.5,
                                           threshold_off=0.35,
                                           refractory=6),
                   # the 0.01-amplitude noise floor sits at ~-40 dBFS:
                   # well under the on threshold, so hops outside the
                   # embedded keyword windows are gated (leakage-only)
                   vad=VADConfig(threshold_on_db=-30.0,
                                 threshold_off_db=-36.0,
                                 wake_margin=2, hang=1))
print(f"== serving {len(streams)} streams "
      f"(window={L}, hop={HOP}, slots=4) ==")
for sid, (wav, kw, at) in streams.items():
    print(f"   {sid}: keyword {kw} at sample {at}")
    # feed in ~real-time-ish chunks, as a microphone driver would
    for off in range(0, len(wav), 517):
        srv.submit(sid, wav[off:off + 517])
    srv.finish(sid)

for ev in srv.drain():
    if ev["trigger"]:
        print(f"   TRIGGER {ev['stream']} hop {ev['hop']}: "
              f"keyword {ev['keyword']} (score {ev['score']:.2f})")

s = srv.stats()
print(f"== {s['decisions']} decisions, "
      f"{s['decisions_per_sec']} decisions/s, "
      f"streaming MACs/decision = "
      f"{s['macs_per_decision']['ratio']:.3f}x offline ==")
g = s["gated_energy"]
print(f"== VAD duty cycle {s['duty_cycle']:.2f} "
      f"({s['speech_hops']} speech / {s['gated_hops']} gated hops): "
      f"{g['gated_uj_per_decision']:.3f} uJ/decision vs "
      f"{g['ungated_uj_per_decision']:.3f} ungated "
      f"({g['reduction_vs_ungated']:.2f}x) ==")
