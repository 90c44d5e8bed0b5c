#!/usr/bin/env bash
# CI gate: the tier-1 quick pass plus the streaming-equivalence,
# gating-equivalence and customization-equivalence contracts and the docs
# consistency check.
#
#   scripts/ci.sh            quick: everything but slow/streaming-marked
#                            tests, then the streaming bit-exactness tests
#                            (incl. the VAD-gating equivalence + wake-margin
#                            replay gates), the customization gates, the
#                            observability gate (telemetry bit-identity +
#                            auditor-in-raise-mode equivalence slice), the
#                            sharding gate (sharded == single-device
#                            bit-identity on 2 host-platform devices +
#                            the --devices 2 bench smoke), then the docs
#                            check
#   scripts/ci.sh --full     the whole suite (tier-1 command verbatim)
#                            plus the docs check
#
# The fault-recovery gate (tests/test_reliability.py) is the acceptance
# contract of the self-healing serving stack: canaries detect and
# localize an injected fault, recompensation heals it back to healthy,
# stuck columns are masked, the one-launch-per-layer invariant holds
# under fault + canary, and snapshot/restore resumes bit-identically.
#
# The `streaming` marker (pytest.ini) tags the serving equivalence tests,
# the gating/backpressure/dynamic-hop server tests and the long
# multi-stream soak: the quick pass deselects them wholesale, then re-runs
# the non-slow subset explicitly (the soak stays out — it is also marked
# `slow`).  The gating-equivalence gate is the acceptance contract that a
# VAD forced to "speech" leaves serving bit-identical to ungated
# streaming, SA noise and chip offsets included.  The
# customization-equivalence gate is the acceptance contract that an
# enrollment session driven through scheduler ticks lands on EXACTLY the
# offline customize loop's result (compensated biases + fine-tuned head)
# and that a mixed inference+learning tick still issues one fused-kernel
# launch per IMC layer.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

if [[ "${1:-}" == "--full" ]]; then
    python -m pytest -x -q
    exec python scripts/check_docs.py
fi

python -m pytest -x -q -m "not slow and not streaming"
python -m pytest -x -q -m "streaming and not slow" tests/test_serving.py
# gating-equivalence gate (explicit, so a marker edit can't silently drop it)
python -m pytest -x -q tests/test_serving.py \
    -k "gated_forced_speech_bitexact or wake_margin_replays"
# customization-equivalence gate (session == offline loop — clean AND
# SA-noise-field configs, the -k prefix matches both; one launch per
# layer on mixed inference+learning ticks; batched replay-wave init ==
# sequential; profiles restored from disk serve bit-identically)
python -m pytest -x -q tests/test_customize.py \
    -k "session_matches_offline_loop or mixed_tick_one_fused_launch \
        or batched_replay_init or profile_store_restart"
# fault-recovery gate (canary detect -> localize -> recompensate back to
# healthy; one fused launch per layer under fault + canary; snapshots
# restore bit-identically) plus the quick soak slice — the long
# randomized soaks stay out (marked slow)
python -m pytest -x -q tests/test_reliability.py \
    -k "canary_detects or drift_fault_heals or one_launch_per_layer \
        or snapshot_restore_bit_identical"
python -m pytest -x -q -m "streaming and not slow" tests/test_reliability.py
# observability gate (docs/OBSERVABILITY.md): registry/recorder/auditor
# unit contracts, telemetry-fully-on == telemetry-off bit-identity (SA
# noise, chip offsets, fault + canary + learning traffic) and the
# snapshot v2 round-trip — then the gating-equivalence slice re-run with
# the launch auditor armed in raise mode through the environment, so a
# doubled fused launch or a gate fill that touches a kernel aborts CI
python -m pytest -x -q tests/test_obs.py
REPRO_OBS_AUDIT=raise python -m pytest -x -q tests/test_serving.py \
    -k "gated_forced_speech_bitexact or wake_margin_replays"
# sharding gate (docs/SHARDING.md): the sharded-equivalence contract —
# a ShardedStreamServer (per-device slot pools behind the placement
# router) is bit-identical per stream to single-device serving, noise /
# chip offsets / faults / gating / snapshot bundles included, and the
# one-launch-per-layer audit holds PER DEVICE — run under a forced
# 2-device host platform so placement exercises real device boundaries,
# then the --devices 2 bench smoke (scaling section machinery end to
# end; the committed artifact's full regen command is in docs/SHARDING.md)
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2" \
    python -m pytest -x -q tests/test_sharded_serving.py -m "not slow"
XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=2" \
    python -m pytest -x -q tests/test_obs.py -k "device or sharded"
JAX_PLATFORMS=cpu python -m benchmarks.run --streaming --devices 2 \
    --stream-hops 2 --streaming-out "$(mktemp -d)/BENCH_streaming.json" > /dev/null
# compiled-tick gate (docs/SERVING.md): the whole-tick fast path is
# bit-identical to the interpreted tick — a quick differential slice
# (gated + noise/chip configs, single-tick block routing, the byte-pinned
# golden decision trace), the auditor's compiled-cause rules with
# REPRO_OBS_AUDIT=raise armed through the environment, then the
# --streaming --compiled bench smoke (in-bench event-identity assert +
# raise-mode audit; the committed artifact's full regen command is in
# docs/SERVING.md).  The full differential matrix (faults, dynamic hop,
# autoscale, sharded, soak) runs under `-m compiled` in the full suite.
python -m pytest -x -q tests/test_compiled.py \
    -k "(block_bitident and (gated_clean or noise_and_chip)) \
        or routes_single_tick or golden_decision_trace \
        or auditor_compiled_cause_rules or audit_raise_clean_env"
python -m benchmarks.run --streaming --compiled --compiled-ticks 8 \
    --compiled-block 4 --stream-hops 2 \
    --streaming-out "$(mktemp -d)/BENCH_streaming.json" > /dev/null
python scripts/check_docs.py
