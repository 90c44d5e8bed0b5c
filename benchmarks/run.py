"""Benchmark harness: one function per paper table/figure + kernel
microbenchmarks + the dry-run roofline summary.

Prints ``name,us_per_call,derived`` CSV rows.  Expensive artifacts
(results/kws_results.json from benchmarks.kws_experiments,
results/dryrun_baseline.json from repro.launch.dryrun) are loaded if present;
the table functions degrade to "run benchmarks.kws_experiments first"
markers instead of silently re-running multi-minute jobs.

Run:  PYTHONPATH=src python -m benchmarks.run
      PYTHONPATH=src python -m benchmarks.run --imc-fused
          (fused-vs-group-loop IMC layer benchmark, batch sweep {1,4,16};
           writes the per-layer and end-to-end hw_forward decisions/sec
           record to results/BENCH_imc_fused.json)
      PYTHONPATH=src python -m benchmarks.run --streaming
          (always-on serving: frame-incremental streaming vs full-window
           recompute, >=4 batched streams, plus the voice-activity-gated
           path on a --duty speech/silence mixture; writes decisions/sec,
           MACs and the duty-cycled uJ/decision to
           results/BENCH_streaming.json)
      PYTHONPATH=src python -m benchmarks.run --streaming --devices 2
          (adds the device-sharded serving section: the same total
           stream load on one device vs a ShardedStreamServer of N
           per-device slot pools, decisions/sec scaling from the max
           per-device compute wall into the 'sharded' section of
           BENCH_streaming.json; on CPU hosts the device count comes
           from --xla_force_host_platform_device_count, set before jax
           initializes; schema in docs/SHARDING.md)
      PYTHONPATH=src python -m benchmarks.run --streaming --compiled
          (adds the whole-tick compiled fast-path section: the same
           steady-state load served by the interpreted Python tick vs
           step_block's fused lax.scan dispatch — events asserted
           bit-identical, launch auditor in raise mode — decisions/sec
           speedup into the 'compiled' section of BENCH_streaming.json;
           schema in docs/SERVING.md)
      PYTHONPATH=src python -m benchmarks.run --customize --sessions 4
          (on-device customization as a serving workload: enrollment
           sessions driven through scheduler ticks — bias compensation +
           SGA fine-tuning as background jobs; writes the
           utterances-to-recovered-accuracy trajectory, the N-concurrent-
           session record with per-tick batched-launch accounting, the
           error-scaling ablation (fixed 1.375 vs dynamic ceil/floor) and
           the analytical uJ per fine-tune step to
           results/BENCH_customize.json; schemas in docs/ENERGY.md)
      PYTHONPATH=src python -m benchmarks.run --faults
          (fault-injected self-healing serving: drift / bit-flip / stuck
           scenarios through the canary health monitor, held-out accuracy
           before the fault, under the fault, and after the on-chip
           recompensation heal — drift and bit-flip heals must land
           within 2 points of the clean chip — plus detection/recovery
           latencies, recovery energy, and a crash-safety
           snapshot->restore record; writes results/BENCH_faults.json;
           schema in docs/RELIABILITY.md)
      PYTHONPATH=src python -m benchmarks.run --obs-overhead
          (observability tax: the gated streaming workload run
           telemetry-off vs fully instrumented — metrics registry +
           flight recorder + launch auditor in raise mode — asserting
           the decision streams are bit-identical, recording the
           per-tick overhead percentage and the auditor's launch
           accounting; writes results/BENCH_obs.json; schema in
           docs/OBSERVABILITY.md)

Any single-bench flag also takes ``--trace-out DIR``: the run goes under
``jax.profiler.trace(DIR, create_perfetto_trace=True)``, so DIR gets a
Perfetto timeline of the host spans and the device's ops
(docs/OBSERVABILITY.md).

Every ``BENCH_*.json`` goes through one shared atomic writer
(:func:`_write_bench`): tmp + fsync + rename like
``repro.checkpoint.profiles.ProfileStore``, stamped with a ``bench``
header ``{name, schema_version, regen}`` so partially-written artifacts
can't be published and every record names the command that regenerates
it.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results")


def _load(name):
    path = os.path.join(RESULTS, name)
    if os.path.exists(path):
        with open(path) as f:
            return json.load(f)
    return None


def _row(name, us, derived):
    print(f"{name},{us},{derived}")


# schema_version per artifact: bump when a bench's JSON layout changes
# incompatibly (keys removed/renamed), not when keys are added
_BENCH_SCHEMAS = {
    "BENCH_imc_fused.json": 1,
    "BENCH_streaming.json": 1,
    "BENCH_customize.json": 1,
    "BENCH_faults.json": 1,
    "BENCH_obs.json": 1,
}


def _write_bench(report, out_path, default_name, regen):
    """The single write path for every ``BENCH_*.json``.

    Atomic (tmp + fsync + rename, the ``ProfileStore`` idiom) so a
    crash mid-dump can't publish a truncated artifact, and stamped with
    a deterministic ``bench`` header — artifact name, schema version,
    and the exact command that regenerates it.  No timestamps: reruns
    on identical results diff clean.  Returns the path written."""
    if out_path is None:
        out_path = os.path.normpath(os.path.join(RESULTS, default_name))
    if os.path.dirname(out_path):
        os.makedirs(os.path.dirname(out_path), exist_ok=True)
    stamped = {"bench": {
        "name": os.path.splitext(default_name)[0],
        "schema_version": _BENCH_SCHEMAS[default_name],
        "regen": regen,
    }}
    stamped.update(report)
    tmp = f"{out_path}.tmp"
    with open(tmp, "w") as f:
        json.dump(stamped, f, indent=2)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, out_path)
    return out_path


# ---------------------------------------------------------------------------
# Paper tables
# ---------------------------------------------------------------------------


def table2_model() -> None:
    """Paper Table II: ideal-model accuracy / parameters / model size."""
    r = _load("kws_results.json")
    if not r:
        _row("table2_model", "", "MISSING:run benchmarks.kws_experiments")
        return
    t = r["table2"]
    _row("table2_accuracy", "", f"{t['accuracy']:.4f}(paper:0.9083)")
    _row("table2_parameters", "", f"{t['parameters']}(paper:125K)")
    _row("table2_model_bits", "", f"{t['model_bits']}(paper:171K)")


def table3_hw_constraints() -> None:
    """Paper Table III: ideal -> FC-quant -> BN-constraints -> +noise ->
    +compensation -> +fine-tune."""
    r = _load("kws_results.json")
    if not r:
        _row("table3_hw_constraints", "",
             "MISSING:run benchmarks.kws_experiments")
        return
    t = r["table3"]
    for key in ("ideal", "fc_quantized", "bn_constraints", "mav_sa_noise",
                "bias_compensation", "compensation_finetune"):
        _row(f"table3_{key}", "",
             f"{t[key]:.4f}(paper:{t['paper'][key]:.4f})")


def table4_customization() -> None:
    """Paper Table IV: customization ablation on the personal set."""
    r = _load("kws_results.json")
    if not r:
        _row("table4_customization", "",
             "MISSING:run benchmarks.kws_experiments")
        return
    t = r["table4"]
    _row("table4_before_customization", "",
         f"{t['before_customization']:.4f}")
    for key in ("baseline_fp", "quantized_naive", "error_scaling", "es_sga",
                "es_sga_rgp"):
        _row(f"table4_{key}", "",
             f"{t[key]:.4f}(paper:{t['paper'][key]:.4f})")


def table5_energy() -> None:
    """Paper Fig 14/Table V: energy/latency/TOPS-W analytical chip model."""
    from repro.core.energy import kws_chip_report, training_energy_j
    from repro.models.kws import PAPER_KWS, layer_stats

    stats = layer_stats(PAPER_KWS)
    for freq, tag in ((1e6, "1MHz"), (1e8, "100MHz")):
        rep = kws_chip_report(stats, freq_hz=freq)
        _row(f"table5_energy_per_decision_{tag}", "",
             f"{rep.energy_j_per_decision * 1e6:.2f}uJ"
             + ("(paper:~14.3uJ)" if tag == "1MHz" else "(paper:~4.5uJ)"))
        _row(f"table5_power_{tag}", "",
             f"{rep.power_w * 1e6:.1f}uW"
             + ("(paper:89.5uW)" if tag == "1MHz" else "(paper:2833uW)"))
        _row(f"table5_tops_per_w_{tag}", "",
             f"{rep.tops_per_w:.1f}(paper:23.6-68)")
    _row("table5_latency", "", f"{kws_chip_report(stats).latency_s*1e3:.0f}ms"
         "(paper:160ms@1MHz)")
    e_train = training_energy_j(num_epochs=1, macs_per_epoch=90 * 586 * 10,
                                lut_ops=90 * 10, div_ops=90 * 10,
                                sram_bits=90 * 576 * 8)
    _row("table5_training_energy_per_epoch", "", f"{e_train*1e6:.1f}uJ")


def dryrun_summary() -> None:
    """Deliverable e/g: the 40-cell x 2-mesh dry-run + roofline terms."""
    rs = _load("dryrun_baseline.json")
    if not rs:
        _row("dryrun", "", "MISSING:run repro.launch.dryrun")
        return
    ok = sum(1 for r in rs if r.get("status") == "ok")
    skip = sum(1 for r in rs if r.get("status") == "skip")
    err = sum(1 for r in rs if r.get("status") == "error")
    _row("dryrun_cells", "", f"ok={ok};skip={skip};error={err}")
    for r in rs:
        if r.get("status") != "ok" or r.get("multi_pod"):
            continue
        ro = r["roofline"]
        _row(f"roofline_{r['arch']}_{r['shape']}", "",
             f"dom={ro['dominant']};comp={ro['compute_s']:.4f}s;"
             f"mem={ro['memory_s']:.4f}s;coll={ro['collective_s']:.4f}s;"
             f"frac={ro['roofline_fraction']:.3f};"
             f"frac_serial={ro.get('roofline_fraction_serial', 0):.3f}")


# ---------------------------------------------------------------------------
# Kernel microbenchmarks (CPU interpret mode: correctness-grade timings)
# ---------------------------------------------------------------------------


def _time_us(fn, *args, iters: int = 5) -> float:
    import jax
    fn(*args)                      # compile + warmup
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def kernel_bench() -> None:
    """us/call for each Pallas kernel vs its jnp oracle (interpret mode on
    CPU measures dispatch+semantics, not TPU perf — the BlockSpecs encode
    the TPU tiling; see DESIGN.md §3)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import default_interpret
    from repro.kernels.imc_mav import ops as mav_ops
    from repro.kernels.imc_mav.ref import imc_mav_ref
    from repro.kernels.int8_matmul.int8_matmul import int8_matmul
    from repro.kernels.int8_matmul.ref import int8_matmul_ref
    from repro.kernels.sga_update.sga_update import sga_update
    from repro.kernels.sga_update.ref import sga_update_ref

    # independent keys per operand: reusing one key correlates x with w
    # (and xq with wq), which skews the agree/disagree statistics the ±1
    # and int8 kernels are exercised on
    kx, kw, kxq, kwq, kwv, kgv = jax.random.split(jax.random.PRNGKey(0), 6)
    x = jnp.where(jax.random.bernoulli(kx, 0.5, (512, 128)), 1.0, -1.0)
    w = jnp.where(jax.random.bernoulli(kw, 0.5, (128, 128)), 1.0, -1.0)
    bias = jnp.zeros((128,))
    flip = jnp.ones((128,))
    us = _time_us(lambda: mav_ops.mav_matmul(x, w, bias, flip))
    us_ref = _time_us(jax.jit(lambda: imc_mav_ref(x, w, bias, flip)))
    _row("kernel_imc_mav_512x128x128", f"{us:.0f}", f"ref_us={us_ref:.0f}")

    xq = jax.random.randint(kxq, (512, 128), -127, 128, jnp.int8)
    wq = jax.random.randint(kwq, (128, 128), -127, 128, jnp.int8)
    bq = jnp.zeros((128,), jnp.int32)
    interpret = default_interpret()
    us = _time_us(lambda: int8_matmul(xq, wq, bq, shift=7,
                                      interpret=interpret))
    us_ref = _time_us(jax.jit(lambda: int8_matmul_ref(xq, wq, bq, shift=7)))
    _row("kernel_int8_matmul_512x128x128", f"{us:.0f}",
         f"ref_us={us_ref:.0f}")

    n = 8192
    wv = jax.random.uniform(kwv, (n,), minval=-1, maxval=1)
    gv = jax.random.normal(kgv, (n,)) * 0.01
    av = jnp.zeros((n,))
    us = _time_us(lambda: sga_update(wv, gv, av, lr=1 / 16, g_th=0.078125,
                                     interpret=interpret))
    us_ref = _time_us(jax.jit(
        lambda: sga_update_ref(wv, gv, av, 1 / 16, 0.078125)))
    _row("kernel_sga_update_8192", f"{us:.0f}", f"ref_us={us_ref:.0f}")


# ---------------------------------------------------------------------------
# Fused IMC layer: per-layer + end-to-end hw_forward decisions/sec
# ---------------------------------------------------------------------------


def _grouploop_hw_forward(hw, x, cfg):
    """End-to-end seed baseline: one tiny pallas_call per conv group
    (conv_mav loop) with the digital shuffle/pool in jnp — the path the
    fused kernel replaces."""
    import jax.numpy as jnp
    from repro.core import imc
    from repro.core.binary import channel_shuffle, or_maxpool
    from repro.core.quantize import ACT_Q
    from repro.kernels.imc_mav import ops as mav_ops

    h = x[..., None]
    for i in range(cfg.num_conv_layers):
        name = f"conv{i}"
        if i == 0:
            counts = imc.binary_group_conv_counts(h, hw.w_bin[name],
                                                  groups=1,
                                                  stride=cfg.strides[i])
            h = imc.mav_sa(counts, hw.bias[name], hw.flip[name])
        else:
            h = mav_ops.conv_mav(h, hw.w_bin[name], hw.bias[name],
                                 hw.flip[name], groups=cfg.groups(i),
                                 stride=cfg.strides[i])
        h = channel_shuffle(h, cfg.groups(i))
        if cfg.pools[i] > 1:
            h = or_maxpool(h, cfg.pools[i], axis=1)
    feats = ACT_Q.quantize(jnp.mean(h, axis=1))
    return feats @ hw.fc_w + hw.fc_b


def imc_fused_bench(out_path: str | None = None, sample_len: int = 16_000,
                    iters: int = 3,
                    batches: tuple = (1, 4, 16)) -> dict:
    """Per-layer and end-to-end hw_forward timings, fused grouped kernel vs
    the seed per-group-loop path; emits BENCH_imc_fused.json so the perf
    trajectory is machine-readable from this PR on.

    The end-to-end section sweeps ``batches`` so the fused kernel's
    M-tiling amortization (weights stay VMEM-resident across the batch
    grid) is visible, not just batch=1."""
    import jax
    import jax.numpy as jnp
    from repro.core import imc
    from repro.core.binary import channel_shuffle, or_maxpool
    from repro.kernels import default_interpret
    from repro.kernels.imc_mav import ops as mav_ops
    from repro.models import kws as m
    from repro.obs import span

    cfg = m.KWSConfig(sample_len=sample_len)
    params = m.init_params(jax.random.PRNGKey(0), cfg)
    state = m.init_state(cfg)
    hw = m.fold_params(params, state, cfg)
    x = jax.random.uniform(jax.random.PRNGKey(1), (1, sample_len),
                           minval=-1, maxval=1)

    report = {
        "backend": jax.default_backend(),
        "interpret": bool(default_interpret()),
        "sample_len": sample_len,
        "batches": list(batches),
        "per_layer": [],
        "end_to_end": {},
    }

    # per-layer: walk the net, timing each IMC layer both ways on its real
    # input shape (baseline = conv_mav group loop + jnp shuffle/pool)
    h = x[..., None]
    for i in range(cfg.num_conv_layers):
        name = f"conv{i}"
        g, pool = cfg.groups(i), cfg.pools[i]
        if i == 0:
            counts = imc.binary_group_conv_counts(h, hw.w_bin[name],
                                                  groups=1,
                                                  stride=cfg.strides[i])
            h = imc.mav_sa(counts, hw.bias[name], hw.flip[name])
            h = channel_shuffle(h, g)
            if pool > 1:
                h = or_maxpool(h, pool, axis=1)
            continue

        def baseline(h=h, name=name, g=g, pool=pool, i=i):
            o = mav_ops.conv_mav(h, hw.w_bin[name], hw.bias[name],
                                 hw.flip[name], groups=g,
                                 stride=cfg.strides[i])
            o = channel_shuffle(o, g)
            return or_maxpool(o, pool, axis=1) if pool > 1 else o

        def fused(h=h, name=name, g=g, pool=pool, i=i):
            return mav_ops.fused_conv_mav(h, hw.w_bin[name], hw.bias[name],
                                          hw.flip[name], groups=g,
                                          stride=cfg.strides[i], pool=pool)

        with span(f"grouploop.{name}"):
            us_base = _time_us(baseline, iters=iters)
        with span(f"fused.{name}"):
            us_fused = _time_us(fused, iters=iters)
        cog = cfg.channels[i] // g
        layout = imc.make_group_pack_layout(g, cog, cfg.kernels[i],
                                            cfg.channels_per_group)
        report["per_layer"].append({
            "name": name, "groups": g, "cog": cog,
            "packs": layout.packs, "groups_per_block": layout.gpb,
            "grouploop_us": round(us_base, 1),
            "fused_us": round(us_fused, 1),
            "speedup": round(us_base / us_fused, 3),
        })
        _row(f"imc_fused_{name}", f"{us_fused:.0f}",
             f"grouploop_us={us_base:.0f};x{us_base / us_fused:.2f}")
        h = fused()

    hw_packed = m.pack_hw_params(hw, cfg)
    for b in batches:
        xb = jax.random.uniform(jax.random.PRNGKey(2), (b, sample_len),
                                minval=-1, maxval=1)
        with span(f"hw_forward.batch_{b}"):
            us_loop = _time_us(lambda: _grouploop_hw_forward(hw, xb, cfg),
                               iters=iters)
            us_fused = _time_us(
                lambda: m.hw_forward(hw_packed, xb, cfg,
                                     use_kernel=True)[0], iters=iters)
            us_jnp = _time_us(
                lambda: m.hw_forward(hw, xb, cfg, use_kernel=False)[0],
                iters=iters)
        report["end_to_end"][f"batch_{b}"] = {
            "batch": b,
            "grouploop_us": round(us_loop, 1),
            "fused_us": round(us_fused, 1),
            "jnp_us": round(us_jnp, 1),
            "speedup_vs_grouploop": round(us_loop / us_fused, 3),
            "decisions_per_sec_fused": round(b * 1e6 / us_fused, 2),
            "decisions_per_sec_grouploop": round(b * 1e6 / us_loop, 2),
        }
        _row(f"imc_fused_hw_forward_b{b}", f"{us_fused:.0f}",
             f"grouploop_us={us_loop:.0f};jnp_us={us_jnp:.0f};"
             f"decisions_per_s={b * 1e6 / us_fused:.2f}")

    out_path = _write_bench(
        report, out_path, "BENCH_imc_fused.json",
        "PYTHONPATH=src python -m benchmarks.run --imc-fused")
    _row("imc_fused_json", "", out_path)
    return report


# ---------------------------------------------------------------------------
# Streaming serving: frame-incremental vs full-recompute decisions/sec
# ---------------------------------------------------------------------------


def streaming_bench(out_path: str | None = None, sample_len: int = 2_000,
                    hop: int = 256, slots: int = 4, hops: int = 6,
                    use_kernel: bool = True, duty: float = 0.2,
                    devices: int = 1, shard_hop: int = 512,
                    compiled: bool = False, compiled_ticks: int = 96,
                    compiled_block: int = 32) -> dict:
    """Always-on serving benchmark: ``slots`` concurrent streams batched
    through the StreamServer, frame-incremental (streaming) vs full-window
    recompute per hop, plus the voice-activity-gated path on a
    speech/silence mixture at ``duty`` speech duty cycle.  Records
    decisions/sec, per-decision MAC counts, the analytical uJ/decision for
    both ungated paths and the duty-cycled gated uJ/decision (the
    always-on power story: gated hops charge leakage + VAD only) into
    BENCH_streaming.json.

    Timing protocol: servers are stepped once past admission and once past
    the jit trace, then ``hops`` steady-state batched hops are timed; the
    gated run times the whole mixture drain instead (its per-step work is
    intentionally non-uniform).

    With ``devices > 1`` (the ``--devices N`` flag; ``main()`` sets
    ``--xla_force_host_platform_device_count`` before jax initializes) a
    ``sharded`` section is appended: the SAME total stream load —
    ``devices x slots`` streams at ``shard_hop`` — served by one
    N-wide-slot single-device server vs a ``ShardedStreamServer`` of N
    pools.  Both sides report the server-measured batched-compute wall
    (``hop_wall_s``: block-until-ready around every fused launch); the
    sharded side's headline wall is the MAX per-device wall, which is
    what bounds a real fleet where devices compute concurrently — host
    wall-clock is recorded alongside for honesty (on a single-core CI
    host the pools necessarily run sequentially, so host wall shows no
    speedup; the per-device walls are the hardware-truth quantity).
    ``shard_hop`` defaults to 512 rather than inheriting ``hop``: the
    section fixes TOTAL work while varying per-device batch, so it needs
    a regime where per-launch cost scales with batch (at small hops the
    CPU interpreter's fixed per-launch overhead dominates and batching
    is nearly free — splitting such a load across devices measures
    overhead, not compute).

    With ``compiled=True`` (the ``--compiled`` flag) a ``compiled``
    section is appended: the SAME steady-state load served by the
    interpreted Python tick vs the whole-tick compiled fast path
    (``repro.serving.compiled`` — ``compiled_block`` ticks fused into
    one jitted ``lax.scan`` dispatch, ``step_block``).  Events are
    asserted bit-identical in-bench and the candidate runs with the
    launch auditor in raise mode, so the recorded speedup is over a
    PROVEN-equal run.  The section uses the jnp reference path
    (``use_kernel=False``) at a small hop: tick fusion amortizes
    per-tick dispatch + host scheduling, the accelerator-relevant
    quantity; in Pallas interpret mode the per-scan-step kernel
    interpretation cost dominates both sides and the same fusion
    measures the interpreter instead."""
    import jax
    import numpy as np_
    from repro.core import energy
    from repro.kernels import default_interpret
    from repro.models import kws as m
    from repro.serving import StreamServer, VADConfig, streaming_layer_stats

    cfg = m.KWSConfig(sample_len=sample_len)
    params = m.init_params(jax.random.PRNGKey(0), cfg)
    state = m.init_state(cfg)
    hw = m.fold_params(params, state, cfg, pack=True)

    rng = np_.random.default_rng(0)
    total = sample_len + (hops + 2) * hop
    streams = {f"s{i}": rng.uniform(-1, 1, size=total).astype(np_.float32)
               for i in range(slots)}

    def run(streaming: bool) -> dict:
        srv = StreamServer(hw, cfg, hop=hop, slots=slots,
                           use_kernel=use_kernel, streaming=streaming)
        for sid, audio in streams.items():
            srv.submit(sid, audio)
            srv.finish(sid)
        srv.step()                         # admissions (window 0)
        srv.step()                         # first hop: jit trace, untimed
        t0 = time.perf_counter()
        n = 0
        for _ in range(hops):
            n += len(srv.step())
        dt = time.perf_counter() - t0
        assert n == slots * hops, (n, slots, hops)
        return {
            "decisions": n,
            "wall_s": round(dt, 4),
            "us_per_decision": round(dt / n * 1e6, 1),
            "decisions_per_sec": round(n / dt, 2),
        }

    def run_gated() -> dict:
        """Speech/silence mixture: each stream is loud for the first
        ``duty`` fraction of its post-window hops (one utterance burst)
        and near-silent after; the VAD gates the silent tail so only
        ~duty of the hops run the IMC stack."""
        n_hops = max(hops * 4, 20)         # long tail: duty dominates
        n_speech = max(1, round(duty * n_hops))
        mix = {}
        for i in range(slots):
            wav = (1e-4 * rng.standard_normal(sample_len + n_hops * hop)
                   ).astype(np_.float32)
            loud = sample_len + n_speech * hop
            wav[:loud] = rng.uniform(-1, 1, size=loud)
            mix[f"g{i}"] = wav
        srv = StreamServer(hw, cfg, hop=hop, slots=slots,
                           use_kernel=use_kernel,
                           vad=VADConfig(threshold_on_db=-40.0,
                                         threshold_off_db=-50.0,
                                         wake_margin=1, hang=0))
        for sid, audio in mix.items():
            srv.submit(sid, audio)
            srv.finish(sid)
        t0 = time.perf_counter()
        n = len(srv.drain())
        dt = time.perf_counter() - t0
        s = srv.stats()
        return {
            "hops_per_stream": n_hops,
            "duty_cycle_target": duty,
            "duty_cycle_measured": s["duty_cycle"],
            "speech_hops": s["speech_hops"],
            "gated_hops": s["gated_hops"],
            "decisions": n,
            "wall_s": round(dt, 4),
            "decisions_per_sec": round(n / dt, 2),
        }

    from repro.models.kws import layer_stats
    from repro.serving import make_stream_geometry
    geom = make_stream_geometry(cfg, hop)
    stats_off = layer_stats(cfg)
    stats_str = streaming_layer_stats(cfg, geom)
    macs_off = sum(s["macs"] for s in stats_off)
    macs_str = sum(s["macs"] for s in stats_str)

    def run_sharded() -> dict:
        """Fixed total load, one device vs N pools: device-parallel
        decisions/sec from the max per-device compute wall."""
        from repro.serving import ShardedStreamServer
        total = devices * slots
        s_total = sample_len + (hops + 2) * shard_hop
        s_streams = {f"d{i}": rng.uniform(-1, 1, size=s_total)
                     .astype(np_.float32) for i in range(total)}

        def protocol(srv, submit, walls_of):
            for sid, audio in s_streams.items():
                submit(sid, audio)
                srv.finish(sid)
            srv.step()                     # admissions (window 0)
            srv.step()                     # first hop: jit trace, untimed
            base = walls_of()
            t0 = time.perf_counter()
            n = 0
            for _ in range(hops):
                n += len(srv.step())
            host = time.perf_counter() - t0
            assert n == total * hops, (n, total, hops)
            walls = [w - b for w, b in zip(walls_of(), base)]
            return n, host, walls

        one = StreamServer(hw, cfg, hop=shard_hop, slots=total,
                           use_kernel=use_kernel)
        n1, host1, (wall1,) = protocol(one, one.submit,
                                       lambda: [one._hop_wall_s])
        sh = ShardedStreamServer(hw, cfg, hop=shard_hop, devices=devices,
                                 slots=slots, use_kernel=use_kernel)
        nN, hostN, wallsN = protocol(
            sh, sh.submit, lambda: [p._hop_wall_s for p in sh.pools])
        dev_wall = max(wallsN)
        scaling = (nN / dev_wall) / (n1 / wall1)
        return {
            "devices": devices,
            "backend_devices": len(jax.devices()),
            "hop": shard_hop,
            "slots_per_device": slots,
            "streams": total,
            "timed_hops": hops,
            "metric": ("decisions/sec from the batched-compute wall "
                       "(hop_wall_s); sharded uses max per-device wall "
                       "= fleet throughput with devices computing "
                       "concurrently; host_wall_s includes the "
                       "sequential host dispatch"),
            "single_device": {
                "decisions": n1,
                "compute_wall_s": round(wall1, 4),
                "host_wall_s": round(host1, 4),
                "decisions_per_sec": round(n1 / wall1, 2),
            },
            "sharded": {
                "decisions": nN,
                "per_device_wall_s": [round(w, 4) for w in wallsN],
                "max_device_wall_s": round(dev_wall, 4),
                "host_wall_s": round(hostN, 4),
                "decisions_per_sec": round(nN / dev_wall, 2),
            },
            "scaling_decisions_per_sec": round(scaling, 3),
            "regen": ("PYTHONPATH=src python -m benchmarks.run "
                      f"--streaming --devices {devices}"),
        }

    def run_compiled() -> dict:
        """Python tick vs compiled whole-tick block on the same traffic:
        identical decisions asserted, auditor in raise mode, speedup
        from host wall over the timed steady-state ticks."""
        from repro.serving import (CompiledTickConfig, ObsConfig,
                                   StreamServer as _Srv)
        # one always-on stream at the paper's native hop: the deployment
        # regime the block fusion targets — per-tick device work is tiny,
        # so the Python tick's K host->device round trips are the cost
        # the scan amortizes away
        c_hop, c_slots = 64, 1
        warm = 2 * compiled_block          # untimed: trace + cache warm
        c_total = sample_len + (compiled_ticks + warm + 4) * c_hop
        c_streams = {f"c{i}": rng.uniform(-1, 1, size=c_total)
                     .astype(np_.float32) for i in range(c_slots)}

        def drive(fast: bool):
            srv = _Srv(hw, cfg, hop=c_hop, slots=c_slots,
                       use_kernel=False, obs=ObsConfig(audit="raise"),
                       compiled=(CompiledTickConfig(block=compiled_block)
                                 if fast else None))
            for sid, audio in c_streams.items():
                srv.submit(sid, audio)
                srv.finish(sid)
            ev = list(srv.step())          # admissions (window 0)
            while srv._steps < 1 + warm:   # untimed warmup
                ev += (srv.step_block(max_ticks=1 + warm - srv._steps)
                       if fast else srv.step())
            end = 1 + warm + compiled_ticks
            t0 = time.perf_counter()
            n = 0
            while srv._steps < end:
                evs = (srv.step_block(max_ticks=end - srv._steps)
                       if fast else srv.step())
                n += len(evs)
                ev += evs
            dt = time.perf_counter() - t0
            return ev, n, dt, srv

        def best_of(fast: bool, reps: int = 3):
            # deterministic traffic -> identical events every repeat;
            # best-of wall filters host scheduling noise out of the ratio
            kept = None
            for _ in range(reps):
                ev, n, dt, srv = drive(fast)
                if kept is None or dt < kept[2]:
                    kept = (ev, n, dt, srv)
            return kept

        ev_py, n_py, dt_py, _srv = best_of(False)
        ev_c, n_c, dt_c, srv_c = best_of(True)
        # the differential gate, in-bench: the timed runs themselves are
        # bit-identical, full event stream from tick 0 on
        assert ev_py == ev_c, "compiled tick diverged from Python tick"
        assert n_py == n_c == c_slots * compiled_ticks, (n_py, n_c)
        audit = srv_c.auditor.stats()
        assert audit["violations"] == 0    # raise mode would have thrown
        speedup = (n_c / dt_c) / (n_py / dt_py)
        return {
            "hop": c_hop,
            "slots": c_slots,
            "block": compiled_block,
            "timed_ticks": compiled_ticks,
            "use_kernel": False,
            "metric": ("decisions/sec from best-of-3 host wall over the "
                       "timed steady-state ticks; both sides serve the same "
                       "traffic and their event streams are asserted "
                       "bit-identical before the speedup is recorded; "
                       "the compiled side runs with the launch auditor "
                       "in raise mode (one block = the tick's entire "
                       "compute, one fused launch per IMC layer)"),
            "python_tick": {
                "decisions": n_py,
                "wall_s": round(dt_py, 4),
                "decisions_per_sec": round(n_py / dt_py, 2),
            },
            "compiled_tick": {
                "decisions": n_c,
                "wall_s": round(dt_c, 4),
                "decisions_per_sec": round(n_c / dt_c, 2),
                "blocks": srv_c._compiled_blocks,
                "ticks": srv_c._compiled_ticks,
            },
            "speedup_decisions_per_sec": round(speedup, 3),
            "events_bit_identical": True,
            "audit": {"mode": "raise",
                      "violations": audit["violations"],
                      "compiled_calls": audit["calls"]["compiled"]},
            "regen": ("PYTHONPATH=src python -m benchmarks.run "
                      "--streaming --compiled"
                      + (f" --devices {devices}" if devices > 1 else "")),
        }

    res_stream = run(streaming=True)
    res_recomp = run(streaming=False)
    res_gated = run_gated()
    res_sharded = run_sharded() if devices > 1 else None
    res_compiled = run_compiled() if compiled else None
    # charge the energy at the duty cycle the run actually measured (the
    # VAD's hangover/EMA tail makes it slightly above the target), so the
    # recorded reduction describes the attached run
    measured_duty = res_gated["duty_cycle_measured"]
    gated_energy = {
        k: round(v, 4) if isinstance(v, float) else v
        for k, v in energy.gated_energy_summary(
            stats_off, stats_str, hop_samples=hop,
            duty_cycle=measured_duty if measured_duty is not None
            else duty).items()
    }
    res_gated["energy"] = gated_energy
    speedup = (res_stream["decisions_per_sec"]
               / res_recomp["decisions_per_sec"])
    report = {
        "backend": jax.default_backend(),
        "interpret": bool(default_interpret()),
        "use_kernel": use_kernel,
        "window": sample_len,
        "hop": hop,
        "hop_over_window": round(hop / sample_len, 4),
        "slots": slots,
        "timed_hops": hops,
        "streaming": res_stream,
        "recompute": res_recomp,
        "gated": res_gated,
        "speedup_decisions_per_sec": round(speedup, 3),
        "macs_per_decision": {
            "offline": macs_off,
            "streaming": macs_str,
            "ratio": round(macs_str / macs_off, 4),
        },
        "energy": {
            k: round(v, 4) if isinstance(v, float) else v
            for k, v in energy.streaming_energy_summary(
                stats_off, stats_str).items()
        },
    }
    if res_compiled is not None:
        report["compiled"] = res_compiled
        _row("compiled_tick_speedup", "",
             f"x{res_compiled['speedup_decisions_per_sec']:.2f};"
             f"block={res_compiled['block']};"
             f"py={res_compiled['python_tick']['decisions_per_sec']};"
             f"compiled="
             f"{res_compiled['compiled_tick']['decisions_per_sec']}")
    if res_sharded is not None:
        report["sharded"] = res_sharded
        _row("sharded_scaling_decisions_per_sec", "",
             f"x{res_sharded['scaling_decisions_per_sec']:.2f}"
             f"@{devices}dev;"
             f"single={res_sharded['single_device']['decisions_per_sec']};"
             f"sharded={res_sharded['sharded']['decisions_per_sec']}")
    _row("streaming_decisions_per_sec",
         f"{res_stream['us_per_decision']:.0f}",
         f"recompute_us={res_recomp['us_per_decision']:.0f};"
         f"x{speedup:.2f};slots={slots};hop/window={hop / sample_len:.3f}")
    _row("streaming_macs_ratio", "", f"{macs_str / macs_off:.4f}")
    _row("streaming_gated_uj_per_decision", "",
         f"{gated_energy['gated_uj_per_decision']:.3f}uJ"
         f"@duty{gated_energy['duty_cycle']:.2f};"
         f"ungated={gated_energy['ungated_uj_per_decision']:.3f}uJ;"
         f"x{gated_energy['reduction_vs_ungated']:.2f}")

    out_path = _write_bench(
        report, out_path, "BENCH_streaming.json",
        "PYTHONPATH=src python -m benchmarks.run --streaming")
    _row("streaming_json", "", out_path)
    return report


def customize_bench(out_path: str | None = None, sample_len: int = 2_000,
                    hop: int = 256, slots: int = 4,
                    utts_per_class: tuple = (1, 3),
                    epochs: int = 120, sessions: int = 4) -> dict:
    """On-device customization as a serving workload: enrollment sessions
    driven through the StreamServer's scheduler ticks (bias compensation
    + error-scaled/SGA fine-tuning as background jobs), recording the
    utterances-to-recovered-accuracy trajectory and the analytical uJ per
    fine-tune step into BENCH_customize.json.

    Three sections land in the JSON: the single-session recovery
    trajectory over ``utts_per_class``; a ``--sessions N`` concurrent
    phase — N interleaved enrollment sessions plus a live inference
    stream through ONE StreamServer, with per-tick batched-call
    accounting proving the one-fused-launch-per-layer invariant holds on
    mixed inference + multi-session learning ticks (per-tick launches
    never scale with N); and the error-scaling ablation — the chip's
    fixed 1.375 factor vs the dynamic Eq-2 ceil exponent (which lands the
    largest error at/above the Q1.7 rail and can stall) vs the floored /
    clamped variants (``OnChipTrainConfig.error_scale_mode``).

    Uses the cached trained model (results/kws_model.pkl) when present —
    the recovery numbers are meaningful there; otherwise an untrained fold
    exercises the identical mechanics.  The 'before' row is the chip with
    static MAV offsets and no compensation (the Table IV premise)."""
    # the concurrent record (>= 2 sessions) is part of the JSON schema the
    # docs reference (results/BENCH_customize.json#concurrent_sessions.*,
    # CI-checked by scripts/check_docs.py) — reject a sessions-less regen
    # up front, before the multi-minute trajectory runs
    if sessions < 2:
        raise ValueError("--sessions must be >= 2: the concurrent-session "
                         "record is part of the BENCH_customize.json "
                         "schema the docs reference")
    import pickle

    import jax
    import jax.numpy as jnp
    from repro.core import imc
    from repro.core.onchip_training import (OnChipTrainConfig,
                                            head_accuracy)
    from repro.data import audio
    from repro.kernels import default_interpret
    from repro.models import kws as m
    from repro.serving import CustomizeConfig, StreamServer
    from repro.training import kws as tr

    cfg = m.KWSConfig(sample_len=sample_len)
    pkl = os.path.join(RESULTS, "kws_model.pkl")
    trained = os.path.exists(pkl) and sample_len == 2_000
    if trained:
        with open(pkl, "rb") as f:
            params, state = pickle.load(f)
        params = jax.tree_util.tree_map(jnp.asarray, params)
        state = m.KWSState(*[jax.tree_util.tree_map(jnp.asarray, s)
                             for s in state])
    else:
        params = m.init_params(jax.random.PRNGKey(0), cfg)
        state = m.init_state(cfg)
    hw = m.fold_params(params, state, cfg, pack=True)
    chans = {f"conv{i}": cfg.channels[i]
             for i in range(1, cfg.num_conv_layers)}
    offs = imc.sample_chip_offsets(jax.random.PRNGKey(7), chans,
                                   imc.IMCNoiseParams(mav_offset_std=8.0))

    n_max = max(utts_per_class)
    (xp_tr, yp_tr), (xp_te, yp_te) = audio.make_personal(
        train_per_class=n_max, test_per_class=4, length=sample_len,
        accent_shift=0.18)
    before = tr.evaluate_hw(hw, xp_te, yp_te, cfg, chip_offsets=offs)

    # the chip's error-scaling mode: fixed 1.375 (shift-add friendly, §V-C)
    tcfg = OnChipTrainConfig(epochs=epochs, fixed_error_scale=1.375)
    trajectory = []
    uj = None
    for n in utts_per_class:
        srv = StreamServer(hw, cfg, hop=hop, slots=slots, use_kernel=True,
                           chip_offsets=offs)
        sess = srv.customize(f"user{n}", CustomizeConfig(
            train=tcfg, epochs_per_tick=24, layers_per_tick=5))
        # n utterances per keyword, in enrollment-UX order
        by_class = {}
        for wav, lab in zip(xp_tr, yp_tr):
            by_class.setdefault(int(lab), []).append(wav)
        t0 = time.perf_counter()
        for c, wavs in sorted(by_class.items()):
            for wav in wavs[:n]:
                sess.enroll(c, wav)
        sess.finish_enrollment()
        steps = 0
        while not sess.done and steps < 5000:
            srv.step()
            steps += 1
        assert sess.done, sess.phase
        wall = time.perf_counter() - t0
        res = sess.result
        hw_n = sess.refolded()
        f_te = tr.hw_features(hw_n, xp_te, cfg, chip_offsets=offs)
        acc = float(head_accuracy(jnp.asarray(f_te), jnp.asarray(yp_te),
                                  jnp.asarray(res.fc_w),
                                  jnp.asarray(res.fc_b), tcfg))
        uj = res.energy
        trajectory.append({
            "utterances_per_class": n,
            "utterances": res.n_utterances,
            "accuracy": round(acc, 4),
            "scheduler_ticks": steps,
            "wall_s": round(wall, 2),
            "train_history": res.history,
        })
        _row(f"customize_{n}_per_class", "",
             f"acc={acc:.4f};before={before:.4f};ticks={steps}")

    # -- concurrent sessions: N users enrolling at once, one server --------
    srv = StreamServer(hw, cfg, hop=hop, slots=sessions + 4,
                       use_kernel=True, chip_offsets=offs)
    rng = np.random.default_rng(3)
    live = rng.uniform(-1, 1, sample_len + 4000 * hop
                       ).astype(np.float32)
    srv.submit("live", live[:sample_len])
    pos = sample_len
    by_class = {}
    for wav, lab in zip(xp_tr, yp_tr):
        by_class.setdefault(int(lab), []).append(wav)
    sess_list = []
    for k in range(sessions):
        s = srv.customize(f"user{k}", CustomizeConfig(
            train=tcfg, epochs_per_tick=24, layers_per_tick=5))
        for c, wavs in sorted(by_class.items()):
            s.enroll(c, wavs[k % len(wavs)])
        s.finish_enrollment()
        sess_list.append(s)
    done_tick = [None] * sessions
    per_tick_calls = []
    t0 = time.perf_counter()
    ticks = 0
    while not all(s.done for s in sess_list) and ticks < 20_000:
        if pos < len(live):
            srv.submit("live", live[pos:pos + hop])
            pos += hop
        before_calls = (srv._init_calls + srv._hop_calls
                        + srv._replay_calls)
        srv.step()
        per_tick_calls.append(srv._init_calls + srv._hop_calls
                              + srv._replay_calls - before_calls)
        ticks += 1
        for k, s in enumerate(sess_list):
            if s.done and done_tick[k] is None:
                done_tick[k] = ticks
    wall = time.perf_counter() - t0
    assert all(s.done for s in sess_list), \
        [s.phase for s in sess_list]
    imc_layers = cfg.num_conv_layers - 1
    max_calls = max(per_tick_calls)
    # the invariant: per-tick fused launches never scale with the
    # number of sessions — at most one batched init wave plus one
    # batched hop per tick, each = one launch per IMC layer
    # (launch-per-call is trace-enforced in tests/test_customize.py)
    assert max_calls <= 2, (max_calls, sessions)
    per_session = []
    for k, s in enumerate(sess_list):
        e = s.result.energy
        per_session.append({
            "stream": f"user{k}",
            "utterances": s.result.n_utterances,
            "epochs": s.result.epochs,
            "ticks_to_done": done_tick[k],
            "final_train_accuracy":
                s.history[-1]["train_accuracy"] if s.history else None,
            "uj_per_finetune_step":
                round(e["uj_per_finetune_step"], 4),
            "total_uj": round(e["total_uj"], 4),
        })
    total_calls = sum(per_tick_calls)
    concurrent = {
        "sessions": sessions,
        "slots": sessions + 4,
        "ticks": ticks,
        "wall_s": round(wall, 2),
        "live_decisions": srv._decisions,
        "learn_hops": srv.stats()["learn_hops"],
        "imc_layers": imc_layers,
        "batched_calls_total": total_calls,
        "fused_launches_total": total_calls * imc_layers,
        "max_batched_calls_per_tick": max_calls,
        "one_launch_per_layer_per_call": True,
        "per_session": per_session,
    }
    _row("customize_concurrent_sessions", "",
         f"n={sessions};ticks={ticks};"
         f"max_calls_per_tick={max_calls};"
         f"launches={total_calls * imc_layers}")

    # -- error-scaling ablation: fixed 1.375 vs dynamic ceil/floor ---------
    # run on the §IV-B-compensated chip (the real pipeline: calibrate ->
    # features -> fine-tune) — this is where the ROADMAP's Q1.7-rail
    # stall was observed: the dynamic ceil exponent lands the largest
    # error at/above the rail every batch and stalls on weakly separated
    # features, while the chip's fixed 1.375 recovers
    hw_comp = tr.calibrate_and_compensate(hw, xp_tr, offs, cfg)
    hwp, _ = m.as_hw_params(hw_comp)
    f_tr = tr.hw_features(hw_comp, xp_tr, cfg, chip_offsets=offs)
    f_te_a = tr.hw_features(hw_comp, xp_te, cfg, chip_offsets=offs)
    from repro.core.onchip_training import quantized_head_finetune
    ablation = {}
    for name, ocfg in {
        "fixed_1p375": OnChipTrainConfig(epochs=epochs,
                                         fixed_error_scale=1.375),
        "dynamic_ceil": OnChipTrainConfig(epochs=epochs),
        "dynamic_floor": OnChipTrainConfig(epochs=epochs,
                                           error_scale_mode="floor"),
        "dynamic_floor_clamp4": OnChipTrainConfig(
            epochs=epochs, error_scale_mode="floor",
            error_scale_max_exponent=4),
    }.items():
        w, b = quantized_head_finetune(
            jnp.asarray(f_tr), jnp.asarray(yp_tr), hwp.fc_w, hwp.fc_b,
            ocfg)
        tr_acc = float(head_accuracy(jnp.asarray(f_tr),
                                     jnp.asarray(yp_tr), w, b, ocfg))
        te_acc = float(head_accuracy(jnp.asarray(f_te_a),
                                     jnp.asarray(yp_te), w, b, ocfg))
        ablation[name] = {"train_accuracy": round(tr_acc, 4),
                          "test_accuracy": round(te_acc, 4)}
        _row(f"customize_escale_{name}", "",
             f"train={tr_acc:.4f};test={te_acc:.4f}")

    report = {
        "backend": jax.default_backend(),
        "interpret": bool(default_interpret()),
        "trained_model": trained,
        "window": sample_len,
        "hop": hop,
        "slots": slots,
        "epochs": epochs,
        "chip_mav_offset_std": 8.0,
        "accuracy_before": round(before, 4),
        "recovery_trajectory": trajectory,
        "concurrent_sessions": concurrent,
        "error_scaling_ablation": ablation,
        "energy_per_finetune_step": {
            k: round(v, 4) if isinstance(v, float) else v
            for k, v in (uj or {}).items()
        },
    }
    _row("customize_before_accuracy", "", f"{before:.4f}")
    _row("customize_uj_per_finetune_step", "",
         f"{report['energy_per_finetune_step'].get('uj_per_finetune_step')}")

    out_path = _write_bench(
        report, out_path, "BENCH_customize.json",
        "PYTHONPATH=src python -m benchmarks.run --customize --sessions 4")
    _row("customize_json", "", out_path)
    return report


def faults_bench(out_path: str | None = None, sample_len: int = 2_000,
                 hop: int = 256) -> dict:
    """Fault-injected self-healing serving (docs/RELIABILITY.md): for each
    fault scenario — offset drift, trim bit flips, stuck SA columns — a
    live StreamServer with the fault model and the canary health monitor
    detects the fault, localizes it, and recompensates through the chip's
    test mode; the bench records held-out accuracy on the clean chip,
    under the fault, and on the healed chip (pristine bias + the heal
    delta, evaluated WITH the fault still present).

    The acceptance gate baked in here: for the recoverable scenarios
    (drift, bit flips) the full recovery loop — the serving heal
    (SIV-B recompensation) plus a head re-enrollment on the healed chip
    (SV-C, the same offline chain the enrollment sessions run) — must
    land within 2 points of the clean chip; integer bit-flip faults must
    additionally heal within 2 points from the bias write alone.  Stuck
    columns cannot be healed by a bias write (the rail dominates any
    finite bias) — they are permanently masked and reported as a
    write-off, not gated.

    A crash-safety record rides along: snapshot the fault+health server
    mid-recovery, restore into a fresh process-equivalent server, and
    verify the next ticks' events are bit-identical (the same invariant
    tests/test_reliability.py trace-enforces), recording snapshot size
    and timings."""
    import pickle
    import tempfile

    import jax
    import jax.numpy as jnp
    from repro.core import faults as flt
    from repro.core import imc
    from repro.data import audio
    from repro.kernels import default_interpret
    from repro.models import kws as m
    from repro.serving import HealthConfig, StreamServer
    from repro.serving import customize as cz
    from repro.training import kws as tr

    cfg = m.KWSConfig(sample_len=sample_len)
    (x_tr, y_tr), (x_te, y_te) = audio.make_gscd_like(
        train_per_class=40, test_per_class=30, length=sample_len)
    # the accuracy gate below is meaningless at chance level, so a
    # trained model is required: load the shared cache
    # (results/kws_model.pkl, the benchmarks/kws_experiments.py artifact)
    # or train the fast config once and cache it for every later bench
    pkl = os.path.join(RESULTS, "kws_model.pkl")
    if sample_len != 2_000:
        raise SystemExit("--faults runs the trained 2000-sample config")
    trained = os.path.exists(pkl)
    if trained:
        with open(pkl, "rb") as f:
            params, state = pickle.load(f)
        params = jax.tree_util.tree_map(jnp.asarray, params)
        state = m.KWSState(*[jax.tree_util.tree_map(jnp.asarray, s)
                             for s in state])
    else:
        tcfg = tr.TrainConfig(
            epochs=24, batch_size=100, lr=3e-3, log_every=48,
            alpha_schedule=((0.3, 2.0), (0.5, 5.0), (0.65, 12.0),
                            (1.0, -8.0)),
            polarize_weight=5e-3)
        params, state = tr.train_base(jnp.asarray(x_tr), jnp.asarray(y_tr),
                                      cfg, tcfg)
        os.makedirs(RESULTS, exist_ok=True)
        with open(pkl, "wb") as f:
            pickle.dump((jax.tree_util.tree_map(np.asarray, params),
                         tuple(jax.tree_util.tree_map(np.asarray, s)
                               for s in state)), f)
        trained = True
    hw = m.fold_params(params, state, cfg, pack=True)
    chans = {f"conv{i}": cfg.channels[i]
             for i in range(1, cfg.num_conv_layers)}
    offs = imc.sample_chip_offsets(jax.random.PRNGKey(7), chans,
                                   imc.IMCNoiseParams(mav_offset_std=8.0))
    # the 'clean' baseline is a fully enrolled device — §IV-B bias
    # compensation plus the §V-C head fine-tune on the chip's own
    # features (the customization path) — so the fault scenarios measure
    # drops from a working operating point, not from chance
    from repro.core.onchip_training import (OnChipTrainConfig,
                                            quantized_head_finetune)
    hw_comp = tr.calibrate_and_compensate(hw, x_tr[:40], offs, cfg)
    hwp0, _ = m.as_hw_params(hw_comp)
    f_tr = tr.hw_features(hw_comp, x_tr, cfg, chip_offsets=offs)
    ocfg = OnChipTrainConfig(epochs=200, fixed_error_scale=1.375)
    fc_w, fc_b = quantized_head_finetune(
        jnp.asarray(f_tr), jnp.asarray(y_tr), hwp0.fc_w, hwp0.fc_b, ocfg)
    hw_comp = cz.refold(cz.CustomizationResult(
        bias={k: np.asarray(v) for k, v in hwp0.bias.items()},
        fc_w=np.asarray(fc_w), fc_b=np.asarray(fc_b), epochs=ocfg.epochs,
        n_utterances=int(len(y_tr)), history=[], energy={}), hw_comp, cfg)
    hwp, _ = m.as_hw_params(hw_comp)
    acc_clean = tr.evaluate_hw(hw_comp, x_te, y_te, cfg, chip_offsets=offs)
    _row("faults_clean_accuracy", "", f"{acc_clean:.4f}")

    def chip_with(delta):
        """The faulted chip as offline offsets: fault deltas add to the
        counts exactly like static MAV offsets do."""
        return {k: jnp.asarray(offs[k])
                + jnp.asarray(np.asarray(delta.get(k, 0.0), np.float32))
                for k in offs}

    def healed_fold(heal):
        """Pristine compensated bias + the serving heal delta, refolded."""
        bias = {name: np.asarray(hwp.bias[name], np.float32)
                + np.asarray(heal.get(name, 0.0), np.float32)
                for name in cfg.imc_layer_names()}
        res = cz.CustomizationResult(
            bias={k: np.rint(v).astype(np.int32) for k, v in bias.items()},
            fc_w=np.asarray(hwp.fc_w), fc_b=np.asarray(hwp.fc_b),
            epochs=0, n_utterances=0, history=[], energy={})
        return cz.refold(res, hw_comp, cfg)

    def inject_drift(f):
        # public-API surgery: a one-shot static drift burst (std 24
        # counts on two layers) via the fault model's own snapshot codec,
        # so it does not keep walking while the heal converges
        snap = f.snapshot()
        rng = np.random.default_rng(1)
        for name in ("conv2", "conv4"):
            snap["drift"][name] = rng.normal(
                0.0, 24.0, snap["drift"][name].shape).astype(np.float32)
        f.restore(snap)

    def run_scenario(name, inject):
        # recal_sa_noise_std 0.25 models the chip's test mode averaging
        # repeated SA reads (16 reads at unit noise): integer faults then
        # round to the exactly-correct even bias write, so bit-flip heals
        # are EXACT instead of carrying +-2-count measurement wobble.
        # recal_scope="all" re-runs the full SIV-B pass per recovery —
        # the direct test mode also cancels canary-invisible faults the
        # tail-only localization can never flag
        srv = StreamServer(hw_comp, cfg, hop=hop, slots=3, use_kernel=True,
                           chip_offsets=offs,
                           faults=flt.FaultConfig(seed=5),
                           health=HealthConfig(interval=5,
                                               recal_sa_noise_std=0.25,
                                               recal_scope="all"),
                           seed=9)
        rng = np.random.default_rng(11)
        srv.submit("live", rng.uniform(-1, 1, sample_len)
                   .astype(np.float32))
        for _ in range(30):          # warm up to the first clean canary
            srv.submit("live", rng.uniform(-1, 1, hop).astype(np.float32))
            srv.step()
            if srv.health.canaries >= 1:
                break
        assert srv.health.state == "healthy", srv.health.state
        injected_tick = srv._steps
        inject(srv.faults)
        delta_f = {k: np.asarray(v).copy()
                   for k, v in srv.faults.deltas().items()}
        acc_faulted = tr.evaluate_hw(hw_comp, x_te, y_te, cfg,
                                     chip_offsets=chip_with(delta_f))
        healed_tick = None
        for _ in range(400):
            srv.submit("live", rng.uniform(-1, 1, hop).astype(np.float32))
            srv.step()
            h = srv.health
            if (h.detected_tick is not None
                    and h.detected_tick >= injected_tick
                    and h.state == "healthy"):
                healed_tick = srv._steps
                break
        h = srv.health
        assert healed_tick is not None, \
            f"{name}: not healed in 400 ticks (state={h.state})"
        heal = {k: np.asarray(v) for k, v in (srv._heal_delta or {}).items()}
        hw_healed = healed_fold(heal)
        co_f = chip_with(delta_f)
        acc_healed = tr.evaluate_hw(hw_healed, x_te, y_te, cfg,
                                    chip_offsets=co_f)
        # complete the paper's recovery loop: the serving heal is the
        # SIV-B compensation stage, and the paper's customization always
        # pairs it with the SV-C head fine-tune.  Integer bias writes
        # cannot cancel a fractional fault (the grid is even-parity, the
        # rail clips), and the enrolled head is fitted to the exact count
        # landscape — so the sub-count heal residual costs real accuracy
        # until the head is re-enrolled on the healed chip (same offline
        # chain the enrollment sessions run, fault still present)
        f_h = tr.hw_features(hw_healed, x_tr, cfg, chip_offsets=co_f)
        hwp_h, _ = m.as_hw_params(hw_healed)
        fcw2, fcb2 = quantized_head_finetune(
            jnp.asarray(f_h), jnp.asarray(y_tr), hwp_h.fc_w, hwp_h.fc_b,
            ocfg)
        hw_re = cz.refold(cz.CustomizationResult(
            bias={k: np.asarray(v) for k, v in hwp_h.bias.items()},
            fc_w=np.asarray(fcw2), fc_b=np.asarray(fcb2),
            epochs=ocfg.epochs, n_utterances=int(len(y_tr)), history=[],
            energy={}), hw_healed, cfg)
        acc_re = tr.evaluate_hw(hw_re, x_te, y_te, cfg, chip_offsets=co_f)
        hs = h.stats()
        rec = {
            "kind": name,
            "accuracy_faulted": round(acc_faulted, 4),
            "accuracy_healed": round(acc_healed, 4),
            "accuracy_reenrolled": round(acc_re, 4),
            "accuracy_drop_faulted": round(acc_clean - acc_faulted, 4),
            "accuracy_gap_healed": round(acc_clean - acc_healed, 4),
            "accuracy_gap_reenrolled": round(acc_clean - acc_re, 4),
            "detect_ticks": hs["detected_tick"] - injected_tick,
            "ticks_to_quarantine": (hs["quarantined_tick"] - injected_tick
                                    if hs["quarantined_tick"] is not None
                                    else None),
            "heal_ticks": healed_tick - injected_tick,
            "canaries": hs["canaries"],
            "failed_canaries": hs["failed_canaries"],
            "recoveries": hs["recoveries"],
            "recovery_energy_uj": hs["recovery_energy_uj"],
            "masked_channels": hs["masked_channels"],
        }
        _row(f"faults_{name}", "",
             f"faulted={acc_faulted:.4f};healed={acc_healed:.4f};"
             f"reenrolled={acc_re:.4f};detect={rec['detect_ticks']};"
             f"heal={rec['heal_ticks']}")
        return rec

    scenarios = {
        "drift": run_scenario("drift", inject_drift),
        "bit_flips": run_scenario(
            "bit_flips", lambda f: f.inject_bit_flips(n=8)),
        "stuck": run_scenario(
            "stuck", lambda f: f.inject_stuck("conv2", [3, 11])),
    }
    # acceptance: the full recovery loop (recompensation + re-enrollment)
    # lands the recoverable faults within 2 points of clean; integer
    # bit-flip faults additionally heal EXACTLY with the bias write alone
    # (even-integer shifts round to the correct even-grid correction)
    for name in ("drift", "bit_flips"):
        gap = scenarios[name]["accuracy_gap_reenrolled"]
        assert gap <= 0.02, (name, gap)
        scenarios[name]["reenrolled_within_2pts"] = True
    gap_bf = scenarios["bit_flips"]["accuracy_gap_healed"]
    assert gap_bf <= 0.02, ("bit_flips raw heal", gap_bf)
    scenarios["bit_flips"]["healed_within_2pts"] = True

    # -- crash safety: snapshot mid-recovery, restore, bit-identical -------
    srv = StreamServer(hw_comp, cfg, hop=hop, slots=3, use_kernel=True,
                       chip_offsets=offs, faults=flt.FaultConfig(seed=5),
                       health=HealthConfig(interval=5), seed=9)
    rng = np.random.default_rng(12)
    srv.submit("live", rng.uniform(-1, 1, sample_len).astype(np.float32))
    srv.faults.inject_bit_flips(n=4)
    for _ in range(12):
        srv.submit("live", rng.uniform(-1, 1, hop).astype(np.float32))
        srv.step()
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "server.npz")
        t0 = time.perf_counter()
        srv.snapshot(path)
        snap_ms = (time.perf_counter() - t0) * 1e3
        snap_bytes = os.path.getsize(path)
        srv2 = StreamServer(hw_comp, cfg, hop=hop, slots=3,
                            use_kernel=True, chip_offsets=offs,
                            faults=flt.FaultConfig(seed=5),
                            health=HealthConfig(interval=5), seed=9)
        t0 = time.perf_counter()
        srv2.restore(path)
        restore_ms = (time.perf_counter() - t0) * 1e3
    future = [rng.uniform(-1, 1, hop).astype(np.float32)
              for _ in range(8)]
    ev1, ev2 = [], []
    for ch in future:
        srv.submit("live", ch)
        ev1.extend(srv.step())
    for ch in future:
        srv2.submit("live", ch)
        ev2.extend(srv2.step())
    assert ev1 == ev2, "restore is not bit-identical"
    crash = {
        "snapshot_bytes": snap_bytes,
        "snapshot_ms": round(snap_ms, 2),
        "restore_ms": round(restore_ms, 2),
        "replay_ticks": len(future),
        "events_bit_identical": True,
    }
    _row("faults_snapshot_restore", "",
         f"bytes={snap_bytes};identical=True")

    report = {
        "backend": jax.default_backend(),
        "interpret": bool(default_interpret()),
        "trained_model": trained,
        "window": sample_len,
        "hop": hop,
        "chip_mav_offset_std": 8.0,
        "test_utterances": int(len(y_te)),
        "baseline": {"accuracy_clean": round(acc_clean, 4)},
        "scenarios": scenarios,
        "snapshot_restore": crash,
    }
    out_path = _write_bench(
        report, out_path, "BENCH_faults.json",
        "PYTHONPATH=src python -m benchmarks.run --faults")
    _row("faults_json", "", out_path)
    return report


def obs_overhead_bench(out_path: str | None = None, sample_len: int = 2_000,
                       hop: int = 256, slots: int = 4,
                       repeats: int = 2) -> dict:
    """Observability tax (docs/OBSERVABILITY.md): the gated streaming
    workload — speech head, silent stretch (gated fills + wake replay),
    speech tail — run telemetry-off vs fully instrumented: metrics
    registry + flight recorder + launch auditor in **raise** mode (the
    ``serving.*`` profiler spans are on in both).

    Records into BENCH_obs.json: the decision streams are bit-identical
    (asserted, not just reported), min-of-``repeats`` wall time and
    us/tick for both modes, the overhead percentage, the auditor's
    launch accounting (zero violations, max one batched hop per tick),
    and recorder/metrics volumes.  A second *mixed-traffic*
    section drives live inference + canary health windows + an
    enrollment session through one auditor-raise server, proving the
    one-fused-launch-per-IMC-layer contract holds with learning and
    canary traffic riding the same ticks."""
    import jax
    import numpy as np_
    from repro.core import faults as flt
    from repro.core.onchip_training import OnChipTrainConfig
    from repro.kernels import default_interpret
    from repro.models import kws as m
    from repro.serving import (CustomizeConfig, HealthConfig, ObsConfig,
                               StreamServer, VADConfig)

    cfg = m.KWSConfig(sample_len=sample_len)
    params = m.init_params(jax.random.PRNGKey(0), cfg)
    state = m.init_state(cfg)
    hw = m.fold_params(params, state, cfg, pack=True)
    imc_layers = cfg.num_conv_layers - 1

    # speech / silence / speech per stream: exercises init, batched hops,
    # gated fills and the wake replay in one drain
    n_hops = 20
    rng = np_.random.default_rng(0)
    streams = {}
    for i in range(slots):
        wav = rng.uniform(-1, 1, sample_len + n_hops * hop
                          ).astype(np_.float32)
        lo = sample_len + (5 + i % 2) * hop
        wav[lo:lo + 7 * hop] *= 1e-4
        streams[f"s{i}"] = wav
    vad = VADConfig(threshold_on_db=-40.0, threshold_off_db=-50.0,
                    wake_margin=1, hang=0)

    def run(ocfg):
        srv = StreamServer(hw, cfg, hop=hop, slots=slots, use_kernel=True,
                           vad=vad, obs=ocfg)
        for sid, wav in streams.items():
            srv.submit(sid, wav)
            srv.finish(sid)
        t0 = time.perf_counter()
        events = srv.drain()
        return srv, events, time.perf_counter() - t0

    obs_off = ObsConfig()
    obs_on = ObsConfig(recorder=512, audit="raise")
    run(obs_off)                       # jit-trace warmup, untimed
    wall_off, wall_on = [], []
    for _ in range(repeats):
        _, ev_off, dt = run(obs_off)
        wall_off.append(dt)
        srv_on, ev_on, dt = run(obs_on)
        wall_on.append(dt)
    assert ev_off == ev_on, "telemetry changed the decision stream"
    ticks = srv_on._steps
    t_off, t_on = min(wall_off), min(wall_on)
    overhead = (t_on - t_off) / t_off * 100.0
    audit = srv_on.auditor.stats()
    assert audit["violations"] == 0, srv_on.auditor.violations
    prom = srv_on.metrics.prometheus_text()

    # -- mixed traffic: inference + canary windows + an enrollment session
    srv = StreamServer(hw, cfg, hop=hop, slots=slots + 2, use_kernel=True,
                       vad=vad, faults=flt.FaultConfig(seed=5),
                       health=HealthConfig(interval=7),
                       obs=ObsConfig(recorder=512, audit="raise"), seed=3)
    sess = srv.customize("enrollee", CustomizeConfig(
        train=OnChipTrainConfig(epochs=8, fixed_error_scale=1.375),
        epochs_per_tick=4, layers_per_tick=5))
    for c in range(2):
        sess.enroll(c, rng.uniform(-1, 1, sample_len).astype(np_.float32))
    sess.finish_enrollment()
    for sid, wav in streams.items():
        srv.submit(sid, wav)
        srv.finish(sid)
    mixed_events = len(srv.drain())
    steps = 0
    while not sess.done and steps < 2000:
        srv.step()
        steps += 1
    assert sess.done, sess.phase
    mixed_audit = srv.auditor.stats()
    assert mixed_audit["violations"] == 0, srv.auditor.violations
    assert mixed_audit["max_hop_calls_per_tick"] <= 1

    report = {
        "backend": jax.default_backend(),
        "interpret": bool(default_interpret()),
        "window": sample_len,
        "hop": hop,
        "slots": slots,
        "hops_per_stream": n_hops,
        "repeats": repeats,
        "ticks": ticks,
        "bit_identical": True,
        "telemetry_off": {
            "wall_s": round(t_off, 4),
            "us_per_tick": round(t_off / ticks * 1e6, 1),
        },
        "telemetry_on": {
            "wall_s": round(t_on, 4),
            "us_per_tick": round(t_on / ticks * 1e6, 1),
            "recorder_events": len(srv_on.recorder),
            "recorder_dropped": srv_on.recorder.dropped(),
            "metrics_cells": len(srv_on.metrics.collect()),
            "prometheus_bytes": len(prom),
        },
        "overhead_pct": round(overhead, 2),
        "audit": {
            "imc_layers": imc_layers,
            "batched_calls": audit["calls"],
            "max_hop_calls_per_tick": audit["max_hop_calls_per_tick"],
            "violations": audit["violations"],
            "one_launch_per_imc_layer_per_call": True,
        },
        "mixed_traffic": {
            "decisions": mixed_events,
            "session_epochs": sess.result.epochs,
            "canaries": srv.health.canaries,
            "learn_hops": srv.stats()["learn_hops"],
            "batched_calls": mixed_audit["calls"],
            "max_hop_calls_per_tick": mixed_audit["max_hop_calls_per_tick"],
            "violations": mixed_audit["violations"],
        },
    }
    _row("obs_overhead_pct", "", f"{overhead:.2f}%")
    _row("obs_bit_identical", "", "True")
    _row("obs_audit", "",
         f"violations={audit['violations']};"
         f"max_hop_calls_per_tick={audit['max_hop_calls_per_tick']};"
         f"mixed_violations={mixed_audit['violations']}")
    out_path = _write_bench(
        report, out_path, "BENCH_obs.json",
        "PYTHONPATH=src python -m benchmarks.run --obs-overhead")
    _row("obs_json", "", out_path)
    return report


def main(argv=None) -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--imc-fused", action="store_true",
                    help="run only the fused IMC layer benchmark and emit "
                         "BENCH_imc_fused.json")
    ap.add_argument("--imc-fused-out", default=None, metavar="PATH",
                    help="output path for BENCH_imc_fused.json "
                         "(default: results/BENCH_imc_fused.json)")
    ap.add_argument("--sample-len", type=int, default=None,
                    help="audio samples per decision window "
                         "(--imc-fused default 16000; --streaming 2000)")
    ap.add_argument("--batches", default=None, metavar="B1,B2,...",
                    help="batch sizes for the --imc-fused end-to-end sweep "
                         "(default 1,4,16)")
    ap.add_argument("--streaming", action="store_true",
                    help="run the always-on serving benchmark (streaming "
                         "vs recompute) and emit BENCH_streaming.json")
    ap.add_argument("--streaming-out", default=None, metavar="PATH",
                    help="output path for BENCH_streaming.json")
    ap.add_argument("--hop", type=int, default=256,
                    help="--streaming hop size in samples (default 256)")
    ap.add_argument("--stream-slots", type=int, default=4,
                    help="--streaming concurrent streams (default 4)")
    ap.add_argument("--stream-hops", type=int, default=6,
                    help="--streaming timed hops per stream (default 6)")
    ap.add_argument("--duty", type=float, default=0.2,
                    help="--streaming speech duty cycle of the gated "
                         "mixture (default 0.2)")
    ap.add_argument("--devices", type=int, default=1,
                    help="--streaming: also run the sharded serving "
                         "section — the same total stream load on one "
                         "device vs a ShardedStreamServer of N per-device "
                         "pools — and record decisions/sec scaling into "
                         "the BENCH_streaming.json 'sharded' section "
                         "(sets --xla_force_host_platform_device_count "
                         "on CPU hosts; real devices used when present)")
    ap.add_argument("--compiled", action="store_true",
                    help="--streaming: also run the whole-tick compiled "
                         "fast-path section — the same steady-state load "
                         "served by the interpreted Python tick vs "
                         "step_block's fused lax.scan dispatch, events "
                         "asserted bit-identical and the launch auditor "
                         "in raise mode — and record the decisions/sec "
                         "speedup into the BENCH_streaming.json "
                         "'compiled' section")
    ap.add_argument("--compiled-ticks", type=int, default=96,
                    help="--compiled timed steady-state ticks per side "
                         "(default 96)")
    ap.add_argument("--compiled-block", type=int, default=32,
                    help="--compiled ticks fused per dispatch "
                         "(CompiledTickConfig.block; default 32)")
    ap.add_argument("--customize", action="store_true",
                    help="run the enrollment-session customization "
                         "benchmark (utterances-to-recovered-accuracy + "
                         "uJ per fine-tune step) and emit "
                         "BENCH_customize.json")
    ap.add_argument("--customize-out", default=None, metavar="PATH",
                    help="output path for BENCH_customize.json")
    ap.add_argument("--customize-epochs", type=int, default=120,
                    help="--customize fine-tune epochs per session "
                         "(default 120)")
    ap.add_argument("--sessions", type=int, default=4,
                    help="--customize concurrent enrollment sessions "
                         "driven through ONE StreamServer (default 4, "
                         "minimum 2 — the record is part of the "
                         "BENCH_customize.json schema)")
    ap.add_argument("--faults", action="store_true",
                    help="run the fault-injection / self-healing benchmark "
                         "(drift, bit-flip and stuck scenarios through the "
                         "canary health monitor; accuracy clean/faulted/"
                         "healed + crash-safety snapshot record) and emit "
                         "BENCH_faults.json")
    ap.add_argument("--faults-out", default=None, metavar="PATH",
                    help="output path for BENCH_faults.json "
                         "(default: results/BENCH_faults.json)")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="run the observability-tax benchmark (gated "
                         "streaming workload telemetry-off vs metrics + "
                         "recorder + auditor-raise, bit-identity "
                         "asserted; plus a mixed inference/canary/learning "
                         "audit section) and emit BENCH_obs.json")
    ap.add_argument("--obs-out", default=None, metavar="PATH",
                    help="output path for BENCH_obs.json "
                         "(default: results/BENCH_obs.json)")
    ap.add_argument("--trace-out", default=None, metavar="DIR",
                    help="with any single-bench flag: run it under "
                         "jax.profiler.trace(DIR) and write a Perfetto "
                         "timeline of host spans and device ops there")
    args = ap.parse_args(argv)
    bench_flags = (args.imc_fused, args.streaming, args.customize,
                   args.faults, args.obs_overhead)
    if sum(bench_flags) > 1:
        ap.error("--imc-fused/--streaming/--customize/--faults/"
                 "--obs-overhead are separate runs; pick one")
    if args.trace_out is not None and not any(bench_flags):
        ap.error("--trace-out needs one of --imc-fused/--streaming/"
                 "--customize/--faults/--obs-overhead")
    if not args.obs_overhead and args.obs_out is not None:
        ap.error("--obs-out only applies with --obs-overhead")
    if not args.faults and args.faults_out is not None:
        ap.error("--faults-out only applies with --faults")
    if not args.imc_fused and (args.imc_fused_out is not None
                               or args.batches is not None):
        ap.error("--imc-fused-out/--batches only apply with --imc-fused")
    if not args.streaming and (args.streaming_out is not None
                               or args.hop != 256 or args.stream_slots != 4
                               or args.stream_hops != 6
                               or args.duty != 0.2 or args.devices != 1
                               or args.compiled):
        ap.error("--streaming-out/--hop/--stream-slots/--stream-hops/"
                 "--duty/--devices/--compiled only apply with --streaming")
    if not args.compiled and (args.compiled_ticks != 96
                              or args.compiled_block != 32):
        ap.error("--compiled-ticks/--compiled-block only apply with "
                 "--compiled")
    if args.devices < 1:
        ap.error("--devices must be >= 1")
    if args.devices > 1 and os.environ.get("JAX_PLATFORMS") == "cpu":
        # a CPU-only run gets N host-platform devices; must land before
        # the first jax import anywhere in the process (the count locks
        # on backend initialization; appended last so it wins over an
        # inherited setting).  On an accelerator the N devices must
        # exist: ShardedStreamServer refuses to run on fewer.
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}")
    if not args.customize and (args.customize_out is not None
                               or args.customize_epochs != 120
                               or args.sessions != 4):
        ap.error("--customize-out/--customize-epochs/--sessions only "
                 "apply with --customize")
    if args.sample_len is not None and not any(bench_flags):
        ap.error("--sample-len only applies with "
                 "--imc-fused/--streaming/--customize/--faults/"
                 "--obs-overhead")
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    if not any(bench_flags):
        table2_model()
        table3_hw_constraints()
        table4_customization()
        table5_energy()
        dryrun_summary()
        kernel_bench()
        return
    if args.trace_out is None:
        _run_bench(args)
        return
    import jax
    with jax.profiler.trace(args.trace_out, create_perfetto_trace=True):
        _run_bench(args)
    _row("trace_dir", "", args.trace_out)


def _run_bench(args) -> None:
    """The one single-bench flag of ``args``."""
    if args.imc_fused:
        batches = tuple(int(b) for b in
                        (args.batches or "1,4,16").split(","))
        imc_fused_bench(args.imc_fused_out,
                        sample_len=args.sample_len or 16_000,
                        batches=batches)
    elif args.streaming:
        streaming_bench(args.streaming_out,
                        sample_len=args.sample_len or 2_000,
                        hop=args.hop, slots=args.stream_slots,
                        hops=args.stream_hops, duty=args.duty,
                        devices=args.devices, compiled=args.compiled,
                        compiled_ticks=args.compiled_ticks,
                        compiled_block=args.compiled_block)
    elif args.customize:
        customize_bench(args.customize_out,
                        sample_len=args.sample_len or 2_000,
                        epochs=args.customize_epochs,
                        sessions=args.sessions)
    elif args.faults:
        faults_bench(args.faults_out, sample_len=args.sample_len or 2_000)
    else:
        obs_overhead_bench(args.obs_out,
                           sample_len=args.sample_len or 2_000)


if __name__ == "__main__":
    main()
