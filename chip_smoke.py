"""Chip smoke run: the always-on KWS server end to end on one TPU, at the
paper network's published size (``KWSConfig()``: 16000-sample window,
channels up to 576) and its 64-sample hop.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --chips 4 [--seed N]

One chip, in one process:

1. ``kernels`` — the five IMC layers lower to one ``tpu_custom_call``
   each (full window and hop-64 tail), Pallas runs compiled
   (``default_interpret()`` is False), and the fused ``hw_forward`` equals
   the jnp path bit for bit on four windows;
2. ``serve`` — a few seeded synthetic microphone streams (about 2.5
   windows each, keywords from ``repro.data.audio``) through
   ``StreamServer(hop=64, slots=4, use_kernel=True)`` with VAD gating;
   the same traffic on the jnp path and on the compiled whole-tick path
   must give identical decision events;
3. ``customize`` — two concurrent enrollment sessions, so the batched
   ``sga_update_rows`` kernel runs with two rows and both fine-tunes
   finish; the same sessions on the jnp path must give identical
   profiles.

``--chips 4`` runs only ``sharded``: a ``ShardedStreamServer`` over four
real devices against a single-device ``StreamServer`` on the same
traffic; every stream's events must be identical.

The model is an untrained net folded from ``--seed``; no file outside
the repository is read.  Times printed are set-up and smoke walls, not a
benchmark.  Exits non-zero on any failure, and when JAX finds no TPU.
The last line of standard output is the JSON result.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

import numpy as np

HOP = 64
SLOTS = 4
STREAM_WINDOWS = 2.5
_KERNEL = "@tpu_custom_call"      # a compiled Pallas call in lowered text


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def _log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# traffic and model
# ---------------------------------------------------------------------------


def fold_model(cfg, seed: int):
    """A seeded, untrained paper net folded onto the IMC path."""
    import jax
    from repro.models import kws as m
    params = m.init_params(jax.random.PRNGKey(seed), cfg)
    return m.fold_params(params, m.init_state(cfg), cfg, pack=True)


def make_streams(cfg, seed: int, n: int) -> dict:
    """``n`` microphone streams of ~2.5 windows: a low noise floor with
    two keyword clips at seeded offsets, so the VAD both gates and
    wakes."""
    from repro.data import audio
    rng = np.random.default_rng(seed)
    clips, _ = audio.make_dataset(seed, n_per_class=1, n_speakers=4,
                                  length=cfg.sample_len)
    total = int(STREAM_WINDOWS * cfg.sample_len) // HOP * HOP
    streams = {}
    for i in range(n):
        wav = 0.01 * rng.standard_normal(total).astype(np.float32)
        for at in (int(rng.integers(0, total // 4)),
                   int(rng.integers(total // 2, total - cfg.sample_len))):
            wav[at:at + cfg.sample_len] += clips[rng.integers(len(clips))]
        streams[f"mic{i}"] = np.clip(wav, -1.0, 1.0)
    return streams


def _vad():
    from repro.serving import VADConfig
    # the 0.01 noise floor sits near -40 dBFS: hops outside the keyword
    # clips are gated
    return VADConfig(threshold_on_db=-30.0, threshold_off_db=-36.0,
                     wake_margin=2, hang=1)


def serve(srv, streams: dict, chunk: int = 517) -> list:
    """Feed every stream in microphone-sized chunks, then drain."""
    for sid, wav in streams.items():
        for off in range(0, len(wav), chunk):
            srv.submit(sid, wav[off:off + chunk])
        srv.finish(sid)
    return srv.drain()


def per_stream(events) -> dict:
    """Each stream's own event sequence, without the sharded router's
    ``device`` tag (global interleaving across pools is not part of the
    contract)."""
    out = {}
    for ev in events:
        e = {k: v for k, v in ev.items() if k != "device"}
        out.setdefault(e.pop("stream"), []).append(e)
    return out


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_kernels(cfg, hw, seed: int) -> dict:
    """Every IMC layer lowers to one compiled Pallas kernel, at the full
    window and at the hop-64 streaming tail, and the fused forward equals
    the jnp forward bit for bit."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import default_interpret
    from repro.kernels.imc_mav import ops as mav_ops
    from repro.models import kws as m
    from repro.serving import make_stream_geometry

    if default_interpret():
        _fail("default_interpret() is True: Pallas would run interpreted")
    n_imc = cfg.num_conv_layers - 1
    x = jax.ShapeDtypeStruct((1, cfg.sample_len), jnp.float32)
    fwd = jax.jit(lambda hw_, x_: m.hw_forward(hw_, x_, cfg,
                                               use_kernel=True))
    lowered = fwd.lower(hw, x)
    n_calls = lowered.as_text().count(_KERNEL)
    if n_calls != n_imc:
        _fail(f"full-window hw_forward lowers to {n_calls} "
              f"tpu_custom_call, want {n_imc}")
    t0 = time.perf_counter()
    lowered.compile()
    compile_s = time.perf_counter() - t0
    _log(f"kernels: full window sample_len={cfg.sample_len}: "
         f"{n_calls} tpu_custom_call for {n_imc} IMC layers "
         f"(compile {compile_s:.2f} s)")

    hwp, packed = m.as_hw_params(hw)
    geom = make_stream_geometry(cfg, HOP)
    for i in range(1, cfg.num_conv_layers):
        name = f"conv{i}"
        tail = jax.ShapeDtypeStruct(
            (SLOTS, geom.layers[i].tail_in, cfg.channels[i - 1]),
            jnp.float32)
        step = jax.jit(functools.partial(
            mav_ops.fused_conv_mav_step, groups=cfg.groups(i),
            stride=cfg.strides[i], pool=cfg.pools[i]))
        low = step.lower(tail, hwp.w_bin[name], hwp.bias[name],
                         hwp.flip[name], packed=packed[name])
        n = low.as_text().count(_KERNEL)
        if n != 1:
            _fail(f"{name} hop-{HOP} tail lowers to {n} tpu_custom_call")
        t0 = time.perf_counter()
        low.compile()
        _log(f"kernels: {name} hop-{HOP} tail B={SLOTS} "
             f"T={geom.layers[i].tail_in}: 1 tpu_custom_call "
             f"(compile {time.perf_counter() - t0:.2f} s)")

    from repro.data import audio
    wins, _ = audio.make_dataset(seed, n_per_class=1, n_speakers=4,
                                 length=cfg.sample_len)
    xs = jnp.asarray(wins[:SLOTS])
    lg_k, f_k = fwd(hw, xs)
    lg_j, f_j = jax.jit(lambda hw_, x_: m.hw_forward(
        hw_, x_, cfg, use_kernel=False))(hw, xs)
    for what, a, b in (("logits", lg_k, lg_j), ("features", f_k, f_j)):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            _fail(f"hw_forward {what}: fused kernel != jnp on the chip")
    _log(f"kernels: hw_forward B={SLOTS} fused == jnp (logits, features)")
    return {"custom_calls": n_calls, "compile_s": compile_s}


def phase_serve(cfg, hw, seed: int) -> dict:
    """Kernel path, jnp path and compiled whole-tick path on the same
    traffic: identical decision events."""
    from repro.serving import CompiledTickConfig, StreamServer

    streams = make_streams(cfg, seed, SLOTS)
    runs = {}
    for name, kw in (
            ("kernel", dict(use_kernel=True)),
            ("jnp", dict(use_kernel=False)),
            ("compiled", dict(use_kernel=True,
                              compiled=CompiledTickConfig(block=16)))):
        srv = StreamServer(hw, cfg, hop=HOP, slots=SLOTS, vad=_vad(),
                           seed=seed, **kw)
        t0 = time.perf_counter()
        events = serve(srv, streams)
        wall = time.perf_counter() - t0
        st = srv.stats()
        runs[name] = events
        extra = (f", compiled ticks {st['compiled']['ticks']}"
                 if "compiled" in st else "")
        _log(f"serve[{name}]: {len(events)} events, {st['decisions']} "
             f"decisions, {sum(e['trigger'] for e in events)} triggers, "
             f"{st['speech_hops']} speech / {st['gated_hops']} gated "
             f"hops{extra}; wall {wall:.2f} s incl. compile "
             f"(not a benchmark)")
        if name == "compiled" and not st["compiled"]["ticks"]:
            _fail("the compiled server fused no ticks")
    if not runs["kernel"]:
        _fail("the server emitted no decision events")
    for name in ("jnp", "compiled"):
        if runs[name] != runs["kernel"]:
            _fail(f"serve: {name} events != kernel events on the chip")
    _log("serve: kernel == jnp == compiled events")
    return {"events": len(runs["kernel"]),
            "triggers": sum(e["trigger"] for e in runs["kernel"])}


def phase_customize(cfg, hw, seed: int, epochs: int = 20) -> dict:
    """Two concurrent enrollment sessions: the batched SGA kernel runs
    with both rows, both fine-tunes finish, and the profiles equal the
    jnp path's."""
    from repro.core.onchip_training import OnChipTrainConfig
    from repro.data import audio
    from repro.kernels.sga_update import ops as sga_ops
    from repro.serving import CustomizeConfig, StreamServer

    (utts, labels), _ = audio.make_personal(
        seed, train_per_class=1, test_per_class=1, length=cfg.sample_len)
    rows = []
    real = sga_ops.sga_update_batch

    def counting(w, *args, **kwargs):
        rows.append(int(w.shape[0]))
        return real(w, *args, **kwargs)

    results = {}
    for use_kernel in (True, False):
        # two enrolling streams plus two replay streams per session: with
        # every replay admitted in one wave, both sessions reach
        # fine-tuning on the same tick and share the SGA launch
        srv = StreamServer(hw, cfg, hop=HOP, slots=2 * SLOTS,
                           use_kernel=use_kernel, seed=seed)
        sessions = []
        for k in range(2):
            s = srv.customize(f"user{k}", CustomizeConfig(
                train=OnChipTrainConfig(epochs=epochs,
                                        fixed_error_scale=1.375),
                epochs_per_tick=5, use_kernel=use_kernel))
            for j in (k, k + 3):          # two keywords per speaker
                s.enroll(int(labels[j]), utts[j])
            s.finish_enrollment()
            sessions.append(s)
        sga_ops.sga_update_batch = counting
        t0 = time.perf_counter()
        try:
            for ticks in range(1, 5000):
                srv.step()
                if all(s.done for s in sessions):
                    break
        finally:
            sga_ops.sga_update_batch = real
        if not all(s.phase == "swapped" for s in sessions):
            _fail(f"customize: sessions stuck in "
                  f"{[s.phase for s in sessions]}")
        results[use_kernel] = [s.result for s in sessions]
        _log(f"customize[{'kernel' if use_kernel else 'jnp'}]: 2 sessions "
             f"swapped after {ticks} ticks, {epochs} epochs each; wall "
             f"{time.perf_counter() - t0:.2f} s incl. compile "
             f"(not a benchmark)")
        if use_kernel and max(rows, default=0) != 2:
            _fail(f"sga_update_rows ran with rows {sorted(set(rows))}, "
                  f"never 2 concurrent sessions")
    for a, b in zip(results[True], results[False]):
        same = (np.array_equal(a.fc_w, b.fc_w)
                and np.array_equal(a.fc_b, b.fc_b)
                and all(np.array_equal(a.bias[n], b.bias[n])
                        for n in a.bias))
        if not same:
            _fail("customize: kernel profile != jnp profile on the chip")
    _log(f"customize: sga_update_rows ran {rows.count(2)} times with 2 "
         f"rows; kernel profiles == jnp profiles")
    return {"sga_rows": max(rows)}


def phase_sharded(cfg, hw, seed: int, n_dev: int) -> dict:
    """A ShardedStreamServer over ``n_dev`` real devices against one
    single-device StreamServer: identical events for every stream."""
    from repro.serving import ShardedStreamServer, StreamServer

    per_dev = 2
    streams = make_streams(cfg, seed, n_dev * per_dev)
    t0 = time.perf_counter()
    one = serve(StreamServer(hw, cfg, hop=HOP, slots=n_dev * per_dev,
                             vad=_vad(), seed=seed, use_kernel=True),
                streams)
    t1 = time.perf_counter()
    fleet = ShardedStreamServer(hw, cfg, hop=HOP, devices=n_dev,
                                slots=per_dev, vad=_vad(), seed=seed,
                                use_kernel=True)
    sharded = serve(fleet, streams)
    t2 = time.perf_counter()
    used = sorted({ev["device"] for ev in sharded})
    _log(f"sharded: {len(sharded)} events from devices {used} "
         f"({[str(d) for d in fleet.devices]}); single device "
         f"{len(one)} events; walls {t1 - t0:.2f} s / {t2 - t1:.2f} s "
         f"incl. compile (not a benchmark)")
    if not one:
        _fail("sharded: no decision events")
    if len(used) != n_dev:
        _fail(f"sharded: events came from {len(used)} pools, "
              f"want {n_dev}")
    if per_stream(one) != per_stream(sharded):
        _fail("sharded: per-stream events != single-device events")
    _log(f"sharded: {n_dev}-device fleet == single device, per stream")
    return {"events": len(sharded)}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the model weights and the traffic")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded-fleet phase on four "
                         "chips")
    args = ap.parse_args(argv)

    root = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(root, "src", "repro")):
        _fail(f"no src/repro next to {__file__}: run from a checkout")
    sys.path.insert(0, os.path.join(root, "src"))

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < args.chips:
        _fail(f"--chips {args.chips} but JAX sees {len(devs)} device(s)")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    _log(f"device: {device}")

    from repro.compile_cache import enable_compile_cache
    _log(f"compile cache: {enable_compile_cache()}")
    from repro.models import kws as m
    cfg = m.KWSConfig()
    _log(f"model: KWSConfig() sample_len={cfg.sample_len} "
         f"channels={cfg.channels} hop={HOP} seed={args.seed} (untrained)")
    hw = fold_model(cfg, args.seed)

    phases = ([("sharded", lambda: phase_sharded(cfg, hw, args.seed, 4))]
              if args.chips == 4 else
              [("kernels", lambda: phase_kernels(cfg, hw, args.seed)),
               ("serve", lambda: phase_serve(cfg, hw, args.seed)),
               ("customize", lambda: phase_customize(cfg, hw, args.seed))])
    for name, run in phases:
        t0 = time.perf_counter()
        out = run()
        _log(f"phase {name}: ok in {time.perf_counter() - t0:.2f} s {out}")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
