"""Run one benchmark cell once on the chip and print one JSON result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--streams <n>]

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``).  The configuration names its model
family (``family``: ``bench/families/<family>/``), and the run calls the
family's three entry points alone (``bench/registry.py``); of the
configuration it reads only what every family shares: ``model.sample_len``
(the decision window) and ``model.sample_rate``, ``serving.hop`` and
``serving.block``, and ``check``.  A run:

1. set-up (``setup_s``): finds the TPU (and fails without one), draws the
   weights from the seed (the family's ``draw``), builds the program's
   ``StreamServer`` (``build_server``), admits every stream in one batched
   init and steps the traffic's warm-up hops so that every program the
   window runs is compiled;
2. the window: ``--seconds`` of traffic, open loop (``realtime``) or a
   backlog drained by ``step_block`` (``backlog``), timed on the host
   clock.  With ``--trace 1`` the window is at most the traffic's
   ``trace_seconds`` long and runs under the JAX profiler; the run then
   reports the cell's per-layer metrics instead of its end-to-end ones;
3. the check: the served decisions of a sample of streams against the
   family's plain reference (``stream_reference``), after the server is
   freed.

The last line of standard output is the result; the numbers compared,
each beside its limit, are the last lines of standard error.
``--streams`` overrides the traffic's stream count (the knee and backlog
sweeps in ``PERF.md``).  Exits non-zero with no result when JAX finds no
TPU, or fewer chips than the cell asks for, and at once when the
configuration names no family that exists.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"


def fail(msg: str) -> None:
    print(f"bench: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--streams", type=int, default=None,
                    help="override the traffic's stream count (sweeps)")
    return ap.parse_args(argv)


def find_devices(chips: int) -> dict:
    """The device record; fails without a TPU or with too few chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        fail(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        fail(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def enable_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed place in
    the checkout (or ``JAX_COMPILATION_CACHE_DIR``), every program kept."""
    import jax
    from repro.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def _gc_timer(pauses: list):
    """A ``gc.callbacks`` hook that records each full collection's time."""
    t = [0.0]

    def hook(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                t[0] = time.perf_counter()
            else:
                pauses.append(time.perf_counter() - t[0])
    return hook


def measure(args, spec, cell: dict, t_start: float) -> None:
    import jax
    import numpy as np

    from bench import (check, devtrace, loops, peaks, registry, serve,
                       traffic as tr)

    family = spec.family(cell["config"])
    device = find_devices(cell["chips"])
    pk = peaks.peaks(device["kind"])
    log(f"device {device}; compile cache {enable_cache()}")
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    model, hop = config["model"], config["serving"]["hop"]
    window = model["sample_len"]
    n = args.streams or traffic["streams"]
    vad = traffic.get("vad")
    realtime = traffic["loop"] == "realtime"

    w = family.draw(config, args.seed)
    srv = family.build_server(config, w, n, args.seed, vad)
    plan = tr.audio_plan(traffic, n, args.seed, hop)
    sids = tr.stream_ids(n)
    feed = loops.Feed(plan, sids, window, hop)
    block = config["serving"]["block"]
    step = srv.step if realtime else srv.step_block
    setup_events = loops.warm_up(srv, feed, step, 1 if realtime else block)
    # set-up's objects (JAX, the server, the bank) are never garbage: keep
    # the collector from walking them in the window
    gc.collect()
    gc.freeze()
    pauses = []
    gc_hook = _gc_timer(pauses)
    gc.callbacks.append(gc_hook)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s: {n} streams, {feed.n_warm} warm-up hops")

    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, traffic["trace_seconds"])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        c0 = serve.counters(srv)
        jax.profiler.start_trace(str(TRACE_DIR))
    if realtime:
        period = hop / model["sample_rate"]
        res = loops.realtime(srv, feed, seconds, period,
                             tr.hop_phases(n, period, args.seed),
                             traced=bool(args.trace))
    else:
        res = loops.backlog(srv, feed, seconds, block,
                            traffic["ahead_hops"], traced=bool(args.trace))
    if args.trace:
        jax.profiler.stop_trace()
        counts = {k: v - c0[k] for k, v in serve.counters(srv).items()}
    device["memory_peak_bytes"] = int(
        (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use", 0))
    gc.callbacks.remove(gc_hook)
    gc.unfreeze()
    server_hops = serve.per_stream_hops(srv)
    del srv, step
    gc.collect()
    log(f"window {res['window_s']:.3f} s: {res['ticks']} ticks, "
        f"{res['attempted']} hops, {res['failed']} failed, "
        f"tick {1e3 * res['step_s'] / max(res['ticks'], 1):.4f} ms"
        + (f", generator at most {1e3 * res['generator_late_s']:.4f} ms "
           f"late, backlog at close {res['backlog_end']} hops"
           if realtime else "")
        + f"; {len(pauses)} full garbage collections in it, longest "
          f"{1e3 * max(pauses, default=0.0):.4f} ms")

    # -- the check: sampled streams against the plain reference ---------
    t_ref = time.perf_counter()
    keep = check.sample_streams(n, traffic["check_streams"], args.seed)
    served = loops.collect(setup_events + res["events"],
                           {sids[i] for i in keep})
    base_key = jax.random.PRNGKey(serve.server_seed(args.seed))
    refs = {}
    for i in keep:
        taken = int(res["taken"][i])
        refs[sids[i]] = family.stream_reference(
            config, w, tr.full_stream(plan, i, window, taken, hop), taken,
            hop, jax.random.fold_in(base_key, i), vad)
    numbers = check.compare(served, refs)
    numbers["hop_mirror_mismatch"] = check.mirror_mismatch(
        {sids[i]: int(res["taken"][i]) for i in range(n)}, server_hops,
        vad["wake_margin"] if vad else 0)
    checks = check.verdict(numbers, config["check"])
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    log(f"check: {len(keep)} streams, {numbers['decisions_compared']} "
        f"decisions, {numbers['streams_skipped']} skipped as ambiguous, "
        f"reference {time.perf_counter() - t_ref:.3f} s")

    out = {"correct": correct, "attempted": res["attempted"],
           "failed": res["failed"], "device": device}
    if args.trace:
        summary = devtrace.reduce(TRACE_DIR)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = res["window_s"]
        ctx = {"model": model, "hop": hop, "peaks": pk, "traffic": traffic,
               "ticks": res["ticks"], "step_s": res["step_s"],
               "window_s": res["window_s"], "counters": counts,
               "trace": summary}
        out["metrics"] = registry.read_per_layer(spec, cell["name"], ctx)
        out["breakdown"] = summary["breakdown"]
    else:
        e2e = {"setup_s": setup_s}
        if realtime:
            lat = np.concatenate([res["latency_s"], np.full(
                res["failed"], loops.DRAIN_GRACE_S)])
            e2e["hop_latency_p50_ms"] = float(np.percentile(lat, 50)) * 1e3
            e2e["hop_latency_p99_ms"] = float(np.percentile(lat, 99)) * 1e3
        else:
            e2e["hops_per_s"] = res["hops"] / res["window_s"]
        out["metrics"] = {}
        for m in spec.metrics(cell["name"], "end_to_end"):
            if m["name"] not in e2e:
                fail(f"this loop cannot measure {m['name']!r}")
            out["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    out["checks"] = checks
    print(json.dumps(out), flush=True)


def main(argv=None) -> None:
    t_start = time.perf_counter()
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro in {ROOT}: run from a checkout of the repo")
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import registry
    spec = registry.Spec(ROOT / "BENCHMARK.json")
    measure(args, spec, spec.cell(args.workload), t_start)


if __name__ == "__main__":
    main()
