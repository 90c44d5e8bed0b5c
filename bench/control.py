"""The check's control: the reference in bfloat16 in the program's place.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--hops N] \
        [--streams N]

For each seed, the cell's sampled streams (as a run of ``--seconds``
would serve them: the warm-up hops and then ``--hops`` more) are computed
by the float32 reference and by the same reference in bfloat16, and the
bfloat16 decisions are compared with the float32 ones by the run's own
comparison (``bench.check``).  The control must come out not correct: it
sets the upper reading of each limit in ``PERF.md``.  It needs no program
run, and no TPU either, but its numbers are read on the chip.  The
benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def control_numbers(spec, cell: dict, seed: int, hops: int,
                    streams: int = None) -> dict:
    import jax
    import jax.numpy as jnp

    from bench import check, serve, traffic as tr

    family = spec.family(cell["config"])
    config = spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    model, hop = config["model"], config["serving"]["hop"]
    window = model["sample_len"]
    n = streams or traffic["streams"]
    vad = traffic.get("vad")
    w = family.draw(config, seed)
    plan = tr.audio_plan(traffic, n, seed, hop)
    base_key = jax.random.PRNGKey(serve.server_seed(seed))
    taken = len(plan["warmup"]) // hop + hops
    ref32, ctl = {}, {}
    for i in check.sample_streams(n, traffic["check_streams"], seed):
        audio = tr.full_stream(plan, i, window, taken, hop)
        key = jax.random.fold_in(base_key, i)
        args = (config, w, audio, taken, hop, key, vad)
        sid = f"mic{i}"
        ref32[sid] = family.stream_reference(*args)
        low = family.stream_reference(*args, dtype=jnp.bfloat16)
        ctl[sid] = list(zip(low["keyword"].tolist(), low["score"].tolist()))
    return check.compare(ctl, ref32)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--hops", type=int, default=2500)
    ap.add_argument("--streams", type=int, default=None,
                    help="override the traffic's stream count")
    args = ap.parse_args(argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import registry
    spec = registry.Spec(ROOT / "BENCHMARK.json")
    cell = spec.cell(args.workload)
    limits = spec.config(cell["config"])["check"]
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = control_numbers(spec, cell, seed, args.hops, args.streams)
        fails = [k for k in limits if k in numbers
                 and numbers[k] > limits[k]]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": numbers, "fails": fails}), flush=True)


if __name__ == "__main__":
    main()
