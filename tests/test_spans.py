"""Spans on the profiler's clock and the compile counter (repro.obs).

* ``obs.span`` names its profiler event ``serving.<name>``;
* a profiled compiled server serves through the compiled block (the
  ``serving.compiled`` counter rises), its events are bit-identical to an
  unprofiled run, and every tick's ``serving.step`` holds the block's
  phases (horizon / stage / vad / fate / dispatch / fetch / book);
* a sharded router's ``serving.fleet_step`` holds one ``serving.step``
  per pool, each naming its device;
* ``serving.compiles`` rises on a step that meets a new shape and stays
  put on a warmed step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _spans import host_spans, inside
from repro.models import kws as m
from repro.obs import compiles, span
from repro.serving import (CompiledTickConfig, ShardedStreamServer,
                           StreamServer, VADConfig)

L, HOP = 640, 64
CFG = m.KWSConfig(sample_len=L)
BLOCK_PHASES = ("horizon", "stage", "vad", "fate", "dispatch", "fetch",
                "book")

pytestmark = pytest.mark.streaming


@pytest.fixture(scope="module")
def folded():
    params = m.init_params(jax.random.PRNGKey(5), CFG)
    state = m.init_state(CFG)
    return m.fold_params(params, state, CFG, pack=True)


def _audio(seed, n_hops):
    r = np.random.default_rng(seed)
    return r.uniform(-1.0, 1.0, L + n_hops * HOP).astype(np.float32)


def _compiled_server(folded, slots=2):
    return StreamServer(folded, CFG, hop=HOP, slots=slots, seed=3,
                        vad=VADConfig(),
                        compiled=CompiledTickConfig(block=4))


def _serve(srv, n_hops=9, streams=2):
    """Admit ``streams`` streams, then serve ``n_hops`` hops each: one
    interpreted admission tick, then compiled blocks."""
    for i in range(streams):
        srv.submit(f"s{i}", _audio(10 + i, n_hops))
    events = srv.step()                       # the admission wave
    for _ in range(n_hops):
        if len(events) == streams * (n_hops + 1):
            break
        events += srv.step_block()
    assert len(events) == streams * (n_hops + 1)
    return events


def test_span_names_its_profiler_event(tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        with span("step", ticks=2, slots=4):
            with span("fetch"):
                jnp.ones(3).block_until_ready()
    spans = host_spans(tmp_path)
    (step,) = spans["serving.step"]
    assert step[2] == {"ticks": 2, "slots": 4}
    assert inside(spans["serving.fetch"][0], [step])


def test_profiled_compiled_block_spans_and_bitidentity(folded, tmp_path):
    ref = _serve(_compiled_server(folded))
    srv = _compiled_server(folded)
    with jax.profiler.trace(str(tmp_path)):
        events = _serve(srv)
    assert events == ref
    blocks = srv.stats()["compiled"]["blocks"]
    assert blocks > 0 and srv.metrics.value(
        "serving.compiled", what="ticks") == srv._steps - 1

    spans = host_spans(tmp_path)
    steps = spans["serving.step"]
    assert len(steps) == 1 + blocks           # admission tick + blocks
    assert sum(a["ticks"] for _s, _e, a in steps) == srv._steps
    assert all(a["slots"] == 2 for _s, _e, a in steps)
    for name in BLOCK_PHASES:
        got = spans[f"serving.{name}"]
        assert all(inside(sp, steps) for sp in got), name
        # horizon runs on every step; the block phases once per block
        assert len(got) == (len(steps) if name == "horizon" else blocks), \
            name
    # the admission tick ran interpreted, inside its own step
    assert len(spans["serving.admit"]) == 1
    assert inside(spans["serving.admit"][0], steps[:1])
    # the phases follow each other inside a block
    starts = [spans[f"serving.{n}"][-1][0] for n in BLOCK_PHASES[1:]]
    assert starts == sorted(starts)


def test_sharded_fleet_step_holds_pool_steps(folded, tmp_path):
    dev = jax.devices()[0]
    fleet = ShardedStreamServer(folded, CFG, hop=HOP, devices=[dev, dev],
                                slots=1, seed=3)
    for i in range(2):
        fleet.submit(f"s{i}", _audio(20 + i, 2))
    with jax.profiler.trace(str(tmp_path)):
        fleet.step()
    spans = host_spans(tmp_path)
    (fleet_step,) = spans["serving.fleet_step"]
    assert fleet_step[2] == {"pools": 2}
    steps = spans["serving.step"]
    assert sorted(a["device"] for _s, _e, a in steps) == [0, 1]
    assert all(inside(sp, [fleet_step]) for sp in steps)


def test_compiles_counter_new_shape_vs_warm_step(folded):
    srv = _compiled_server(folded, slots=1)
    srv.submit("s0", _audio(30, 12))
    srv.step()                                # admission: compiles
    srv.step_block(max_ticks=2)               # first 2-tick block

    def booked():
        return {k: srv.metrics.value("serving.compiles", kind=k)
                for k in compiles.KINDS}

    assert booked()["trace"] > 0
    before = booked()
    srv.step_block(max_ticks=2)               # same shapes: warm
    assert booked() == before
    srv.step_block(max_ticks=4)               # a 4-tick block: new shape
    after = booked()
    assert after["trace"] > before["trace"]
    assert after["compile"] + after["cache_load"] > (
        before["compile"] + before["cache_load"])


def test_compiles_tally_counts_a_fresh_jit():
    compiles.install()
    t0 = compiles.tally()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5.0)).block_until_ready()
    t1 = compiles.tally()
    assert t1[0] > t0[0]                       # traced
    assert t1[1] + t1[2] > t0[1] + t0[2]       # compiled or loaded
