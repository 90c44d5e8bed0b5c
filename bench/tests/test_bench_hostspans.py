"""The reduction of the program's spans on hand-made traces, and the
``host_*_ms`` readers."""

from __future__ import annotations

import collections
import pathlib

import pytest

from bench import devtrace, hostspans, registry

ROOT = pathlib.Path(__file__).resolve().parents[2]

Plane = collections.namedtuple("Plane", "name lines")
Line = collections.namedtuple("Line", "name events")
Ev = collections.namedtuple("Ev", "name start_ns duration_ns")


def _trace(serving=True):
    """Two steps on the main thread, the first holding the compiled
    block's phases; the chip runs four ops (and a container)."""
    ops = [Ev("%fusion.1 = f32[8] fusion()", 0, 150),
           Ev("%imc_fused.3 = f32[1,8,128] custom-call()", 150, 50),
           Ev("%while.1 = (f32[8]) while()", 950, 100),
           Ev("%fusion.2 = f32[8] fusion()", 950, 100),
           Ev("%copy.4 = f32[8] copy()", 1600, 100)]
    host = [Ev("bench.step", 0, 1200), Ev("bench.wait", 1200, 400),
            Ev("bench.step", 1600, 200)]
    if serving:
        host += [Ev("serving.step", 0, 1100),
                 Ev("serving.horizon", 10, 20),
                 Ev("serving.stage", 30, 70),
                 Ev("serving.fate", 100, 50),
                 Ev("serving.dispatch", 150, 50),
                 Ev("serving.fetch", 200, 700),
                 Ev("serving.book", 900, 150),
                 Ev("serving.step", 1600, 150),
                 Ev("serving.horizon", 1600, 30)]
    return [Plane("/device:TPU:0", [Line("XLA Ops", ops),
                                    Line("XLA Modules", [])]),
            Plane("/host:CPU", [Line("python", host),
                                Line("other thread", [Ev("serving.vad",
                                                         0, 2000)])])]


def test_span_counts_totals_and_self_time():
    s = hostspans.spans(_trace())
    assert s["serving.step"]["count"] == 2
    assert s["serving.horizon"]["count"] == 2
    assert s["serving.step"]["total_s"] == pytest.approx(1250e-9)
    # step 1: 1100 less its six children (1040); step 2: 150 less 30
    assert s["serving.step"]["self_s"] == pytest.approx((60 + 120) * 1e-9)
    assert s["serving.fetch"]["self_s"] == pytest.approx(700e-9)
    # a span on another thread is nobody's child
    assert s["serving.vad"] == {"count": 1, "total_s": pytest.approx(2e-6),
                                "self_s": pytest.approx(2e-6)}
    assert not any(k.startswith("bench.") for k in s)


def test_idle_gaps_take_the_innermost_span():
    gaps = hostspans.idle_gaps(_trace())
    # [200,950) mid 575: bench.step > serving.step > serving.fetch
    assert gaps[0] == ["serving.fetch", pytest.approx(750e-9)]
    # [1050,1600) mid 1325: only bench.wait (and the other thread's vad,
    # which started earlier) covers it
    assert gaps[1] == ["bench.wait", pytest.approx(550e-9)]
    plain = hostspans.idle_gaps(_trace(serving=False))
    assert [g[0] for g in plain] == ["bench.step", "bench.wait"]


def test_devtrace_keys_unchanged_by_program_spans():
    assert devtrace.summarize(_trace()) == devtrace.summarize(
        _trace(serving=False))


READERS = {"host_prep_ms": ("horizon", "stage", "fate"),
           "host_dispatch_ms": ("dispatch",),
           "host_fetch_ms": ("fetch",),
           "host_book_ms": ("book",)}


@pytest.mark.parametrize("cell", ["rt", "backlog"])
@pytest.mark.parametrize("metric", sorted(READERS))
def test_host_phase_readers(metric, cell):
    read = registry.Spec(ROOT / "BENCHMARK.json").reader(f"{metric}.{cell}")
    table = hostspans.spans(_trace())
    want = 1e3 * sum(table[f"serving.{p}"]["self_s"]
                     for p in READERS[metric]) / 2
    assert read({"trace": {"spans": table}}) == pytest.approx(want)
    # the parent's summary has no spans; a trace without a step reads none
    assert read({"trace": devtrace.summarize(_trace())}) is None
    assert read({"trace": {"spans": hostspans.spans(
        _trace(serving=False))}}) is None
