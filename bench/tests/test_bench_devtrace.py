"""The trace reduction on small hand-made traces."""

from __future__ import annotations

import collections

import pytest

from bench import devtrace

Plane = collections.namedtuple("Plane", "name lines")
Line = collections.namedtuple("Line", "name events")
Ev = collections.namedtuple("Ev", "name start_ns duration_ns")


def _trace():
    ops = [Ev("%fusion.1 = f32[8] fusion()", 0, 100),
           Ev("%imc_fused.3 = f32[1,128,128] custom-call()", 50, 100),
           Ev("%fusion.2 = f32[8] fusion()", 400, 100),
           Ev("%while.1 = (f32[8]) while()", 1000, 500),
           Ev("%imc_fused.3 = f32[1,128,128] custom-call()", 1000, 500)]
    mods = [Ev("jit_block", 0, 200), Ev("jit_block", 400, 1100)]
    host = [Ev("bench.step", 0, 600), Ev("bench.wait", 600, 400),
            Ev("other", 0, 10)]
    return [Plane("/device:TPU:0", [Line("XLA Ops", ops),
                                    Line("XLA Modules", mods)]),
            Plane("/host:CPU", [Line("python", host)])]


def test_busy_is_the_union_of_op_intervals():
    s = devtrace.summarize(_trace())
    assert s["busy_s"] == pytest.approx(750e-9)      # [0,150)+[400,500)+[1000,1500)
    assert s["imc_kernel_s"] == pytest.approx(600e-9)
    assert s["imc_calls"] == 2
    assert s["programs"] == 2
    assert s["ops"]["%fusion.1"] == pytest.approx(100e-9)


def test_idle_gaps_are_labelled_by_host_span():
    s = devtrace.summarize(_trace())
    gaps = s["breakdown"]["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(500e-9)]
    assert gaps[1] == ["bench.step", pytest.approx(250e-9)]
    top = s["breakdown"]["device_ops"]
    assert top[0] == ["%imc_fused.3", pytest.approx(600e-9)]
    assert all(not name.startswith("%while") for name, _ in top)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        devtrace.summarize([Plane("/host:CPU", [])])
