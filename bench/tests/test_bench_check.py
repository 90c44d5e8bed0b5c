"""The check decides ``correct`` the right way round, at a tiny size on the
CPU: the program passes it, the bfloat16 control fails it, and so does a
run with the timed path broken underneath."""

from __future__ import annotations

import pytest

import _bench_tiny as tiny

MIXES = [("speech-rt", True), ("gated20-rt", True),
         ("speech-backlog", False)]


@pytest.mark.parametrize("mix,silicon", MIXES)
def test_program_passes(tmp_path, capsys, monkeypatch, mix, silicon):
    spec = tiny.make(tmp_path, mix, silicon=silicon)
    out = tiny.run(spec, capsys, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("mix,silicon", MIXES)
def test_bfloat16_control_fails(tmp_path, mix, silicon):
    from bench import control
    spec = tiny.make(tmp_path, mix, silicon=silicon)
    limits = spec.config("tiny")["check"]
    numbers = control.control_numbers(spec, spec.cell("tiny"), 3, 200)
    assert numbers["score_gap"] > limits["score_gap"]


def _unchanged_state(monkeypatch):
    from repro.serving import stream
    real = stream.stream_step

    def step(hw, state, audio, *a, **k):
        return real(hw, state, audio, *a, **k)[0], state
    monkeypatch.setattr(stream, "stream_step", step)


def _half_batch(monkeypatch):
    import jax.numpy as jnp
    from repro.serving import stream
    from repro.serving.scheduler import _select_state
    real = stream.stream_step

    def step(hw, state, audio, *a, **k):
        logits, new = real(hw, state, audio, *a, **k)
        keep = jnp.arange(logits.shape[0]) < logits.shape[0] // 2
        return (jnp.where(keep[:, None], logits, 0.0),
                _select_state(keep, new, state))
    monkeypatch.setattr(stream, "stream_step", step)


def _altered_answer(monkeypatch):
    from repro.serving import decision
    real = decision.decision_step

    def step(*a, **k):
        state, out = real(*a, **k)
        return state, out._replace(score=out.score + 1e-3)
    monkeypatch.setattr(decision, "decision_step", step)


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _altered_answer])
def test_broken_timed_path_is_not_correct(tmp_path, capsys, monkeypatch,
                                          fault):
    spec = tiny.make(tmp_path, "speech-rt", streams=4, check_streams=4)
    fault(monkeypatch)
    out = tiny.run(spec, capsys, monkeypatch)
    assert not out["correct"], out["checks"]
