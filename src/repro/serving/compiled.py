"""Whole-tick compiled fast path: K scheduler ticks in ONE dispatch.

The Python tick (repro.serving.scheduler.StreamServer.step) is correct
but host-bound: every tick dispatches a handful of tiny jitted calls
(VAD, masked hop, decision head, gated fill) around the one fused launch
per IMC layer, and at CPU-interpret speeds the dispatch + sync overhead
dominates the actual IMC work.  This module compiles the *steady-state*
portion of the tick — gate -> batched hop -> decision head -> rider
updates (noise-field advance, GAP ring shift, hop counters) — into a
jitted ``lax.scan`` body over the fixed slot layout, so serving a whole
block of K ticks is one host->device round trip.

**What runs inside the scan** (dispatch 2, the main block):

* per scan step, at most one new hop per slot: a ``lax.cond``-gated
  masked step of the server's engine (its ``pure_step``: ``stream_step``,
  or KWT's frame-ring step; or the per-slot-rider customized variant) +
  ``decision_step`` for the computed slots, then a ``lax.cond``-gated
  masked ``gated_step`` for the slots whose deferred silent hop aged out
  of the wake margin.  Masked rows ride verbatim — exactly the Python
  tick's masking contract, so one trace of the body launches at most one
  fused kernel per IMC layer (auditor cause ``"compiled"``).

**What stays in Python** (and forces the block boundary — ``horizon()``
returns 0 and ``step()`` falls back to the interpreted tick):

* structural events: admissions (a slotted stream's first full window),
  evictions are fine mid-block but a non-empty admission queue is not,
  SLO shedding, slot autoscaling *resizes* (counter bookkeeping is
  replayed host-side; a resize due within the block shrinks the block),
  dynamic-hop retargets (the horizon is clipped so a retarget can only
  land exactly at the block end, where the Python path applies it),
* session traffic: active customization sessions, health canaries,
  profile-store sweeps, ``force_compute``/internal streams.

A profiled server serves through the block too: its host phases are
``repro.obs.span``s (``serving.horizon``, ``.stage``, ``.vad``, ``.fate``,
``.dispatch``, ``.fetch``, ``.book``) on the profiler's clock.

**Wake-margin replay without dynamic shapes.**  The scan cannot defer a
variable number of hops, so the block is scanned over a per-slot *hop
timeline* index j (not the tick index): the VAD block (dispatch 1, a
jitted scan of ``vad_step`` over the K ticks) returns the speech flags
to the host, and a host-side fate simulation — the single source of
truth for events, counters and bookkeeping — derives each hop's fate
exactly as the Python tick would have: a silent hop is *filled* once
``wake_margin`` newer hops are all silent, *computed* (as part of a wake
replay) if speech arrives within the margin, and stays deferred
host-side past the block end otherwise.  Multi-hop replays become plain
per-hop ``stream_step``s of the scan (``stream_multi_step`` is
test-enforced bit-identical to sequential steps), per-slot hop order is
preserved, and all batched ops are row-independent, so the compiled
block is **bit-identical** to K Python ticks — decisions, carries,
decision/VAD state, SA-noise fields, chip offsets, fault bias-delta
riders and registry counters included (wall-clock counters excluded;
``tests/_equiv.py`` enforces the rest).

Fault drift mid-block is honored: the host ticks the fault model K
times up front and, when the chip delta actually changes inside the
block, stages per-scan-step delta operands mapped by each hop's
*compute* tick (a wake replay reads the delta of its wake tick, exactly
like the Python replay call).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import span
from repro.serving import decision as dec
from repro.serving import stream as sv
from repro.serving import vad as vd

__all__ = ["CompiledTickConfig", "CompiledTick"]


@dataclasses.dataclass(frozen=True)
class CompiledTickConfig:
    """``block``: the hard cap on ticks fused into one dispatch
    (``step_block`` clamps any caller-passed ``max_ticks`` to it, so the
    padded scan length — and with it jit retracing — stays bounded;
    ``step()`` always uses K=1 blocks).  Block and
    timeline lengths are padded up to powers of two with all-False masks
    so the scan re-traces per size bucket, not per length."""

    block: int = 8

    def __post_init__(self):
        if self.block < 1:
            raise ValueError("block must be >= 1")


jax.tree_util.register_static(CompiledTickConfig)


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class CompiledTick:
    """Compiled-block engine bolted onto one ``StreamServer``.

    Owns the jitted VAD-block and main-block callables (cached per
    (hop-multiplier, rider-mode) — jax re-traces per shape bucket) and
    the host fate simulation that replays the Python tick's bookkeeping
    from the block's staged masks.  Holds no serving state of its own,
    so snapshots/restores need no compiled-path awareness."""

    def __init__(self, srv, ccfg: CompiledTickConfig):
        self._srv = srv
        self.cfg = ccfg
        self._vad_block = None
        self._main_cache: Dict[tuple, object] = {}

    # -- eligibility --------------------------------------------------------

    def horizon(self, max_ticks: int) -> int:
        """How many ticks may be fused into one block right now (0 =
        this tick needs the Python path).  Conservative by design: any
        condition the compiled block does not model exactly falls back —
        the Python tick is always correct, and one interpreted tick
        usually clears the condition (admission wave, resize, shed)."""
        with span("horizon"):
            return self._horizon(max_ticks)

    def _horizon(self, max_ticks: int) -> int:
        srv = self._srv
        if max_ticks < 1 or not srv.streaming:
            return 0
        if (srv._health is not None or srv._profiles is not None
                or srv._cust is not None):
            return 0
        if srv._queue:
            return 0
        hop = srv.geom.hop
        window = srv.geom.window
        avail = 0
        any_live = False
        for rec in srv._slots:
            if rec is None:
                continue
            any_live = True
            if rec.internal or rec.force_compute:
                return 0
            if rec.initialized:
                avail = max(avail, len(rec.buf) // hop)
            elif len(rec.buf) >= window:
                return 0                     # admission wave due
        if not any_live or avail == 0:
            return 0
        k = min(max_ticks, avail)
        if srv.acfg is not None and srv.acfg.max_lag_s is not None:
            max_lag = int(srv.acfg.max_lag_s * srv.cfg.sample_rate)
            for rec in srv._streams.values():
                if rec.finished or rec.internal or rec.force_compute:
                    continue
                if sum(map(len, rec.pending)) + len(rec.buf) > max_lag:
                    return 0                 # shed due
        if srv.acfg is not None and srv.max_slots > srv.min_slots:
            # a scale-down may fire at a tick START once idle_ticks
            # accrues to the threshold; keep every in-block tick (the
            # first included) strictly below it
            k = min(k, srv.acfg.scale_down_after - srv._idle_ticks - 1)
        if srv.hcfg is not None:
            if srv._mult != 1:
                # a narrow retarget can land at ANY tick end while
                # widened; one-tick blocks keep it at the block boundary
                k = min(k, 1)
            thr = srv.hcfg.widen_after
            if srv.hcfg.calm_silence is not None:
                thr = min(thr, srv.hcfg.calm_silence)
            # a widen at the FINAL tick end is fine (applied host-side
            # after the block, like the Python tick's tail)
            k = min(k, thr - srv._calm_ticks)
        return max(k, 0)

    # -- jitted blocks ------------------------------------------------------

    def _vad_fn(self):
        if self._vad_block is None:
            vcfg = self._srv.vcfg

            def vad_block(vstate, audio, active):
                return vd.vad_scan(vcfg, vstate, audio, active)

            self._vad_block = jax.jit(vad_block)
        return self._vad_block

    def _main_fn(self, mult: int, cust: bool, per_tick_chip: bool,
                 gated: bool):
        key = (mult, cust, per_tick_chip, gated)
        if key not in self._main_cache:
            # deferred to call time: scheduler.py's package import runs
            # this module's top level before _select_state exists
            from repro.serving.scheduler import _select_state
            srv = self._srv
            eng = srv._bundle(mult)["engine"]
            cfg, geom, kw, hw = srv.cfg, eng.geom, eng._kw, srv._hw
            dcfg = srv.dcfg

            def block(state, dstate, audio, cm, fm, delta, hw_, hb_,
                      chip, fills):
                def body(carry, xs):
                    st, ds = carry
                    a, cmj, fmj, chipj = xs

                    def compute(op):
                        st, ds = op
                        if cust:
                            d = delta
                            if per_tick_chip:
                                d = {n: d[n] + chipj[n] for n in d}
                            lg, new = sv.stream_step(
                                hw, st, a, cfg, geom, **kw, bias_delta=d,
                                head_w=hw_, head_b=hb_)
                        else:
                            lg, new = eng.pure_step(st, a)
                        st2 = _select_state(cmj, new, st)
                        ds2, out = dec.decision_step(dcfg, ds, lg, cmj)
                        return st2, ds2, (out.trigger, out.keyword,
                                          out.score)

                    def skip(op):
                        st, ds = op
                        nb = st.hop.shape[0]
                        return st, ds, (
                            jnp.zeros((nb,), bool),
                            jnp.zeros((nb,), jnp.int32),
                            jnp.zeros((nb,), ds.posteriors.dtype))

                    st, ds, out = jax.lax.cond(cmj.any(), compute, skip,
                                               (st, ds))
                    if gated:
                        def fill(s_):
                            new = sv.gated_step(s_, cfg, geom, fills)
                            return _select_state(fmj, new, s_)

                        st = jax.lax.cond(fmj.any(), fill, lambda s_: s_,
                                          st)
                    return (st, ds), out

                (state, dstate), outs = jax.lax.scan(
                    body, (state, dstate), (audio, cm, fm, chip))
                return state, dstate, outs

            self._main_cache[key] = jax.jit(block)
        return self._main_cache[key]

    # -- the compiled block --------------------------------------------------

    def run(self, k: int) -> List[dict]:
        """Serve ``k`` ticks in one compiled block.  ``k`` must come from
        ``horizon()`` — the caller guarantees no structural event can
        fire inside the block (except at its very end).  Bit-identical
        to ``k`` Python ``step()`` calls."""
        srv = self._srv
        hop = srv.geom.hop
        window = srv.geom.window
        frames_per_hop = srv.geom.frames_per_hop if srv._kwt else 0
        n = srv.slots
        m = srv.vcfg.wake_margin if srv.vcfg is not None else 0
        tick0 = srv._steps
        mult0 = srv._mult
        t_start = time.perf_counter()
        if srv._audit is not None:
            srv._audit.begin_tick(tick0)

        with span("stage"):
            chip_seq = self._tick_faults(k)
            ready, audio, recs, seq, p0, nready, rem0 = self._stage(k)

        with srv._region("compiled"):
            # dispatch 1: the VAD block (no IMC kernels) — flags come
            # back to the host so the fate simulation below is the one
            # source of truth for masks, events and counters
            with span("vad"):
                if srv.vcfg is not None:
                    kp = _pow2(k)
                    audio_p = np.zeros((kp, n, hop), np.float32)
                    audio_p[:k] = audio
                    ready_p = np.zeros((kp, n), bool)
                    ready_p[:k] = ready
                    srv._vstate, flags = self._vad_fn()(
                        srv._vstate, jnp.asarray(audio_p),
                        jnp.asarray(ready_p))
                    speech = np.asarray(flags)[:k] & ready
                else:
                    speech = ready.copy()

            with span("fate"):
                sched, comp_tick, jmax, ops = self._fate(
                    k, m, ready, speech, recs, seq, p0, nready, chip_seq)

            trig = kwd = sc = None
            if jmax > 0:
                key, (audio_tl, cm_p, fm_p), riders = ops
                with span("dispatch"):
                    fn = self._main_fn(srv._mult, *key)
                    srv._state, srv._dstate, outs = fn(
                        srv._state, srv._dstate, jnp.asarray(audio_tl),
                        jnp.asarray(cm_p),
                        None if fm_p is None else jnp.asarray(fm_p),
                        *riders)
            with span("fetch"):
                if jmax > 0:
                    trig, kwd, sc = jax.device_get(outs)   # one transfer
                jax.block_until_ready((srv._state, srv._dstate))
        dt = time.perf_counter() - t_start
        with span("book"):
            srv._hop_wall_s += dt
            if comp_tick:
                per_slot = {}
                for (s, _j) in comp_tick:
                    per_slot[s] = per_slot.get(s, 0) + 1
                for s, cnt in per_slot.items():
                    recs[s].wall_s += dt * cnt / len(comp_tick)

            # host replay of the per-tick bookkeeping, in tick order — the
            # exact side-effect sequence of k Python ticks, booked in bulk:
            # each slot's consumed chunks join its ``recent`` window in one
            # concatenate per block (per-slot hop order is kept), registry
            # counters move once per tick, and the outputs are read from
            # Python lists (the values of int/float/bool on the scalars)
            if trig is not None:
                trig, kwd, sc = trig.tolist(), kwd.tolist(), sc.tolist()
            ready_l, speech_l = ready.tolist(), speech.tolist()
            order = sorted(recs)
            fresh: Dict[int, list] = {s: [] for s in order}  # unbooked chunks

            def book_recent():
                for s, chunks in fresh.items():
                    if chunks:
                        rec = recs[s]
                        rec.recent = np.concatenate(
                            [rec.recent, *chunks])[-window:]
                        chunks.clear()

            events_all: List[dict] = []
            for t in range(k):
                tick = tick0 + t
                self._sim_autoscale()
                tk = sched[t]
                tick_events: List[dict] = []
                aged_n = 0
                for s in order:
                    if not ready_l[t][s]:
                        continue
                    rec = recs[s]
                    if speech_l[t][s]:
                        rec.silent_run = 0
                        if rec.pending:
                            rec.pending = []   # drained by the wake replay
                    else:
                        rec.silent_run += 1
                        rec.pending.append(audio[t, s])
                        if len(rec.pending) > m:
                            fresh[s].append(rec.pending.pop(0))
                            rec.consumed += hop
                            rec.gated_hops += 1
                            aged_n += 1
                # a registry move is a get and a set: skip the zero ones
                if aged_n:
                    srv._gated_hops += aged_n
                # replays first, then regular hops: the Python tick's order
                hops_t = [(s, j) for s, js in tk["replays"] for j in js]
                hops_t += tk["regular"]
                for s, j in hops_t:
                    rec = recs[s]
                    fresh[s].append(seq[s][j])
                    rec.consumed += hop
                    rec.hops += 1
                    ev = {"stream": rec.stream_id, "hop": rec.hops - 1,
                          "keyword": kwd[j][s], "score": sc[j][s],
                          "trigger": trig[j][s]}
                    tick_events.append(ev)
                    if ev["trigger"]:
                        rec.triggers.append(ev)
                computed = len(hops_t)
                if computed:
                    srv._speech_hops += computed
                    srv._decisions += computed
                    if srv._kwt:
                        srv._mfcc_frames += computed * frames_per_hop
                if tk["replays"]:
                    srv._replay_calls += len(tk["replays"])
                if tk["regular"]:
                    srv._hop_calls += 1
                if tk["fills"]:
                    srv._gate_calls += 1

                # retire drained finished streams (evaluated on the VIRTUAL
                # buffer length: staging consumed the block's hops up front)
                for s, rec in enumerate(list(srv._slots)):
                    if rec is None or not rec.finished:
                        continue
                    if rec.initialized and s in recs:
                        remaining = (rem0[s]
                                     + max(nready[s] - (t + 1), 0) * hop)
                    else:
                        remaining = len(rec.buf)
                    if remaining < (hop if rec.initialized else window):
                        srv._free_slot(rec)
                srv._steps += 1
                silent_t = (bool(ready[t].any())
                            and not bool((speech[t] & ready[t]).any()))
                if srv.hcfg is not None:
                    book_recent()   # a retarget re-inits from ``recent``
                srv._retarget_hop(tick_events, woke=bool(tk["replays"]),
                                  silent=silent_t)
                if srv.hcfg is not None and t < k - 1:
                    assert srv._mult == mult0, \
                        "hop retarget fired inside a compiled block"
                gated_n = len(tk["fills"])
                if srv._rec is not None and (computed or gated_n
                                             or tick_events):
                    uj = srv._tick_uj(computed, gated_n)
                    srv._rec.record(tick, "tick", init=0, computed=computed,
                                    gated=gated_n, replays=len(tk["replays"]),
                                    decisions=len(tick_events),
                                    uj=round(uj, 4))
                    srv._metrics.observe("serving.tick_uj", uj)
                events_all.extend(tick_events)
            book_recent()

            if srv._audit is not None:
                srv._audit.end_tick()
                for t in range(1, k):
                    srv._audit.begin_tick(tick0 + t)
                    srv._audit.end_tick()
            srv._compiled_blocks += 1
            srv._compiled_ticks += k
        return events_all

    def _tick_faults(self, k: int) -> Optional[list]:
        """Tick the fault model in lockstep: the per-tick chip delta
        sequence (the Python tick refreshes the rider operand at each tick
        start), or None when it is constant across the block (the current
        rider operand covers it)."""
        srv = self._srv
        if srv._faults is None:
            return None
        chip_seq = []
        for _ in range(k):
            srv._faults.tick()
            if srv._faults.pop_dirty():
                srv._refresh_chip_delta()
            chip_seq.append(srv._chip_delta_j)
        if all(c is chip_seq[0] for c in chip_seq):
            return None
        return chip_seq

    def _stage(self, k: int):
        """Stage the block's ready hops (the Python tick consumes one hop
        per ready slot per tick; readiness is a per-slot prefix since
        nothing is submitted mid-block)."""
        srv = self._srv
        hop, n = srv.geom.hop, srv.slots
        ready = np.zeros((k, n), bool)
        audio = np.zeros((k, n, hop), np.float32)
        recs: Dict[int, object] = {}
        seq: Dict[int, list] = {}     # slot -> pending + fresh hop chunks
        p0: Dict[int, int] = {}       # slot -> deferred hops entering
        nready: Dict[int, int] = {}   # slot -> fresh ready hops staged
        rem0: Dict[int, int] = {}     # slot -> buffered samples left
        for s, rec in enumerate(srv._slots):
            if rec is None or not rec.initialized:
                continue
            rs = min(k, len(rec.buf) // hop)
            recs[s] = rec
            p0[s] = len(rec.pending)
            nready[s] = rs
            # one reshape, not rs tiny copies: row views stage the block
            chunks = np.asarray(rec.buf[:rs * hop],
                                np.float32).reshape(rs, hop)
            rec.buf = rec.buf[rs * hop:]
            rem0[s] = len(rec.buf)
            seq[s] = list(rec.pending) + list(chunks)
            ready[:rs, s] = True
            audio[:rs, s] = chunks
        return ready, audio, recs, seq, p0, nready, rem0

    def _fate(self, k, m, ready, speech, recs, seq, p0, nready, chip_seq):
        """The host fate simulation, its masks and the main block's
        operands.  Returns (per-tick schedule, {(slot, j): compute tick},
        timeline length, operands), the operands (None when nothing is
        computed or filled) being the rest of the ``_main_fn`` cache key,
        the host arrays (audio timeline, compute mask, fill mask or None)
        and the rider operands."""
        srv = self._srv
        hop, n = srv.geom.hop, srv.slots
        # replicate the Python tick's classification exactly — per tick,
        # per slot (slot order): speech wakes + replays any deferred hops,
        # silence defers the hop and ages the oldest out of the wake margin
        pend = {s: list(range(p0[s])) for s in recs}
        sched = []
        for t in range(k):
            tk = {"replays": [], "regular": [], "fills": []}
            for s in sorted(recs):
                if not ready[t, s]:
                    continue
                j = p0[s] + t
                if speech[t, s]:
                    if pend[s]:
                        tk["replays"].append((s, pend[s] + [j]))
                        pend[s] = []
                    else:
                        tk["regular"].append((s, j))
                else:
                    pend[s].append(j)
                    if len(pend[s]) > m:
                        tk["fills"].append((s, pend[s].pop(0)))
            sched.append(tk)

        # masks over the hop-timeline index j (per slot, hop j is its j-th
        # hop since block start: deferred-entering hops first, then the
        # freshly staged ones)
        jcap = max((p0[s] + nready[s] for s in recs), default=0)
        cm = np.zeros((max(jcap, 1), n), bool)
        fm = np.zeros((max(jcap, 1), n), bool)
        comp_tick: Dict[tuple, int] = {}
        jmax = 0
        for t, tk in enumerate(sched):
            for s, js in tk["replays"]:
                for j in js:
                    cm[j, s] = True
                    comp_tick[(s, j)] = t
                    jmax = max(jmax, j + 1)
            for s, j in tk["regular"]:
                cm[j, s] = True
                comp_tick[(s, j)] = t
                jmax = max(jmax, j + 1)
            for s, j in tk["fills"]:
                fm[j, s] = True
                jmax = max(jmax, j + 1)
        if jmax == 0:
            return sched, comp_tick, 0, None

        jp = _pow2(jmax)
        audio_tl = np.zeros((jp, n, hop), np.float32)
        for s in recs:
            for j, ch in enumerate(seq[s][:jmax]):
                audio_tl[j, s] = ch
        cm_p = np.zeros((jp, n), bool)
        cm_p[:jmax] = cm[:jmax]
        fm_p = np.zeros((jp, n), bool)
        fm_p[:jmax] = fm[:jmax]

        cust = srv._cust_on
        per_tick_chip = chip_seq is not None
        gated = srv.vcfg is not None
        delta = hw_ = hb_ = chip = fills = None
        if cust:
            if per_tick_chip:
                # stage per-scan-step chip deltas mapped by each hop's
                # COMPUTE tick (a wake replay reads its wake tick's delta,
                # like the Python replay call)
                delta = srv._slot_delta
                hw_, hb_ = srv._slot_head_w, srv._slot_head_b
                chip = {
                    name: np.zeros((jp, n, srv.cfg.channels[
                        int(name[4:])]), np.float32)
                    for name in srv.cfg.imc_layer_names()}
                for (s, j), t in comp_tick.items():
                    d = chip_seq[t]
                    if d is not None:
                        for name in chip:
                            chip[name][j, s] = np.asarray(d[name])
                chip = {name: jnp.asarray(v) for name, v in chip.items()}
            else:
                delta, hw_, hb_ = srv._slot_custom_args()
        if gated:
            fills = (srv._slot_fills
                     if cust and srv._slot_fills is not None
                     else srv._fills)
        ops = ((cust, per_tick_chip, gated),
               (audio_tl, cm_p, fm_p if gated else None),
               (delta, hw_, hb_, chip, fills))
        return sched, comp_tick, jmax, ops

    def _sim_autoscale(self) -> None:
        """Replay ``_autoscale``'s counter bookkeeping for one in-block
        tick.  The admission queue is empty (horizon precondition) so no
        pressure accrues, and the horizon keeps ``idle_ticks`` strictly
        below the scale-down threshold — a due resize always lands on a
        Python tick."""
        srv = self._srv
        if srv.acfg is None or srv.max_slots <= srv.min_slots:
            return
        srv._pressure_ticks = 0
        free_tail = 0
        for rec in reversed(srv._slots):
            if rec is None:
                free_tail += 1
            else:
                break
        if free_tail and srv.slots > srv.min_slots:
            srv._idle_ticks += 1
            assert srv._idle_ticks < srv.acfg.scale_down_after, \
                "slot resize fired inside a compiled block"
        else:
            srv._idle_ticks = 0
