"""The comparison that decides ``correct``.

For a sample of streams drawn from the seed, every decision the server
emitted (keyword, smoothed score) is compared with the plain reference of
the configuration's model family (its ``stream_reference``) run over the
same audio:

* ``decision_count_mismatch``: decisions emitted versus the reference's
  computed hops, summed over the sampled streams (exact, limit 0);
* ``score_gap``: the widest gap over all compared decisions by which the
  served keyword's reference posterior lies below the reference's best, or
  by which the served score differs from it;
* ``hop_mirror_mismatch``: streams whose hops taken (the benchmark's
  mirror) disagree with the server's own count of decided, gated and
  deferred hops (exact, limit 0).

The limits live in the configuration file (``check``).
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from bench import reference as ref


def sample_streams(n_streams: int, k: int, seed: int) -> list:
    """``k`` stream indices drawn from the seed (all when ``k >= n``)."""
    rng = np.random.default_rng([seed, 0xC4EC])
    return sorted(rng.choice(n_streams, size=min(k, n_streams),
                             replace=False).tolist())


def compare(served: Dict[str, list], refs: Dict[str, dict]) -> dict:
    """Numbers compared: count mismatches and the widest score gap over
    the streams whose reference is unambiguous."""
    count_mismatch, gap, n_dec, skipped = 0, 0.0, 0, 0
    for sid, r in refs.items():
        if r["ambiguous"]:
            skipped += 1
            continue
        got = served[sid]
        want = int((r["fates"] == ref.COMPUTED).sum())
        count_mismatch += abs(len(got) - want)
        n = min(len(got), want)
        if n == 0:
            continue
        kw = np.asarray([g[0] for g in got[:n]], np.int64)
        score = np.asarray([g[1] for g in got[:n]], np.float64)
        sm = r["smoothed"][:n]
        at_kw = sm[np.arange(n), kw]
        gap = max(gap, float(np.max(np.maximum(np.abs(score - at_kw),
                                               sm.max(axis=1) - at_kw))))
        n_dec += n
    return {"decision_count_mismatch": count_mismatch, "score_gap": gap,
            "decisions_compared": n_dec, "streams_skipped": skipped}


def mirror_mismatch(taken: Dict[str, int], server_hops: Dict[str, dict],
                    wake_margin: int) -> int:
    """Streams whose mirror count of hops taken after the first window
    does not split into the server's decided + gated + (at most
    ``wake_margin``) deferred hops."""
    bad = 0
    for sid, n in taken.items():
        st = server_hops[sid]
        deferred = n - (st["hops"] - 1) - st["gated_hops"]
        bad += not (0 <= deferred <= wake_margin)
    return bad


def verdict(numbers: dict, limits: dict) -> Dict[str, dict]:
    """Each compared number beside its limit; a run is correct when none
    exceeds its limit."""
    return {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
