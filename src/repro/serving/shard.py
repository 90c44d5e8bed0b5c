"""Device-sharded serving: per-device slot pools behind a host router.

Scale-out for the always-on KWS fleet.  The paper's accelerator is a
complete inference engine per chip — weights folded into the IMC arrays,
decisions local — so the natural multi-device deployment is N independent
slot pools (one full ``StreamServer`` per device, its folded model and
carry buffers resident on that device) with a thin host-side router in
front.  Nothing per-hop ever crosses a device boundary:

* **placement** — a new stream is pinned to one device for life by the
  deterministic policy in ``repro.sharding.placement`` (most free slots,
  then shortest queue, optionally duty-aware, round-robin tie-break);
  replay waves, canaries and customization sessions stay on the stream's
  device because they ride that pool's batched launches;
* **per-device invariants** — every serving contract holds per pool:
  each router ``step()`` ticks every pool once, and each pool issues at
  most ONE fused launch per IMC layer for all its ready slots
  (``LaunchAuditor`` carries the pool's ``device`` label, so violations
  and stats are attributable);
* **all-gather only for telemetry** — ``stats()`` materializes one small
  counter vector per device and gathers them host-side into the fleet
  rollup; that is the only cross-device data motion in the tier.

**Bit-identity with single-device serving** (test-enforced in
``tests/test_sharded_serving.py``): the router assigns every external
stream a GLOBAL uid in submission order and pins it via
``StreamServer.submit(uid=...)``.  A stream's SA-noise field key is
``fold_in(base_key, uid)``, so with every pool sharing the same ``seed``
a stream's noise field — and therefore its full decision sequence,
chip offsets, fault deltas and gating included — is identical no matter
which pool it lands on, and identical to a single-device server fed the
same streams.  Per-pool ``FaultModel``s are built from one shared
``FaultConfig`` (same seed), and every pool ticks its model once per
router tick, so drift trajectories stay in lockstep with the
single-device oracle.

**Sharded snapshots**: ``snapshot()`` bundles every pool's v2 snapshot
plus the router state (stream->device map, global uid counter, placement
cursor) into one atomically-written npz; ``restore()`` on a freshly
constructed identically-configured sharded server resumes
bit-identically.

Device binding follows ``launch/mesh.py``'s idiom — devices are resolved
at construction time, never at import time: ``devices=N`` takes the
first N entries of ``jax.devices()`` and raises when fewer exist, so a
fleet never runs on fewer chips than it names.  N logical pools on one
physical device is an explicit device list (``[dev] * N``), which is how
the equivalence tests run on one CPU device; real host-platform devices
come from ``XLA_FLAGS=--xla_force_host_platform_device_count``.
"""

from __future__ import annotations

import json
import os
import tempfile
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import span
from repro.serving.scheduler import StreamServer
from repro.sharding.placement import (PlacementConfig, PlacementPolicy,
                                      PoolLoad)

__all__ = ["ShardedStreamServer"]

# fleet counter vector layout: one row per device, gathered host-side in
# stats() — the tier's single cross-device collective
_GATHER_KEYS = ("decisions", "speech_hops", "gated_hops", "learn_hops",
                "rejected_streams", "queue_depth", "hop_wall_s")


class ShardedStreamServer:
    """N per-device ``StreamServer`` pools behind a placement router."""

    def __init__(self, hw, cfg, *, hop: int,
                 devices: Union[int, Sequence] = 2,
                 slots: int = 4,
                 placement: Optional[PlacementConfig] = None,
                 parallel: bool = False,
                 faults=None,
                 seed: int = 0,
                 **server_kw):
        """``devices`` is a count (the first N of ``jax.devices()`` at
        construction; fewer available devices is an error) or an
        explicit device sequence, which may repeat a device.  ``slots``
        is PER DEVICE.  ``faults`` must be a ``FaultConfig`` (each pool
        builds its own seeded ``FaultModel`` so injections replay
        identically per pool) — a shared ``FaultModel`` instance would
        double-tick across pools.
        Remaining ``server_kw`` is forwarded verbatim to every pool.

        ``parallel=True`` dispatches pool ticks on one thread per device
        (``jax.default_device`` is thread-local, so each tick stays
        pinned); the default sequential dispatch keeps per-device wall
        attribution clean, which is what the scaling bench reports."""
        if isinstance(devices, int):
            if devices < 1:
                raise ValueError("devices must be >= 1")
            avail = jax.devices()
            if len(avail) < devices:
                raise ValueError(
                    f"devices={devices} but only {len(avail)} "
                    f"{avail[0].platform} device(s) exist; pass an "
                    f"explicit device list to share devices")
            self.devices = avail[:devices]
        else:
            self.devices = list(devices)
        if faults is not None:
            from repro.core import faults as flt
            if isinstance(faults, flt.FaultModel):
                raise ValueError(
                    "sharded serving needs a FaultConfig, not a "
                    "FaultModel: each pool builds its own seeded model "
                    "so injections replay identically on every device")
        self.n_devices = len(self.devices)
        self.cfg = cfg
        self.parallel = bool(parallel)
        self._pool_exec = (ThreadPoolExecutor(max_workers=self.n_devices)
                           if self.parallel else None)
        self.policy = PlacementPolicy(self.n_devices, placement)
        self.pools: List[StreamServer] = []
        for d, dev in enumerate(self.devices):
            with jax.default_device(dev):
                # per-device weight residency: each pool computes against
                # its own copy of the folded model (the chip's in-SRAM
                # weights), so no launch ever reads across devices
                hw_d = jax.device_put(hw, dev)
                kw = dict(server_kw)
                if kw.get("chip_offsets") is not None:
                    kw["chip_offsets"] = jax.device_put(
                        kw["chip_offsets"], dev)
                self.pools.append(StreamServer(
                    hw_d, cfg, hop=hop, slots=slots, faults=faults,
                    seed=seed, device_label=d, **kw))
        # global uid counter: starts past whatever every pool reserved at
        # construction (health canaries reserve one uid each, identically
        # across pools AND in the single-device oracle), then advances
        # once per accepted external stream in submission order
        self._next_uid = self.pools[0]._uid
        self._where: Dict[str, int] = {}
        self._steps = 0

    # -- routing ----------------------------------------------------------

    def _loads(self) -> List[PoolLoad]:
        out = []
        for srv in self.pools:
            total = srv._speech_hops + srv._gated_hops
            out.append(PoolLoad(
                free_slots=sum(r is None for r in srv._slots),
                queue_depth=len(srv._queue),
                duty=(srv._speech_hops / total) if total else None))
        return out

    def _route(self, stream_id: str) -> int:
        """Device index owning ``stream_id``, placing it if new.  A new
        stream is created empty on its pool with the next GLOBAL uid, so
        its SA-noise field matches the single-device oracle's."""
        d = self._where.get(stream_id)
        if d is not None:
            return d
        d = self.policy.place(self._loads())
        with jax.default_device(self.devices[d]):
            res = self.pools[d].submit(stream_id,
                                       np.zeros((0,), np.float32),
                                       uid=self._next_uid)
        if res == "rejected":
            return -1
        self._where[stream_id] = d
        self._next_uid += 1
        return d

    def where(self, stream_id: str) -> Optional[int]:
        """Device index a stream was placed on (None if never admitted)."""
        return self._where.get(stream_id)

    # -- stream lifecycle (delegated to the owning pool) -------------------

    def submit(self, stream_id: str, chunk, user_id: Optional[str] = None):
        """Route + append audio.  Returns the pool's placement verdict
        ('slot' / 'queued') or 'rejected' when the chosen pool's
        admission queue is full (nothing is buffered; the uid is not
        consumed, matching a single-device rejection)."""
        d = self._route(stream_id)
        if d < 0:
            return "rejected"
        with jax.default_device(self.devices[d]):
            return self.pools[d].submit(stream_id, chunk, user_id=user_id)

    def finish(self, stream_id: str) -> None:
        self.pools[self._where[stream_id]].finish(stream_id)

    def evict(self, stream_id: str) -> None:
        d = self._where[stream_id]
        with jax.default_device(self.devices[d]):
            self.pools[d].evict(stream_id)

    def customize(self, stream_id: str, ccfg=None):
        """Open an enrollment session on the stream's pool (placing the
        stream first if it does not exist yet) — the session's replay
        waves and background jobs all stay device-local."""
        d = self._route(stream_id)
        if d < 0:
            raise RuntimeError(f"cannot place stream {stream_id!r}: "
                               f"chosen pool's admission queue is full")
        with jax.default_device(self.devices[d]):
            return self.pools[d].customize(stream_id, ccfg)

    def install_custom(self, stream_id: str, result) -> None:
        d = self._route(stream_id)
        if d < 0:
            raise RuntimeError(f"cannot place stream {stream_id!r}: "
                               f"chosen pool's admission queue is full")
        with jax.default_device(self.devices[d]):
            self.pools[d].install_custom(stream_id, result)

    # -- fault / health fan-out -------------------------------------------

    @property
    def fault_models(self):
        """Per-device FaultModels (empty list when faults are off).  A
        chip-global fault campaign injects into EVERY model — same seed,
        same draws, so all pools (and the single-device oracle) mutate
        identically."""
        return [srv.faults for srv in self.pools
                if srv.faults is not None]

    # -- ticking ----------------------------------------------------------

    def _tick_pool(self, d: int) -> List[dict]:
        with jax.default_device(self.devices[d]):
            events = self.pools[d].step()
        for ev in events:
            ev["device"] = d
        return events

    def _block_pool(self, d: int, max_ticks: Optional[int]) -> List[dict]:
        with jax.default_device(self.devices[d]):
            events = self.pools[d].step_block(max_ticks)
        for ev in events:
            ev["device"] = d
        return events

    def step(self) -> List[dict]:
        """One fleet tick: every pool steps exactly once (sequentially by
        default, one thread per device with ``parallel=True``).  Events
        are returned in device order, each tagged with its ``device``."""
        with span("fleet_step", pools=self.n_devices):
            if self._pool_exec is not None:
                futs = [self._pool_exec.submit(self._tick_pool, d)
                        for d in range(self.n_devices)]
                events = [ev for f in futs for ev in f.result()]
            else:
                events = [ev for d in range(self.n_devices)
                          for ev in self._tick_pool(d)]
        self._steps += 1
        return events

    def step_block(self, max_ticks: Optional[int] = None) -> List[dict]:
        """Serve up to ``max_ticks`` steady-state ticks PER POOL as one
        compiled dispatch each (``StreamServer.step_block``) — the
        whole-tick fast path, per device.  Pools advance independently
        (each fuses as many ticks as its own structural boundaries
        allow), so unlike ``step()`` this does not keep pools in tick
        lockstep; per-stream decision sequences are still bit-identical
        because streams never interact across pools.  Events are
        returned in device order, tagged with their ``device``.  Pools
        without ``compiled=`` just run one interpreted tick."""
        with span("fleet_step", pools=self.n_devices):
            if self._pool_exec is not None:
                futs = [self._pool_exec.submit(self._block_pool, d,
                                               max_ticks)
                        for d in range(self.n_devices)]
                events = [ev for f in futs for ev in f.result()]
            else:
                events = [ev for d in range(self.n_devices)
                          for ev in self._block_pool(d, max_ticks)]
        self._steps += 1
        return events

    def drain(self, max_steps: int = 10_000) -> List[dict]:
        """Step the fleet until no pool can make progress (in compiled
        blocks when the pools were built with ``compiled=``)."""
        events: List[dict] = []
        blocks = any(srv._compiled is not None for srv in self.pools)

        def view():
            return [(len(srv._queue),
                     [None if r is None else len(r.buf)
                      for r in srv._slots]) for srv in self.pools]

        for _ in range(max_steps):
            before = view()
            events.extend(self.step_block() if blocks else self.step())
            if view() == before:
                break
        return events

    def active_streams(self) -> List[str]:
        return [sid for srv in self.pools for sid in srv.active_streams()]

    # -- fleet telemetry ---------------------------------------------------

    def stats(self) -> dict:
        """Fleet rollup + per-device detail.  The rollup sums one small
        per-device counter vector gathered host-side — the sharded tier's
        only cross-device data motion (decisions never leave their
        device)."""
        per_device = [srv.stats() for srv in self.pools]
        vecs = [jax.device_put(
                    jnp.asarray([float(s[k]) if s[k] is not None else 0.0
                                 for k in _GATHER_KEYS], jnp.float32),
                    dev)
                for s, dev in zip(per_device, self.devices)]
        gathered = np.asarray(jnp.stack(vecs))      # host-side all-gather
        tot = dict(zip(_GATHER_KEYS, gathered.sum(axis=0).tolist()))
        total_hops = tot["speech_hops"] + tot["gated_hops"]
        fleet = {
            "decisions": int(tot["decisions"]),
            "speech_hops": int(tot["speech_hops"]),
            "gated_hops": int(tot["gated_hops"]),
            "learn_hops": int(tot["learn_hops"]),
            "rejected_streams": int(tot["rejected_streams"]),
            "queue_depth": int(tot["queue_depth"]),
            "duty_cycle": (round(tot["speech_hops"] / total_hops, 4)
                           if total_hops else None),
            "hop_wall_s": round(tot["hop_wall_s"], 4),
            "decisions_per_sec": (round(tot["decisions"]
                                        / tot["hop_wall_s"], 2)
                                  if tot["hop_wall_s"] > 0 else None),
        }
        out = {
            "devices": self.n_devices,
            "steps": self._steps,
            "streams_placed": len(self._where),
            "placement": self.policy.snapshot(),
            "fleet": fleet,
            "per_device": per_device,
        }
        if any(srv.health is not None for srv in self.pools):
            states = [srv.health.state if srv.health is not None else None
                      for srv in self.pools]
            out["health"] = {"states": states,
                             "healthy": all(s in (None, "healthy")
                                            for s in states)}
        audits = [s.get("obs", {}).get("audit") for s in per_device]
        if any(a is not None for a in audits):
            out["audit"] = {
                "violations": sum(a["violations"] for a in audits
                                  if a is not None),
                "per_device": audits,
            }
        return out

    # -- sharded snapshot bundle ------------------------------------------

    def snapshot(self, path: Optional[str] = None):
        """Bundle every pool's snapshot plus the router state into one
        unit.  In-memory form: ``{"spec": ..., "arrays": ...}`` with pool
        arrays prefixed ``d{i}_``.  With ``path``: one npz, written
        atomically (tmp + fsync + ``os.replace``), restoring
        bit-identically on an identically-configured sharded server.
        Take it at fleet tick boundaries (between ``step()`` calls)."""
        arrays: Dict[str, np.ndarray] = {}
        pool_specs = []
        for d, srv in enumerate(self.pools):
            snap = srv.snapshot()
            pool_specs.append(snap["spec"])
            for k, v in snap["arrays"].items():
                arrays[f"d{d}_{k}"] = v
        spec = {
            "version": 1,
            "kind": "sharded",
            "devices": self.n_devices,
            "router": {"next_uid": self._next_uid,
                       "where": dict(self._where),
                       "steps": self._steps,
                       "policy": self.policy.snapshot()},
            "pools": pool_specs,
        }
        if path is None:
            return {"spec": spec, "arrays": arrays}
        payload = dict(arrays)
        payload["meta"] = np.frombuffer(
            json.dumps(spec).encode("utf-8"), dtype=np.uint8)
        parent = os.path.dirname(os.path.abspath(path)) or "."
        os.makedirs(parent, exist_ok=True)
        fd, tmp = tempfile.mkstemp(prefix=".tmp.shardsnap.", suffix=".npz",
                                   dir=parent)
        try:
            with os.fdopen(fd, "wb") as f:
                np.savez(f, **payload)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)                   # atomic commit
        except Exception:
            try:
                os.remove(tmp)
            except OSError:
                pass
            raise
        return path

    def restore(self, snap) -> None:
        """Restore a sharded bundle (path or in-memory dict) into THIS
        freshly constructed, identically-configured sharded server —
        same device count, per-pool configuration and wiring.  Resumes
        bit-identically, router placement state included."""
        if isinstance(snap, (str, os.PathLike)):
            with np.load(snap, allow_pickle=False) as data:
                spec = json.loads(bytes(data["meta"]).decode("utf-8"))
                arrays = {k: data[k] for k in data.files if k != "meta"}
        else:
            spec, arrays = snap["spec"], snap["arrays"]
        if spec.get("kind") != "sharded" or spec.get("version") != 1:
            raise ValueError(f"not a v1 sharded snapshot bundle: "
                             f"kind={spec.get('kind')!r} "
                             f"version={spec.get('version')!r}")
        if spec["devices"] != self.n_devices:
            raise ValueError(f"snapshot has {spec['devices']} device "
                             f"pools, this server has {self.n_devices}")
        for d, (srv, pool_spec) in enumerate(zip(self.pools,
                                                 spec["pools"])):
            prefix = f"d{d}_"
            pool_arrays = {k[len(prefix):]: v for k, v in arrays.items()
                           if k.startswith(prefix)}
            with jax.default_device(self.devices[d]):
                srv.restore({"spec": pool_spec, "arrays": pool_arrays})
        router = spec["router"]
        self._next_uid = int(router["next_uid"])
        self._where = {sid: int(d) for sid, d in router["where"].items()}
        self._steps = int(router["steps"])
        self.policy.restore(router["policy"])
