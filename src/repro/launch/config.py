"""The public serving-configuration schema (ISSUE 9 API redesign).

One keyword-only, versioned config family shared by every serving
entry point — `ContinuousBatchingEngine` (one engine, and one per cell
of a `sweep` grid) and `FleetEngine` (multi-node disaggregation) both
construct from :class:`ServingConfig`; the fleet layer adds its knobs
in :class:`FleetConfig`, which *embeds* a ServingConfig per node
instead of duplicating its fields.

Schema contract (locked by tests/test_serving_api.py):

  * keyword-only construction — positional field order is not API;
  * ``to_dict()`` / ``from_dict()`` round-trip exactly, including the
    nested ``kv_cache`` (`runtime.kv_cache.KVCacheConfig`) and
    ``engine`` blocks;
  * ``from_dict`` REJECTS unknown keys (`ValueError` naming them) — a
    typo'd knob must fail loudly, not silently fall back to defaults;
  * every dict carries a ``schema`` stamp; ``from_dict`` refuses
    documents newer than it understands.

``repro.launch.serving_engine.EngineConfig`` remains as a deprecated
alias (same fields, accepts the legacy positional form) that warns on
construction — see the shim there.

Pure Python, JAX-free, like the rest of the analytic serving stack.
"""
from __future__ import annotations

import dataclasses
import math
import random
from typing import ClassVar, Dict, Optional, Tuple

from repro.core.interconnect import MeasuredTraffic
from repro.runtime.kv_cache import KVCacheConfig


def _check_known_keys(cls, d: Dict) -> None:
    """Unknown-key rejection shared by every ``from_dict``."""
    known = {f.name for f in dataclasses.fields(cls)}
    unknown = sorted(set(d) - known)
    if unknown:
        raise ValueError(
            f"unknown {cls.__name__} keys: {unknown} "
            f"(known: {sorted(known)})")


def _check_schema(cls, d: Dict) -> Dict:
    """Pop + validate the version stamp; returns a shallow copy of
    ``d`` without it."""
    d = dict(d)
    ver = d.pop("schema", 1)
    if not isinstance(ver, int) or ver < 1:
        raise ValueError(f"bad {cls.__name__} schema stamp: {ver!r}")
    if ver > cls.SCHEMA_VERSION:
        raise ValueError(
            f"{cls.__name__} document has schema {ver}, this build "
            f"understands <= {cls.SCHEMA_VERSION}")
    return d


@dataclasses.dataclass(kw_only=True)
class ServingConfig:
    """Per-engine serving knobs (one PICNIC node).

    The field set (and every default) is the former ``EngineConfig``
    — promoting it to a keyword-only, versioned schema is the ISSUE 9
    API consolidation; the semantics of each knob are unchanged and
    documented inline.
    """
    SCHEMA_VERSION: ClassVar[int] = 1

    max_batch: int = 8          # KV-cache slots = max co-resident requests
    queue_limit: int = 256      # admission queue bound (then reject)
    decode_quantum: int = 4     # decode rounds per allowed prefill
    ccpg: bool = False          # cluster power gating (paper §II-E)
    dynamic_ccpg: bool = False  # full ClusterWake latency per iteration
    #                             instead of the folded pre-wake residue
    overlap: float = 0.0        # fraction of decode C2C hidden by compute
    max_iters: int = 2_000_000  # safety valve for the event loop
    # -- paged KV cache (None = capacity unbounded, paging off; the
    #    default path stays byte-identical to timeline_golden.json) -----
    kv_cache: Optional[KVCacheConfig] = None
    # chunked prefill: prompts longer than this are prefilled in chunks
    # of at most this many tokens, one chunk per engine iteration, so a
    # long prompt cannot monopolize an iteration (0 = off)
    chunked_prefill_tokens: int = 0
    # columnar TimelineIR recording (the fast simulation core).  False
    # restores the one-dataclass-per-append reference recorder — both
    # are byte-identical (tests/test_fastpath.py); the toggle exists for
    # the equivalence tests and the microbench before/after measurement.
    columnar_timeline: bool = True

    def to_dict(self) -> Dict:
        d = {"schema": self.SCHEMA_VERSION}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name == "kv_cache" and v is not None:
                v = dataclasses.asdict(v)
            d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "ServingConfig":
        d = _check_schema(cls, d)
        _check_known_keys(cls, d)
        kv = d.get("kv_cache")
        if isinstance(kv, dict):
            _check_known_keys(KVCacheConfig, kv)
            d["kv_cache"] = KVCacheConfig(**kv)
        return cls(**d)


@dataclasses.dataclass(frozen=True)
class LinkFault:
    """One photonic link-degradation window.

    Thermal drift of the ring resonators raises the BER past the FEC
    budget for ``[t_start, t_end)``; every KV handoff sent during the
    window re-transmits ``retransmit_frac`` of its payload, priced on
    the timeline as ``C2CTransfer(phase="retransmit")`` riding the same
    link model as the payload itself.
    """
    t_start: float
    t_end: float
    retransmit_frac: float = 0.1


@dataclasses.dataclass(frozen=True)
class NodeFault:
    """One fleet-node crash/recover event: the node freezes at
    ``t_fail`` holding its in-flight KV (lost), and rejoins the fleet at
    ``t_recover`` (inf = never).  The router only learns of the death
    when the heartbeat gap crosses ``FaultConfig.heartbeat_dead_s``."""
    node: int
    t_fail: float
    t_recover: float = math.inf


@dataclasses.dataclass(frozen=True)
class WakeFault:
    """CCPG wake failures: the first ``failures`` ClusterWake attempts
    on this node time out (regulator settle never completes); each
    failed attempt costs ``FaultConfig.wake_timeout_s`` plus the
    RestartPolicy backoff before the router retries or falls back to
    the awake pool."""
    node: int
    failures: int = 1


@dataclasses.dataclass(kw_only=True)
class FaultConfig:
    """A reproducible fault schedule for the fleet (ISSUE 10).

    Declarative and fully deterministic: the schedule is data, not
    callbacks, so the same FaultConfig replayed against the same trace
    yields a hex-identical report and timeline.  ``seeded()`` draws a
    schedule from a seed for fault-rate sweeps.  An empty schedule is
    inert — the fleet takes the exact zero-fault code paths.
    """
    SCHEMA_VERSION: ClassVar[int] = 1

    seed: int = 0
    links: Tuple[LinkFault, ...] = ()
    nodes: Tuple[NodeFault, ...] = ()
    wakes: Tuple[WakeFault, ...] = ()
    # CCPG wake retry policy (RestartPolicy on the DES clock)
    wake_timeout_s: float = 2e-3
    wake_retries: int = 3
    wake_backoff_base_s: float = 1e-3
    wake_backoff_max_s: float = 16e-3
    # DES-clock HeartbeatMonitor thresholds: a crashed node keeps
    # receiving work until its heartbeat gap crosses heartbeat_dead_s
    # (bounded pile-up, drained and re-routed at detection)
    heartbeat_suspect_s: float = 5e-3
    heartbeat_dead_s: float = 20e-3
    # degraded-mode load shedding: when capacity has dropped, shed the
    # re-routed requests whose TTFT deadline is already infeasible
    # (counted as fault_shed, never silent) instead of recomputing them
    shed_infeasible: bool = True

    def active(self) -> bool:
        """Inert configs (no scheduled faults) take zero-fault paths."""
        return bool(self.links or self.nodes or self.wakes)

    @classmethod
    def seeded(cls, *, seed: int, n_nodes: int, horizon_s: float,
               link_windows: int = 0, node_crashes: int = 0,
               wake_faults: int = 0, recover: bool = True,
               **knobs) -> "FaultConfig":
        """Draw a reproducible schedule: same seed -> same faults."""
        rng = random.Random(seed)
        links = tuple(sorted(
            (LinkFault(t_start=(t0 := rng.uniform(0.05, 0.70) * horizon_s),
                       t_end=t0 + rng.uniform(0.05, 0.25) * horizon_s,
                       retransmit_frac=rng.uniform(0.05, 0.30))
             for _ in range(link_windows)),
            key=lambda w: (w.t_start, w.t_end)))
        crash_ids = sorted(rng.sample(range(n_nodes),
                                      min(node_crashes, n_nodes)))
        nodes = tuple(
            NodeFault(node=i,
                      t_fail=(tf := rng.uniform(0.10, 0.60) * horizon_s),
                      t_recover=(tf + rng.uniform(0.10, 0.30) * horizon_s
                                 if recover else math.inf))
            for i in crash_ids)
        wake_ids = sorted(rng.sample(range(n_nodes),
                                     min(wake_faults, n_nodes)))
        wakes = tuple(WakeFault(node=i, failures=1 + rng.randrange(2))
                      for i in wake_ids)
        return cls(seed=seed, links=links, nodes=nodes, wakes=wakes,
                   **knobs)

    def to_dict(self) -> Dict:
        d = {"schema": self.SCHEMA_VERSION}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name in ("links", "nodes", "wakes"):
                v = [dataclasses.asdict(x) for x in v]
            d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "FaultConfig":
        d = _check_schema(cls, d)
        _check_known_keys(cls, d)
        for key, sub in (("links", LinkFault), ("nodes", NodeFault),
                         ("wakes", WakeFault)):
            items = d.get(key)
            if items is not None:
                built = []
                for x in items:
                    if isinstance(x, dict):
                        _check_known_keys(sub, x)
                        x = sub(**x)
                    built.append(x)
                d[key] = tuple(built)
        return cls(**d)


@dataclasses.dataclass(kw_only=True)
class FleetConfig:
    """Fleet-level knobs for `launch.fleet_engine.FleetEngine`: pool shape,
    router policy, KV-handoff pricing and node autoscaling.  Every node
    runs one :class:`ServingConfig` (the ``engine`` block).

    Schema 2 adds the optional ``fault`` block (:class:`FaultConfig`);
    absent/None keeps the zero-fault fleet byte-identical to schema 1.
    """
    SCHEMA_VERSION: ClassVar[int] = 2

    # pool shape.  handoff=True splits the fleet into n_prefill
    # dedicated prefill nodes and n_decode decode nodes with priced KV
    # handoff between them; handoff=False runs n_prefill + n_decode
    # COMBINED nodes (plain data-parallel replication, the
    # disaggregation baseline) — node count is preserved either way so
    # ratio sweeps compare like for like.
    n_prefill: int = 1
    n_decode: int = 1
    handoff: bool = True
    # per-node engine schema (shared by every node of the fleet)
    engine: ServingConfig = dataclasses.field(default_factory=ServingConfig)
    # router backlog bound: requests held (NOT rejected) while every
    # awake prefill node's admission queue is full; beyond this the
    # router itself rejects
    queue_limit: int = 4096
    # SLO-aware admission: reject at the ROUTER when the estimated
    # queue-wait + prefill time of the least-loaded node already blows
    # the request's TTFT deadline (deadline-free requests never reject
    # here; off by default so the degenerate fleet stays bare-engine
    # identical)
    slo_admission: bool = False
    # CCPG-driven node autoscaling: nodes beyond min_awake (per pool)
    # start asleep; the router wakes one — paying the REAL ClusterWake
    # cluster-walk latency on that node's timeline — when every awake
    # node of the pool carries more than scale_up_queue outstanding
    # units of work; drained nodes above min_awake go back to sleep.
    autoscale: bool = False
    min_awake: int = 1
    scale_up_queue: int = 4
    # KV-handoff wire pricing: bytes/token of resident context moved
    # prefill -> decode over the fabric.  None derives the analytic
    # Table-II-style per-token KV footprint from the model config
    # (`runtime.kv_cache.kv_bytes_per_token`, or the paged cache's own
    # bytes_per_token when the engine block carries one).
    handoff_bytes_per_token: Optional[int] = None
    # opt-in measured pricing (launch/collective_capture.py): adds the
    # HLO-measured prefill collective wire bytes per handoff — the
    # resharding traffic of re-establishing the KV on the destination
    # node's chiplets, which the analytic footprint ignores.
    measured_handoff: Optional[MeasuredTraffic] = None
    max_iters: int = 8_000_000  # safety valve over ALL node steps
    # deterministic fault injection (ISSUE 10): link-degradation
    # windows, CCPG wake failures and node crash/recover events.  None
    # (or an inert FaultConfig) keeps every zero-fault code path.
    fault: Optional[FaultConfig] = None

    @property
    def n_nodes(self) -> int:
        return self.n_prefill + self.n_decode

    def to_dict(self) -> Dict:
        d = {"schema": self.SCHEMA_VERSION}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if f.name == "engine":
                v = v.to_dict()
            elif f.name == "measured_handoff" and v is not None:
                v = dataclasses.asdict(v)
            elif f.name == "fault" and v is not None:
                v = v.to_dict()
            d[f.name] = v
        return d

    @classmethod
    def from_dict(cls, d: Dict) -> "FleetConfig":
        d = _check_schema(cls, d)
        _check_known_keys(cls, d)
        eng = d.get("engine")
        if isinstance(eng, dict):
            d["engine"] = ServingConfig.from_dict(eng)
        mh = d.get("measured_handoff")
        if isinstance(mh, dict):
            _check_known_keys(MeasuredTraffic, mh)
            d["measured_handoff"] = MeasuredTraffic(**mh)
        fl = d.get("fault")
        if isinstance(fl, dict):
            d["fault"] = FaultConfig.from_dict(fl)
        return cls(**d)
