"""Continuous-batching PICNIC serving engine (discrete-event, multi-user).

Unifies three layers that previously only worked one request at a time:

  * ``launch/serve.py``     — the JAX functional server (slot recycling),
  * ``launch/scheduler.py`` — the abstract admission policy with a FIXED
    per-iteration :class:`CostModel`,
  * ``core/simulator.py``   — the analytic single-stream PicnicSimulator,

into one engine whose iteration costs come from the *mapped* PICNIC cycle
model instead of constants:

  arrival trace (Poisson / replay)
    -> admission queue (bounded, rejects at queue_limit)
    -> iteration-level scheduler: deficit-based prefill/decode interleave
       (same starvation-free policy as launch/scheduler.py), per-request
       KV-context tracking, preemption-free decode
    -> batched decode cost path (CycleModel.batched_token_decode_cycles):
       weight-stationary CIM crossbar reads amortized across the batch,
       per-request KV-scratchpad and C2C activation traffic charged fully
    -> CCPG cluster residency: co-batched requests share the active
       cluster, wake residue charged once per iteration; idle gaps between
       arrivals drop to scratchpad-retention power
    -> TimelineIR (core/timeline.Timeline): every round appends typed
       events (ComputeSpan / C2CTransfer / ClusterWake / ClusterSleep /
       TokenEmit); time, span-integrated energy, occupancy and C2C bytes
       all come from that one integrator — `engine.timeline` exports a
       chrome://tracing JSON of the whole run
    -> ServingReport: p50/p99 TTFT + end-to-end latency, aggregate
       tokens/s, tokens/J, queue-depth timeline, batch occupancy.

With ``EngineConfig.kv_cache`` set (runtime/kv_cache.KVCacheConfig) the
engine is **capacity-aware**: KV lives in fixed-size blocks over the
finite chiplet-scratchpad budget with a DRAM-hub spill tier behind the
photonic link — admission checks free *blocks* (not just free slots),
spills/remote reads land on the timeline as ``C2CTransfer`` events plus
DRAM access energy, watermark pressure preempts the newest resident
(recompute-on-resume), and ``chunked_prefill_tokens`` spreads long
prompts over several iterations.  The default (``kv_cache=None``,
capacity unbounded) stays byte-identical to the pre-paging engine —
locked by tests/golden/timeline_golden.json.

Pure Python + numpy on top of ``repro.core`` — no JAX import.  The
iteration loop is the repo's FAST SIMULATION CORE (ISSUE 5): slot state
lives in structure-of-arrays form (a numpy admit-seq column whose argmax
picks preemption victims, parallel slot-ordered active index/request/id/
context-offset lists, a running resident-context sum, an O(1)
request-id -> slot map, and a deferred-finish heap on the capacity-
unbounded path), per-iteration cycle costs come from the memoized
`CycleModel` (one O(layers) walk per distinct batch shape, O(1) affine
lookups after), and every event lands in the columnar TimelineIR
recorder — all byte-identical to the reference object path
(`EngineConfig.columnar_timeline=False` + `CycleModel(memoize=False)`),
locked by tests/test_fastpath.py and measured by
benchmarks/microbench.py.

  PYTHONPATH=src python examples/serve_continuous.py
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from bisect import bisect_left
from collections import deque
from heapq import heappop, heappush
from typing import (Callable, Deque, Dict, Iterable, List, Optional,
                    Sequence, Tuple)

import numpy as np

from repro.core.ccpg import CCPGModel
from repro.core.energy import E_DRAM_ACCESS
from repro.core.interconnect import c2c_average_power
from repro.core.scheduling import ChipletAllocation, allocate_chiplets
from repro.core.simulator import PicnicSimulator
from repro.core.timeline import Timeline
from repro.launch.config import ServingConfig
from repro.launch.scheduler import EventKind, Request, deadline_at_risk
from repro.runtime.kv_cache import (BlockAllocator, KVCacheConfig,
                                    OutOfBlocks)


@dataclasses.dataclass(order=True)
class TrackedRequest(Request):
    """A scheduler Request plus the per-request KV-context the batched
    cycle model charges for (KV-scratchpad reads are per-request)."""
    context: int = dataclasses.field(compare=False, default=0)
    admit_seq: int = dataclasses.field(compare=False, default=-1)
    # prompt token ids (prefix sharing only): the allocator chain-hashes
    # these to find/index shareable prefix blocks.  None = this request
    # never shares (the simulator otherwise has no token identities).
    prompt_tokens: Optional[Tuple[int, ...]] = dataclasses.field(
        compare=False, default=None, repr=False)

    @property
    def latency(self) -> Optional[float]:
        if self.finished_at is None:
            return None
        return self.finished_at - self.arrival

    @property
    def ttft(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.arrival


# ---------------------------------------------------------------------------
# Arrival traces
# ---------------------------------------------------------------------------

_TOKEN_STRIDE = 1 << 24     # id-space stride between synthetic vocab pools


class Trace(List[TrackedRequest]):
    """An arrival trace: a list of :class:`TrackedRequest` with the two
    construction recipes as classmethods — ``Trace.poisson(...)`` for
    open-loop synthetic arrivals and ``Trace.replay(rows)`` for recorded
    ones (the ISSUE 9 unified trace surface, re-exported from
    ``repro.launch``).  It subclasses ``list`` so every existing
    consumer (engines, sweeps, benches) takes it unchanged; the legacy
    ``poisson_trace`` / ``replay_trace`` module functions delegate here
    and return the same object."""

    @classmethod
    def poisson(cls, n_requests: int, rate_rps: float, *, seed: int = 0,
                prompt_len: int = 512, max_new: int = 64,
                prompt_jitter: float = 0.25,
                deadline_ttft: Optional[float] = None,
                prefix_len: int = 0, prefix_frac: float = 0.9,
                prefix_groups: int = 1) -> "Trace":
        """Open-loop Poisson arrivals at ``rate_rps`` requests/second,
        with prompt lengths jittered uniformly by +-``prompt_jitter``.
        Arrivals are monotone by construction (cumulative exponential
        gaps), so ``run()`` never has to re-sort this trace.

        With ``prefix_len > 0`` every request carries synthetic
        ``prompt_tokens``: a ``prefix_frac`` share of requests open with
        one of ``prefix_groups`` shared system prompts of ``prefix_len``
        tokens (positive ids, disjoint per group) followed by
        per-request unique tokens (negative ids, disjoint per request)
        — the prefix-heavy workload the sharing allocator deduplicates.
        ``prefix_len = 0`` (the default) draws nothing extra from the
        RNG, so default traces are byte-identical to the pre-sharing
        generator."""
        rng = np.random.default_rng(seed)
        t = 0.0
        out = cls()
        for i in range(n_requests):
            t += float(rng.exponential(1.0 / rate_rps))
            p = max(1, int(round(prompt_len
                                 * (1.0 + prompt_jitter
                                    * float(rng.uniform(-1.0, 1.0))))))
            tokens: Optional[Tuple[int, ...]] = None
            if prefix_len > 0:
                shares = float(rng.uniform()) < prefix_frac
                g = (int(rng.integers(prefix_groups))
                     if prefix_groups > 1 else 0)
                uniq = -(i * _TOKEN_STRIDE + 1)     # request-private pool
                if shares:
                    pre = min(prefix_len, p - 1)
                    tokens = (tuple(g * _TOKEN_STRIDE + 1 + j
                                    for j in range(pre))
                              + tuple(uniq - j for j in range(p - pre)))
                else:
                    tokens = tuple(uniq - j for j in range(p))
            out.append(TrackedRequest(arrival=t, request_id=i,
                                      prompt_len=p, max_new=max_new,
                                      deadline_ttft=deadline_ttft,
                                      prompt_tokens=tokens))
        return out

    @classmethod
    def replay(cls, rows: Iterable) -> "Trace":
        """Replay recorded arrivals.  ``rows`` are ``(arrival_s,
        prompt_len, max_new)`` or ``(arrival_s, prompt_len, max_new,
        deadline_ttft)`` tuples, or dicts with those keys
        (``deadline_ttft`` optional in both forms).  The returned trace
        is sorted by arrival ONCE here (stable, after request ids are
        assigned in row order) so every ``run()`` re-use skips the
        per-run re-sort."""
        out = cls()
        for i, row in enumerate(rows):
            if isinstance(row, dict):
                out.append(TrackedRequest(
                    arrival=float(row["arrival_s"]), request_id=i,
                    prompt_len=int(row["prompt_len"]),
                    max_new=int(row["max_new"]),
                    deadline_ttft=row.get("deadline_ttft")))
            else:
                arrival, prompt_len, max_new, *rest = row
                deadline = rest[0] if rest else None
                out.append(TrackedRequest(
                    arrival=float(arrival), request_id=i,
                    prompt_len=int(prompt_len), max_new=int(max_new),
                    deadline_ttft=(None if deadline is None
                                   else float(deadline))))
        out.sort()      # stable on arrival — same order `sorted()` gave
        return out


def poisson_trace(n_requests: int, rate_rps: float, **kw) -> Trace:
    """Legacy spelling of :meth:`Trace.poisson` (same signature)."""
    return Trace.poisson(n_requests, rate_rps, **kw)


def replay_trace(rows: Iterable) -> Trace:
    """Legacy spelling of :meth:`Trace.replay`."""
    return Trace.replay(rows)


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

# legacy positional field order of the pre-ISSUE-9 EngineConfig — the
# shim maps positional construction through it
_LEGACY_ENGINE_FIELDS = tuple(
    f.name for f in dataclasses.fields(ServingConfig))


class EngineConfig(ServingConfig):
    """DEPRECATED alias of :class:`repro.launch.config.ServingConfig`.

    Same fields and defaults; still accepts the legacy positional form.
    Construction emits a ``DeprecationWarning`` (asserted by
    tests/test_serving_api.py) — new code should build the keyword-only,
    versioned ``ServingConfig`` instead."""

    def __init__(self, *args, **kw):
        warnings.warn(
            "EngineConfig is deprecated; construct repro.launch."
            "ServingConfig (keyword-only, to_dict/from_dict) instead",
            DeprecationWarning, stacklevel=2)
        if args:
            if len(args) > len(_LEGACY_ENGINE_FIELDS):
                raise TypeError(
                    f"EngineConfig takes at most "
                    f"{len(_LEGACY_ENGINE_FIELDS)} positional arguments")
            kw = {**dict(zip(_LEGACY_ENGINE_FIELDS, args)), **kw}
        super().__init__(**kw)


@dataclasses.dataclass
class KVCacheStats:
    """Paged-KV accounting for one run (``engine.kv_stats``).  Kept out
    of ServingReport so the report schema — and its golden byte-identity
    — is untouched when paging is off."""
    n_blocks: int
    dram_blocks: int
    block_tokens: int
    preemptions: int            # watermark/OOM evictions (recompute)
    spilled_blocks: int
    spilled_bytes: int          # scratchpad -> DRAM hub over the C2C link
    dram_read_bytes: int        # per-iteration remote KV reads
    recomputed_tokens: int      # prefill work re-done after preemption
    peak_blocks_used: int
    infeasible_rejects: int     # could never fit even an empty cache
    # -- prefix sharing / copy-on-write (zeroed when sharing is off) ----
    prefix_sharing: bool = False
    prefix_hits: int = 0        # whole blocks adopted from the index
    prefix_hit_tokens: int = 0  # prompt tokens never (re)computed
    prefix_hit_rate: float = 0.0   # hit tokens / total prompt tokens
    cow_forks: int = 0
    cow_copied_bytes: int = 0
    shared_blocks_now: int = 0  # blocks with >= 2 readers at run end
    shared_blocks_peak: int = 0

    def row(self) -> Dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class ServingReport:
    """Aggregate serving metrics over one trace."""
    n_requests: int
    finished: int
    rejected: int
    wall_s: float
    busy_s: float
    idle_s: float
    tokens_generated: int
    tokens_prefilled: int
    tokens_per_s: float
    energy_J: float
    tokens_per_J: float
    p50_latency_s: float
    p99_latency_s: float
    p50_ttft_s: float
    p99_ttft_s: float
    mean_batch_occupancy: float
    max_queue_depth: int
    queue_depth: List[Tuple[float, int]]   # (clock_s, waiting) timeline
    c2c_bytes_total: int
    ccpg: bool
    # fleet attribution (launch/fleet_engine.py): which node produced this
    # report and its pool role ("prefill" | "decode" | "combined").
    # Both stay None on single-node runs — and row() then omits them —
    # so every pre-fleet BENCH_*.json artifact and the regression gate
    # remain byte-identical.
    node_id: Optional[int] = None
    pool: Optional[str] = None

    def row(self) -> Dict:
        def _r(x: float, nd: int):
            # NaN percentiles (all requests rejected -> finished == 0)
            # become None so the row stays strict-JSON serializable
            # instead of emitting bare `NaN` tokens
            return None if math.isnan(x) else round(x, nd)
        out = {
            "requests": self.n_requests,
            "finished": self.finished,
            "rejected": self.rejected,
            "ccpg": self.ccpg,
            "tokens_per_s": _r(self.tokens_per_s, 1),
            "tokens_per_J": _r(self.tokens_per_J, 1),
            "p50_latency_s": _r(self.p50_latency_s, 4),
            "p99_latency_s": _r(self.p99_latency_s, 4),
            "p50_ttft_s": _r(self.p50_ttft_s, 4),
            "p99_ttft_s": _r(self.p99_ttft_s, 4),
            "mean_batch": _r(self.mean_batch_occupancy, 2),
            "max_queue_depth": self.max_queue_depth,
            "wall_s": _r(self.wall_s, 4),
        }
        if self.node_id is not None:
            out["node_id"] = self.node_id
            out["pool"] = self.pool
        return out

    def summary(self) -> str:
        lines = [
            f"ServingReport (ccpg={'on' if self.ccpg else 'off'})",
            f"  requests          {self.finished}/{self.n_requests} finished"
            f", {self.rejected} rejected",
            f"  wall clock        {self.wall_s:.3f} s "
            f"(busy {self.busy_s:.3f}, idle {self.idle_s:.3f})",
            f"  tokens            {self.tokens_generated} generated, "
            f"{self.tokens_prefilled} prefilled",
            f"  throughput        {self.tokens_per_s:.1f} tok/s (generated)",
            f"  efficiency        {self.tokens_per_J:.1f} tok/J "
            f"({self.energy_J:.3f} J total)",
            f"  latency p50/p99   {self.p50_latency_s * 1e3:.1f} / "
            f"{self.p99_latency_s * 1e3:.1f} ms",
            f"  TTFT    p50/p99   {self.p50_ttft_s * 1e3:.1f} / "
            f"{self.p99_ttft_s * 1e3:.1f} ms",
            f"  batch occupancy   {self.mean_batch_occupancy:.2f} "
            f"(max queue depth {self.max_queue_depth})",
        ]
        return "\n".join(lines)


@dataclasses.dataclass
class SweepCell:
    """One point of a :func:`repro.launch.sweep` grid: a model config, a
    trace and the engine settings to serve it with.  Cells with
    ``sim=None`` share one default `PicnicSimulator`."""
    key: str
    cfg: object
    trace: Sequence[TrackedRequest]
    engine: ServingConfig = dataclasses.field(
        default_factory=ServingConfig)
    sim: Optional[PicnicSimulator] = None


@dataclasses.dataclass
class SweepResult:
    """One cell's report, with its paged-KV stats (``None`` unpaged)."""
    key: str
    report: ServingReport
    kv_stats: Optional[KVCacheStats]


class ContinuousBatchingEngine:
    """Iteration-level continuous batching over the PICNIC cycle model.

    Each engine iteration either PREFILLs one queued request into a free
    KV slot (deficit-gated, deadline-overridable — the policy from
    launch/scheduler.py) or runs one batched DECODE round advancing every
    resident request by one token.  Decode is preemption-free: an admitted
    request keeps its slot until it emits ``max_new`` tokens.
    """

    def __init__(self, cfg, sim: Optional[PicnicSimulator] = None,
                 engine: Optional[ServingConfig] = None,
                 alloc: Optional[ChipletAllocation] = None):
        self.cfg = cfg
        self.sim = sim if sim is not None else PicnicSimulator()
        self.engine = engine if engine is not None else ServingConfig()
        # fleet hook: called at every request-finish site with the
        # finished request; returning True transfers KV ownership to the
        # caller (the engine then skips its own `kv.free`).  Installed
        # once by FleetEngine on prefill nodes; survives reset() so a
        # re-run keeps its wiring.  None (the default) is checked with
        # `is not None` at each site, keeping the single-node float/event
        # sequence byte-identical.
        self.on_finish: Optional[Callable[[TrackedRequest], bool]] = None
        # `alloc` lets the engines of a fleet share one allocation
        # object (allocate_chiplets is deterministic, so sharing changes
        # id()-keyed memo hit rates, never results); default: private.
        self.alloc: ChipletAllocation = (
            alloc if alloc is not None
            else allocate_chiplets(cfg, self.sim.tile))
        ccpg_model: CCPGModel = self.sim.ccpg_model
        self._busy_power = ccpg_model.system_power(
            self.alloc.n_chiplets, ccpg=self.engine.ccpg)
        self._idle_power = ccpg_model.idle_power(
            self.alloc.n_chiplets, ccpg=self.engine.ccpg)
        # static mode folds the pre-wake residue into the iteration cost;
        # dynamic mode charges the full walk as ClusterWake events instead
        self._residue_ccpg = self.engine.ccpg and not self.engine.dynamic_ccpg
        self._dyn_wake = self.engine.ccpg and self.engine.dynamic_ccpg
        self._bandwidth_Bps = self.sim.link.bandwidth_Bps
        self._cm = self.sim.cycle_model
        self._decode_names: Dict[int, str] = {}   # b -> "decode:b{b}"
        self.reset()

    # ------------------------------------------------------------------
    def reset(self) -> None:
        e = self.engine
        # ALL time/energy accounting lives in the TimelineIR accumulator —
        # the engine appends per-round events and never charges privately
        self.timeline = Timeline(link=self.sim.link,
                                 columnar=e.columnar_timeline)
        self.queue: Deque[TrackedRequest] = deque()
        self.slots: List[Optional[TrackedRequest]] = [None] * e.max_batch
        # -- SoA mirrors of the slot table (the fast-path state): the
        # per-iteration decisions read these columns and running
        # aggregates instead of walking the request-object list.
        #   _seq_col              per-slot admit-seq column (victim pick =
        #                         one argmax; -1 encodes a free slot)
        #   _active_idx           sorted occupied-slot indices (the round's
        #                         iteration order, no occupancy scan)
        #   _ctx_sum              running sum of resident contexts (the
        #                         batched cycle model's only context input)
        #   _slot_of              O(1) request-id -> slot map replacing the
        #                         `next(i for i, s ...)` identity scans
        self._seq_col = np.full(e.max_batch, -1, dtype=np.int64)
        # _active_idx / _active_reqs / _active_rids / _active_ctx0 are
        # PARALLEL lists in slot order — the decode round reads them
        # directly instead of rebuilding per-round comprehensions over
        # `slots`.  _active_ctx0 holds each resident's context MINUS the
        # round counter at admission: every resident gains one context
        # token per round, so its exact current context is
        # ``ctx0 + _round_no`` at any time — no per-round writes needed
        # to hand the cycle-model fallback a real per-request list.
        self._active_idx: List[int] = []
        self._active_reqs: List[TrackedRequest] = []
        self._active_rids: List[int] = []
        self._active_ctx0: List[int] = []
        self._ctx_sum = 0
        self._slot_of: Dict[int, int] = {}
        # deferred-finish schedule (capacity-unbounded path only): decode
        # is preemption-free and every resident advances one token per
        # round, so a request admitted with `k` tokens to go finishes in
        # EXACTLY `k` rounds — (finish_round, slot) entries in a heap
        # replace the per-round per-resident countdown, and the request
        # object's generated/context are synced (to their exact final
        # values) at finish.  The paged path keeps per-round object
        # updates: preemption reads resident state mid-flight.
        self._round_no = 0
        self._finish_heap: List[Tuple[int, int]] = []
        # decode-cost affine snapshot per batch size (see CycleModel
        # .decode_affine): valid at overlap == 0, revalidated against the
        # model's calibration version every round, with the CCPG residue
        # and clock frequency snapshotted per run
        self._affine_by_b: Dict[int, tuple] = {}
        self._use_affine = e.overlap == 0.0
        self._residue_cyc = (self.sim.ccpg_model.wake_overhead_cycles(
            self.alloc) if self._residue_ccpg else 0)
        self._freq_hz = self.sim.tile.frequency_hz
        # cleared by run() when no request in the trace carries a TTFT
        # deadline (the at-risk test is then statically False); direct
        # step() drivers keep the full per-iteration check
        self._any_deadline = True
        self.decode_credit = 0
        self.rejected = 0
        self.events: List[Tuple[float, EventKind, int]] = []
        self.queue_depth: List[Tuple[float, int]] = []
        self._tokens_prefilled = 0
        # -- paged KV state (None/zeroed on the default infinite path) --
        self.kv: Optional[BlockAllocator] = (
            BlockAllocator(e.kv_cache, on_spill=self._on_kv_spill,
                           on_cow=self._on_kv_cow)
            if e.kv_cache is not None else None)
        self._prefix_on = (e.kv_cache is not None
                           and e.kv_cache.prefix_sharing)
        # chain-hash + probe memos: hashing a prompt is O(len/block) — do
        # it once per request, and re-probe the index only after it
        # changed (the admission check runs every iteration)
        self._chain_cache: Dict[int, List[int]] = {}
        self._probe_cache: Dict[int, Tuple[int, int]] = {}
        self._partial: Optional[List] = None   # [req, done, target, slot]
        self._admit_counter = 0
        self._kv_fetch_bytes = 0
        self._preemptions = 0
        self._recomputed_tokens = 0
        self._kv_rejected_infeasible = 0

    @property
    def clock(self) -> float:
        return self.timeline.now

    @property
    def kv_stats(self) -> Optional[KVCacheStats]:
        """Paged-KV accounting for the last run (None with paging off)."""
        if self.kv is None:
            return None
        c = self.kv.cfg
        kv = self.kv
        prompt_total = self._tokens_prefilled + kv.shared_tokens_saved
        return KVCacheStats(
            n_blocks=c.n_blocks, dram_blocks=c.dram_blocks,
            block_tokens=c.block_tokens, preemptions=self._preemptions,
            spilled_blocks=kv.spilled_blocks,
            spilled_bytes=kv.spilled_bytes,
            dram_read_bytes=self._kv_fetch_bytes,
            recomputed_tokens=self._recomputed_tokens,
            peak_blocks_used=kv.peak_used,
            infeasible_rejects=self._kv_rejected_infeasible,
            prefix_sharing=c.prefix_sharing,
            prefix_hits=kv.prefix_hits,
            prefix_hit_tokens=kv.shared_tokens_saved,
            prefix_hit_rate=(kv.shared_tokens_saved / prompt_total
                             if prompt_total else 0.0),
            cow_forks=kv.cow_forks,
            cow_copied_bytes=kv.cow_copied_bytes,
            shared_blocks_now=kv.n_shared_blocks,
            shared_blocks_peak=kv.peak_shared_blocks)

    # ------------------------------------------------------------------
    # SoA slot bookkeeping: `slots` (request objects) and the numpy
    # columns are updated together through these two helpers only.
    def _slot_occupy(self, i: int, req: TrackedRequest) -> None:
        self.slots[i] = req
        self._seq_col[i] = req.admit_seq
        pos = bisect_left(self._active_idx, i)
        self._active_idx.insert(pos, i)
        self._active_reqs.insert(pos, req)
        self._active_rids.insert(pos, req.request_id)
        self._active_ctx0.insert(pos, req.context - self._round_no)
        self._ctx_sum += req.context
        self._slot_of[req.request_id] = i

    def _slot_release(self, i: int) -> TrackedRequest:
        req = self.slots[i]
        self.slots[i] = None
        self._seq_col[i] = -1
        pos = bisect_left(self._active_idx, i)
        del self._active_idx[pos]
        del self._active_reqs[pos]
        del self._active_rids[pos]
        del self._active_ctx0[pos]
        self._ctx_sum -= req.context
        del self._slot_of[req.request_id]
        return req

    def _free_slot(self) -> Optional[int]:
        if len(self._active_idx) == len(self.slots):
            return None
        return self.slots.index(None)      # C-level scan: lowest free slot

    def _active(self) -> List[TrackedRequest]:
        return list(self._active_reqs)

    def _wake_walk(self) -> None:
        """Dynamic CCPG: the iteration's cluster walk pays the FULL wake
        latency as a real ClusterWake timeline event (visible in the
        Chrome trace; raises serving p99 — see EXPERIMENTS.md)."""
        if not (self.engine.ccpg and self.engine.dynamic_ccpg):
            return
        dt, cyc = self.sim.wake_seconds(self.alloc)
        if dt:
            self.timeline.wake(dt, power_W=self._busy_power, cycles=cyc)

    def _on_kv_spill(self, nbytes: int) -> None:
        """Allocator spill callback: the cold block rides the photonic
        link to the DRAM hub — a real C2CTransfer on the timeline (DMA
        concurrent with compute) plus DRAM access energy."""
        self.timeline.c2c(nbytes, phase="kv_spill",
                          dur_s=self.sim.kv_transfer_seconds(nbytes))

    def _on_kv_cow(self, nbytes: int) -> None:
        """Allocator copy-on-write callback: the matching head of a
        divergence block is copied pad-to-pad over the C2C fabric — a
        non-advancing DMA like kv_spill (phase "kv_cow"; no new
        TimelineIR event KIND, per the back-compat contract)."""
        self.timeline.c2c(nbytes, phase="kv_cow",
                          dur_s=self.sim.kv_transfer_seconds(nbytes))

    # -- prefix-sharing helpers (all no-ops unless prefix_sharing) ------
    def _prefix_hashes(self, req: TrackedRequest) -> Optional[List[int]]:
        if not self._prefix_on or req.prompt_tokens is None:
            return None
        h = self._chain_cache.get(req.request_id)
        if h is None:
            h = self._chain_cache[req.request_id] = \
                self.kv.chunk_hashes(req.prompt_tokens)
        return h

    def _probe_shared(self, req: TrackedRequest) -> int:
        """Blocks ``req`` would adopt if admitted now (admission credit),
        memoized on the allocator's index version."""
        hashes = self._prefix_hashes(req)
        if hashes is None:
            return 0
        ver = self.kv.index_version
        hit = self._probe_cache.get(req.request_id)
        if hit is not None and hit[0] == ver:
            return hit[1]
        n = self.kv.probe_prefix(req.prompt_tokens, hashes)
        self._probe_cache[req.request_id] = (ver, n)
        return n

    def _admit_arrivals(self, pending: Deque[TrackedRequest]) -> None:
        now = self.timeline.now
        while pending and pending[0].arrival <= now:
            req = pending.popleft()
            if self.kv is not None and not self.kv.feasible(
                    req.prompt_len + max(req.max_new, 1)):
                # could never fit, even with the whole cache to itself
                self.rejected += 1
                self._kv_rejected_infeasible += 1
                self.events.append((now, EventKind.REJECT,
                                    req.request_id))
                continue
            if len(self.queue) >= self.engine.queue_limit:
                self.rejected += 1
                self.events.append((now, EventKind.REJECT,
                                    req.request_id))
                continue
            self.queue.append(req)

    def _kv_can_admit(self) -> bool:
        """Admission checks free KV *blocks*, not just free slots: the
        queue head needs blocks for its (possibly recomputed) context
        plus its first new token, with watermark headroom for the
        residents' growth — except when nothing is resident, where the
        full cache is available by definition."""
        if self.kv is None or not self.queue:
            return True
        head = self.queue[0]
        need = head.prompt_len + head.generated + 1
        # (only reached with no chunked prefill in flight: step() keeps
        # the prefill pipeline for the partial and skips this check)
        reserve = self.kv.cfg.watermark_blocks if self._active_idx else 0
        return self.kv.can_admit(need, reserve=reserve,
                                 shared_blocks=self._probe_shared(head))

    def _prefill_eta_s(self) -> float:
        """Prefill latency the queue HEAD would pay if admitted now —
        the horizon the TTFT at-risk test compares against."""
        head = self.queue[0]
        dt, _ = self.sim.prefill_seconds(
            self.cfg, self.alloc, head.prompt_len + head.generated,
            ccpg=self._residue_ccpg)
        if self.engine.ccpg and self.engine.dynamic_ccpg:
            dt += self.sim.wake_seconds(self.alloc)[0]
        return dt

    def _deadline_at_risk(self) -> bool:
        head = self.queue[0] if self.queue else None
        if head is None or head.deadline_ttft is None:
            # deadline-free heads short-circuit BEFORE pricing the
            # prefill: `deadline_at_risk` would discard it anyway, and
            # this check runs on every admission-eligible iteration
            return False
        return deadline_at_risk(head, self.clock, self._prefill_eta_s())

    # ------------------------------------------------------------------
    def _prefill(self, slot: int) -> None:
        if self._partial is None:
            req = self.queue.popleft()
            # recompute-on-resume: a preempted request re-prefills its
            # prompt PLUS everything it had already generated
            target = req.prompt_len + req.generated
            # prefix sharing: adopt indexed blocks (+ COW fork) FIRST —
            # the adopted tokens need no prefill compute, only the
            # unshared suffix is priced below.  shared == 0 whenever
            # sharing is off, keeping every expression byte-identical.
            shared = 0
            hashes = self._prefix_hashes(req)
            if hashes is not None:
                shared = self.kv.adopt_prefix(
                    req.request_id, req.prompt_tokens, hashes)
            if req.generated:
                self._recomputed_tokens += target - shared
            chunk_cap = self.engine.chunked_prefill_tokens
            if chunk_cap and target - shared > chunk_cap:
                self._partial = [req, shared, target, slot]
            else:
                # monolithic path — the default-config fast path; with
                # paging off its float sequence is byte-identical to the
                # pre-paging engine (timeline golden).  A shared prefix
                # turns it into one suffix "chunk" at context `shared`
                # (prefill_chunk_cycles(n, 0) == prefill_cycles(n), so
                # the two calls agree exactly at shared == 0).
                if shared:
                    dt, c2c = self.sim.prefill_chunk_seconds(
                        self.cfg, self.alloc, target - shared, shared,
                        ccpg=self._residue_ccpg)
                else:
                    dt, c2c = self.sim.prefill_seconds(
                        self.cfg, self.alloc, target,
                        ccpg=self._residue_ccpg)
                self._wake_walk()
                t0 = self.timeline.now
                self.timeline.compute(
                    dt, kind="prefill", power_W=self._busy_power,
                    batch=len(self._active_idx) + 1,
                    name=f"prefill:r{req.request_id}")
                if c2c:
                    # burst rides under the compute wave: anchor at start
                    self.timeline.c2c(c2c, phase="prefill", t0=t0,
                                      dur_s=c2c / self.sim.link.bandwidth_Bps)
                self._tokens_prefilled += target - shared
                self._finish_prefill(req, slot)
                return
        # chunked continuation: one chunk per engine iteration
        req, done, target, slot = self._partial
        chunk = min(self.engine.chunked_prefill_tokens, target - done)
        dt, c2c = self.sim.prefill_chunk_seconds(
            self.cfg, self.alloc, chunk, done, ccpg=self._residue_ccpg)
        self._wake_walk()
        t0 = self.timeline.now
        self.timeline.compute(dt, kind="prefill", power_W=self._busy_power,
                              batch=len(self._active_idx) + 1,
                              name=f"prefill:r{req.request_id}@{done}")
        if c2c:
            self.timeline.c2c(c2c, phase="prefill", t0=t0,
                              dur_s=c2c / self.sim.link.bandwidth_Bps)
        self._tokens_prefilled += chunk
        done += chunk
        if self.kv is not None:
            self._kv_ensure(req, done)
        self.decode_credit = 0
        if done >= target:
            self._partial = None
            self._finish_prefill(req, slot)
        else:
            self._partial = [req, done, target, slot]
            self.events.append((self.clock, EventKind.PREFILL,
                                req.request_id))

    def _finish_prefill(self, req: TrackedRequest, slot: int) -> None:
        """Post-prefill bookkeeping, shared by the monolithic and chunked
        paths.  A fresh prefill emits the request's first output token
        (unless max_new == 0, prefill-only scoring); a resumed one ends
        its recompute by producing the next token."""
        if req.first_token_at is None:
            req.first_token_at = self.clock
            req.generated = min(1, req.max_new)
            new_tokens = req.generated
        else:
            req.generated += 1
            new_tokens = 1
        req.context = req.prompt_len + req.generated
        if self.kv is not None:
            self._kv_ensure(req, max(req.context, 1))
            hashes = self._prefix_hashes(req)
            if hashes is not None:
                # the prompt's blocks now hold final KV — publish them
                self.kv.register_prefix(req.request_id,
                                        req.prompt_tokens, hashes)
        if new_tokens:
            self.timeline.token(new_tokens, request_id=req.request_id)
        self.events.append((self.clock, EventKind.PREFILL, req.request_id))
        if req.generated >= req.max_new:
            req.finished_at = self.clock
            self.events.append((self.clock, EventKind.FINISH,
                                req.request_id))
            handed = (self.on_finish is not None
                      and bool(self.on_finish(req)))
            if self.kv is not None and not handed:
                self.kv.free(req.request_id)
        else:
            req.admit_seq = self._admit_counter
            self._admit_counter += 1
            self._slot_occupy(slot, req)
            if self.kv is None:
                heappush(self._finish_heap,
                         (self._round_no + req.max_new - req.generated,
                          slot))
        self.decode_credit = 0

    # -- paged-KV round bookkeeping ------------------------------------
    def _kv_ensure(self, req: TrackedRequest, n_tokens: int) -> None:
        """Grow the request's block table, preempting (other) residents
        when both tiers are exhausted."""
        while True:
            try:
                self.kv.ensure(req.request_id, n_tokens)
                return
            except OutOfBlocks:
                if not self._preempt_one(exclude=req.request_id):
                    raise RuntimeError(
                        "paged KV cache cannot hold the running set; "
                        "raise n_blocks/dram_blocks or lower max_batch")

    def _preempt_one(self, exclude: int = -1) -> bool:
        """Evict the most-recently-admitted resident (vLLM recompute
        policy): free its blocks, return it to the queue FRONT; its KV is
        recomputed at re-prefill.  The victim is argmax over the SoA
        admit-seq column (unoccupied slots carry -1), and `_slot_of`
        resolves the excluded request in O(1) — no object-identity scan.
        """
        seqs = self._seq_col
        excl_slot = self._slot_of.get(exclude, -1)
        if excl_slot >= 0:
            seqs = seqs.copy()
            seqs[excl_slot] = -1
        idx = int(seqs.argmax())
        if seqs[idx] >= 0:
            victim = self._slot_release(idx)
            self.kv.free(victim.request_id)
            self._preemptions += 1
            self.queue.appendleft(victim)
            self.events.append((self.clock, EventKind.PREEMPT,
                                victim.request_id))
            return True
        # last resort: abort an in-flight chunked prefill.  The partial
        # holds KV blocks but lives outside self.slots, so without this
        # a lone growing resident could exhaust the cache with no victim
        # available and crash a feasible run; its chunks are recomputed
        # when it is re-admitted.
        if self._partial is not None \
                and self._partial[0].request_id != exclude:
            req, done = self._partial[0], self._partial[1]
            self._partial = None
            if req.request_id in self.kv.tables:
                self.kv.free(req.request_id)
            # the discarded chunks are prefill work that will be re-done
            # on re-admission (the resume path only counts requests that
            # had already generated tokens)
            self._recomputed_tokens += done
            self._preemptions += 1
            self.queue.appendleft(req)
            self.events.append((self.clock, EventKind.PREEMPT,
                                req.request_id))
            return True
        return False

    def _kv_prepare_round(self) -> None:
        """Before a decode round: watermark-based preemption, then grow
        every resident's block table by the token this round appends."""
        cfg = self.kv.cfg
        while True:
            active = self._active()
            if not active:
                return
            needed = sum(
                cfg.blocks_for(r.context + 1)
                - len(self.kv.tables[r.request_id].blocks)
                for r in active)
            if needed == 0:
                return
            if (self.kv.free_total()
                    >= max(needed, cfg.watermark_blocks)
                    or len(active) <= 1):
                break
            self._preempt_one()
        # batched fast path: the whole round's growth fits the scratch
        # free list — one allocator pass, identical pops (same block ids
        # to the same tables) to the sequential ensure() loop below
        if self.kv.grow_round([(r.request_id, r.context + 1)
                               for r in self._active()]):
            return
        for r in self._active():
            self._kv_ensure(r, r.context + 1)

    def _decode_round(self) -> None:
        if self.kv is not None:
            self._kv_prepare_round()
        if not self._active_idx:  # everything was preempted back to the queue
            return
        b = len(self._active_idx)
        # the cycle model only needs (batch, sum of contexts) — both are
        # running SoA aggregates.  At overlap == 0 the memoized affine
        # decomposition is inlined as plain arithmetic (bit-identical to
        # the decode_iteration_seconds chain, which remains the fallback
        # for overlap > 0 / memoization off / non-affine subclasses).
        aff = self._affine_by_b.get(b) if self._use_affine else None
        cm = self._cm
        if aff is None or aff[5] != cm._cal_ver:
            aff = cm.decode_affine(self.cfg, self.alloc, b) \
                if self._use_affine else None
            if aff is not None:
                self._affine_by_b[b] = aff
        if aff is not None:
            base, n_attn, c2c, cpp, alpha, _ = aff
            cyc = base + n_attn * int(cpp * self._ctx_sum)
            cyc = int(cyc * alpha)
            dt = (cyc + self._residue_cyc) / self._freq_hz
        else:
            # real per-request contexts for the fallback (a CycleModel
            # subclass may legitimately iterate them): every resident
            # gains one token per round, so ctx0 + round counter is the
            # exact current value — no per-round bookkeeping needed
            rn = self._round_no
            contexts = [c + rn for c in self._active_ctx0]
            dt, c2c = self.sim.decode_iteration_seconds(
                self.cfg, self.alloc, contexts,
                ccpg=self._residue_ccpg, overlap=self.engine.overlap)
        if self._dyn_wake:
            self._wake_walk()
        tl = self.timeline
        name = self._decode_names.get(b)
        if name is None:
            name = self._decode_names[b] = f"decode:b{b}"
        t0 = tl.now
        tl.compute(dt, kind="decode", power_W=self._busy_power,
                   batch=b, name=name)
        if c2c:
            tl.c2c(c2c, phase="decode", t0=t0,
                   dur_s=c2c / self._bandwidth_Bps)
        if self.kv is not None:
            # DRAM-resident context is re-read over the photonic link
            # every iteration: an EXPOSED remote-memory stall (advancing
            # C2C) — the cost Sangam/Photonic-Fabric price for the tier
            fetch = sum(self.kv.dram_tokens(self.slots[i].request_id)
                        for i in self._active_idx) \
                * self.kv.cfg.bytes_per_token
            if fetch:
                # the chiplets keep burning busy power while stalled
                self.timeline.c2c(fetch, phase="kv_fetch",
                                  dur_s=self.sim.kv_transfer_seconds(fetch),
                                  advance=True, power_W=self._busy_power)
                self._kv_fetch_bytes += fetch
        self.decode_credit += 1
        clock = tl.now
        events = self.events
        events.append((clock, EventKind.DECODE, -1))
        # batched timeline append: one TokenEmit per resident, C-level
        # column extends (stream-identical to per-request token() calls)
        tl.token_each(self._active_rids)
        self._ctx_sum += b                  # every resident grew by one
        rn = self._round_no = self._round_no + 1
        kv = self.kv
        if kv is None:
            # deferred finish: pop exactly the residents whose countdown
            # elapsed this round (slot-ordered ties match the old loop)
            # and sync their objects to the exact final values
            heap = self._finish_heap
            while heap and heap[0][0] <= rn:
                i = heappop(heap)[1]
                req = self.slots[i]
                req.generated = req.max_new
                req.context = req.prompt_len + req.max_new
                req.finished_at = clock
                events.append((clock, EventKind.FINISH, req.request_id))
                self._slot_release(i)
                if self.on_finish is not None:
                    self.on_finish(req)  # no KV to hand on this path
            return
        # paged path: preemption can interrupt any resident mid-decode,
        # so per-round object state must stay exact
        act_list = list(self._active_idx)   # copies: releases mutate them
        residents = list(self._active_reqs)
        for i, req in zip(act_list, residents):
            gen = req.generated = req.generated + 1
            req.context += 1
            if gen >= req.max_new:
                req.finished_at = clock
                events.append((clock, EventKind.FINISH, req.request_id))
                self._slot_release(i)
                if self.on_finish is not None and self.on_finish(req):
                    continue        # KV ownership handed to the fleet
                kv.free(req.request_id)

    def step(self, pending: Deque[TrackedRequest]) -> EventKind:
        """One engine iteration; returns what was scheduled."""
        now = self.timeline.now
        if pending and pending[0].arrival <= now:
            self._admit_arrivals(pending)
        self.queue_depth.append((now, len(self.queue)))

        if self._partial is not None:
            # an in-flight chunked prefill owns the prefill pipeline (and
            # its reserved slot); new admissions wait behind it.  Its
            # chunks obey the SAME deficit gating as fresh prefills —
            # that is what stops a long prompt monopolizing iterations
            slot = self._partial[3]
            want_prefill = True
            must_prefill = False
        else:
            slot = self._free_slot()
            want_prefill = (bool(self.queue) and slot is not None
                            and (self.kv is None or self._kv_can_admit()))
            must_prefill = (want_prefill and self._any_deadline
                            and self._deadline_at_risk())
        may_prefill = want_prefill and (
            self.decode_credit >= self.engine.decode_quantum
            or not self._active_idx)
        if must_prefill or may_prefill:
            self._prefill(slot)
            return EventKind.PREFILL
        if self._active_idx:
            self._decode_round()
            return EventKind.DECODE
        if pending:
            # idle gap until the next arrival: CCPG lets every cluster
            # sleep (scratchpad retention only); without it the chiplets
            # burn active power waiting
            gap = max(0.0, pending[0].arrival - self.clock)
            self.timeline.sleep(gap, power_W=self._idle_power)
            self.events.append((self.clock, EventKind.IDLE, -1))
            return EventKind.IDLE
        return EventKind.IDLE

    # ------------------------------------------------------------------
    def import_request(self, req: TrackedRequest, *, nbytes: int = 0,
                       transfer_s: float = 0.0, phase: str = "kv_handoff",
                       retransmit_bytes: int = 0,
                       retransmit_s: float = 0.0) -> bool:
        """Admit a request whose prefill (and first token) ran on
        ANOTHER engine, arriving with resident KV over the fabric — the
        decode-side half of the fleet's prefill->decode handoff.

        Occupies a slot directly (no prefill compute here); with paging
        on, a fresh LOCAL block table covering ``req.context`` tokens is
        allocated (`BlockAllocator.import_table` — block ids never
        travel between allocators, only the footprint does).  The KV
        payload lands on this node's timeline as a non-advancing
        ``C2CTransfer`` (phase ``"kv_handoff"``): the fleet already
        folded the transfer latency into the request's arrival time, so
        the event prices bytes/energy, not time.  Returns False with
        state untouched when no slot is free or the blocks don't fit —
        the caller re-queues (never drops).

        Fault injection (launch/config.FaultConfig) rides the same
        import: ``phase="kv_recompute"`` marks a handoff whose KV was
        recomputed after a node crash, and ``retransmit_bytes`` prices
        the FEC-overflow overhead of a degraded link window as a second
        ``C2CTransfer(phase="retransmit", source="fault")`` on the same
        link — both default off, keeping the zero-fault event stream
        byte-identical."""
        slot = self._free_slot()
        if slot is None:
            return False
        if self.kv is not None:
            reserve = (self.kv.cfg.watermark_blocks
                       if self._active_idx else 0)
            if not self.kv.can_admit(req.context + 1, reserve=reserve):
                return False
            try:
                self.kv.import_table(req.request_id, req.context)
            except OutOfBlocks:
                # fragmented growth raced the headroom check: roll back
                if req.request_id in self.kv.tables:
                    self.kv.free(req.request_id)
                return False
        if nbytes:
            self.timeline.c2c(nbytes, phase=phase, source="fleet",
                              dur_s=transfer_s)
        if retransmit_bytes:
            self.timeline.c2c(retransmit_bytes, phase="retransmit",
                              source="fault", dur_s=retransmit_s)
        req.admit_seq = self._admit_counter
        self._admit_counter += 1
        self._slot_occupy(slot, req)
        if self.kv is None:
            heappush(self._finish_heap,
                     (self._round_no + req.max_new - req.generated, slot))
        self.events.append((self.clock, EventKind.HANDOFF,
                            req.request_id))
        return True

    def drop_inflight(self) -> List[TrackedRequest]:
        """Crash semantics for the fleet's fault layer: every in-flight
        request — queued, resident mid-decode, or mid-chunked-prefill —
        is dropped and returned; their KV block tables (lost with the
        node) are freed.  The timeline is deliberately untouched: a dead
        node emits nothing, and the recovery costs (recompute prefills,
        re-routed handoffs) land on the survivors' timelines."""
        dropped: List[TrackedRequest] = list(self.queue)
        self.queue.clear()
        for i in list(self._active_idx):
            req = self._slot_release(i)
            dropped.append(req)
            if self.kv is not None and req.request_id in self.kv.tables:
                self.kv.free(req.request_id)
        if self._partial is not None:
            req = self._partial[0]
            dropped.append(req)
            if self.kv is not None and req.request_id in self.kv.tables:
                self.kv.free(req.request_id)
            self._partial = None
        self._finish_heap.clear()
        self.decode_credit = 0
        return dropped

    # ------------------------------------------------------------------
    def _prepare_run(self, trace: Sequence[TrackedRequest]
                     ) -> Deque[TrackedRequest]:
        """Reset the engine and the trace's mutable per-run state, verify
        arrival order, and hand back the pending deque (the fleet engine
        mirrors these steps for its nodes)."""
        self.reset()
        for r in trace:
            # re-running a trace must be idempotent: the resume/recompute
            # paths branch on this mutable state, so leftovers from an
            # earlier run would masquerade as preempted residents
            r.generated = 0
            r.context = 0
            r.first_token_at = None
            r.finished_at = None
            r.admit_seq = -1
        # poisson_trace / replay_trace hand back arrival-sorted traces;
        # verify monotonicity in one O(n) pass and only re-sort (stable,
        # same order the old per-run `sorted(trace)` produced) when a
        # hand-built trace violates it
        arr = list(trace)
        prev = -math.inf
        for r in arr:
            if r.arrival < prev:
                arr.sort()
                break
            prev = r.arrival
        self._any_deadline = any(r.deadline_ttft is not None for r in arr)
        return deque(arr)

    def run(self, trace: Sequence[TrackedRequest]) -> ServingReport:
        pending = self._prepare_run(trace)
        it = 0
        while (pending or self.queue or self._active_idx
               or self._partial is not None):
            it += 1
            if it > self.engine.max_iters:
                raise RuntimeError("serving engine exceeded max_iters")
            self.step(pending)
        return self._report(list(trace))

    # ------------------------------------------------------------------
    def _report(self, requests: List[TrackedRequest]) -> ServingReport:
        """Everything here is DERIVED from the timeline integrator: wall
        clock, busy/idle split, span-integrated chip energy, C2C bytes,
        token counts, batch occupancy."""
        tl = self.timeline
        done = [r for r in requests if r.finished_at is not None]
        # NaN, not 0.0, when nothing finished: an all-rejected run must
        # not look like a zero-latency one in the benchmark rows
        nothing = np.array([np.nan])
        lat = np.array([r.latency for r in done]) if done else nothing
        ttft = np.array([r.ttft for r in done]) if done else nothing
        wall = max(tl.now, 1e-12)
        # C2C energy: average power at the delivered byte rate over the
        # whole wall clock (bursty traffic, duty-cycled laser bias)
        c2c_power = c2c_average_power(tl.c2c_bytes / wall, self.sim.link)
        energy = tl.energy_J + c2c_power * wall
        dram_bytes = ((self.kv.spilled_bytes if self.kv is not None else 0)
                      + self._kv_fetch_bytes)
        if dram_bytes:
            # KV spilled to / re-read from the DRAM hub pays the off-chip
            # access energy on top of the link transport charged above
            # (the hub's static power rides in via CCPGModel's
            # include_dram_hub path); guarded so the paging-off default
            # keeps its float sequence byte-identical
            energy += dram_bytes * 8 * E_DRAM_ACCESS
        return ServingReport(
            n_requests=len(requests),
            finished=len(done),
            rejected=self.rejected,
            wall_s=wall,
            busy_s=tl.busy_s,
            idle_s=tl.idle_s,
            tokens_generated=tl.tokens,
            tokens_prefilled=self._tokens_prefilled,
            tokens_per_s=tl.tokens / wall,
            energy_J=energy,
            tokens_per_J=tl.tokens / max(energy, 1e-12),
            p50_latency_s=float(np.percentile(lat, 50)),
            p99_latency_s=float(np.percentile(lat, 99)),
            p50_ttft_s=float(np.percentile(ttft, 50)),
            p99_ttft_s=float(np.percentile(ttft, 99)),
            mean_batch_occupancy=(tl.occupancy_s
                                  / max(tl.busy_s, 1e-12)),
            max_queue_depth=max((d for _, d in self.queue_depth),
                                default=0),
            queue_depth=self.queue_depth,
            c2c_bytes_total=tl.c2c_bytes,
            ccpg=self.engine.ccpg,
        )


def serve_trace(cfg, trace: Sequence[TrackedRequest], *,
                max_batch: int = 8, ccpg: bool = False,
                sim: Optional[PicnicSimulator] = None,
                **engine_kw) -> ServingReport:
    """One-call convenience wrapper: run ``trace`` through a fresh engine."""
    eng = ContinuousBatchingEngine(
        cfg, sim=sim,
        engine=ServingConfig(max_batch=max_batch, ccpg=ccpg, **engine_kw))
    return eng.run(trace)
