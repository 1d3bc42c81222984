"""Launch layer: meshes, train/serve steps, dry-run costing, serving.

Submodules are imported lazily by callers (several pull in JAX at import
time); the analytic serving stack (``scheduler``, ``serving_engine``,
``fleet_engine``) stays JAX-free so traffic simulations run instantly on
any host.

This package is also the PUBLIC serving API (ISSUE 9): one documented
facade over the three execution tiers, so examples and benchmarks stop
importing module internals —

  * configs   — :class:`ServingConfig` (per-node engine knobs),
    :class:`FleetConfig` (pool shape / router / handoff / autoscaling)
    and :class:`FaultConfig` (deterministic fault schedules: LinkFault
    degradation windows, NodeFault crash/recover, WakeFault CCPG wake
    failures), all keyword-only and versioned with
    ``to_dict()``/``from_dict()`` round-trip and unknown-key rejection
    (`repro.launch.config`);
  * traces    — :class:`Trace` with ``Trace.poisson(...)`` /
    ``Trace.replay(rows)`` classmethods (one arrival/deadline/prefix
    spec; the legacy ``poisson_trace``/``replay_trace`` functions
    delegate to them);
  * reports   — :class:`ServingReport` (per node, with optional
    ``node_id``/``pool`` attribution) and :class:`FleetReport`
    (cluster aggregate);
  * entry points —
      serve(cfg, trace, ...)   one engine, one trace  -> ServingReport
      sweep(cells)             one engine per cell    -> [SweepResult]
      fleet(cfg, trace, ...)   multi-node disaggregated cluster
                                                      -> FleetReport

All three construct from the same :class:`ServingConfig` schema.  The
facade functions import their engines lazily, keeping ``import
repro.launch`` cheap and JAX-free.
"""
from __future__ import annotations

import copy
from typing import List, Optional, Sequence

from repro.launch.config import (FaultConfig, FleetConfig, LinkFault,
                                 NodeFault, ServingConfig, WakeFault)
from repro.launch.serving_engine import (ServingReport, SweepCell,
                                         SweepResult, Trace, TrackedRequest,
                                         poisson_trace, replay_trace)

__all__ = [
    "FaultConfig", "FleetConfig", "LinkFault", "NodeFault",
    "ServingConfig", "ServingReport", "SweepCell", "SweepResult", "Trace",
    "TrackedRequest", "WakeFault", "poisson_trace", "replay_trace",
    "serve", "sweep", "fleet",
]


def serve(cfg, trace: Sequence[TrackedRequest], *,
          config: Optional[ServingConfig] = None, sim=None
          ) -> ServingReport:
    """Run ``trace`` through one fresh :class:`ContinuousBatchingEngine`
    built from ``config`` (default :class:`ServingConfig`)."""
    from repro.launch.serving_engine import ContinuousBatchingEngine
    eng = ContinuousBatchingEngine(
        cfg, sim=sim,
        engine=config if config is not None else ServingConfig())
    return eng.run(trace)


def sweep(cells: Sequence[SweepCell]) -> List[SweepResult]:
    """Run each :class:`SweepCell` through its own fresh
    :class:`ContinuousBatchingEngine`, in cell order.  Cells with
    ``sim=None`` share one default simulator.  Each cell serves a copy
    of its trace's requests, so a trace shared by several cells is left
    as it was."""
    from repro.core.simulator import PicnicSimulator
    from repro.launch.serving_engine import ContinuousBatchingEngine
    default_sim = None
    results = []
    for cell in cells:
        sim = cell.sim
        if sim is None:
            if default_sim is None:
                default_sim = PicnicSimulator()
            sim = default_sim
        eng = ContinuousBatchingEngine(cell.cfg, sim=sim, engine=cell.engine)
        report = eng.run([copy.copy(r) for r in cell.trace])
        results.append(SweepResult(cell.key, report, eng.kv_stats))
    return results


def fleet(cfg, trace: Sequence[TrackedRequest], *,
          config: Optional[FleetConfig] = None, sim=None):
    """Run ``trace`` through a multi-node prefill/decode fleet built
    from ``config`` (default :class:`FleetConfig`); returns a
    `launch.fleet_engine.FleetReport`."""
    from repro.launch.fleet_engine import FleetEngine
    return FleetEngine(cfg, config, sim=sim).run(trace)
