"""The shell (paper §4.1): the static infrastructure that owns the device
grid, instantiates the reconfigurable regions, and provides global/per-region
resets.

On a real pod the shell slices the device grid into disjoint sub-meshes via
the ``Floorplanner`` (every device lands in exactly one region — remainder
devices are spread across the first regions rather than stranded); on this
CPU container regions may share the single CpuDevice (``allow_overlap=True``),
time-multiplexed — DESIGN.md §2.1(5).  The initial region count is the shell
build parameter (the TCL script input), but — unlike the paper's fixed
floorplan — the region list is *dynamic*: ``add_region``/``retire_region``
let the elastic pool (``core/pool.py``, DESIGN.md §6) grow and shrink the
pool at runtime while the shared reconfiguration plumbing survives.

The shell also owns that plumbing: the ``ReconfigEngine`` (LRU bitstream
cache + single ICAP port) and the ``BitstreamPrefetcher`` that generates
bitstreams off the dispatch path.  Both are shared handles — regions added
after construction reuse the same engine, cache, and prefetcher.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax

from repro.core.floorplan import Floorplanner
from repro.core.interrupts import InterruptController
from repro.core.prefetch import BitstreamPrefetcher
from repro.core.reconfig import ReconfigEngine
from repro.core.region import Region


class Shell:
    def __init__(self, n_regions: int = 2, devices=None,
                 allow_overlap: bool = True,
                 chunk_budget: Optional[int] = None,
                 simulate_partial_s: float = 0.0,
                 simulate_full_s: float = 0.0,
                 cache_capacity: Optional[int] = None,
                 prefetch: bool = True,
                 prefetch_max_queue: int = 64,
                 region_widths: Optional[Sequence[int]] = None,
                 pipeline: bool = True,
                 engine: Optional[str] = None,
                 tracer=None, metrics=None):
        self.devices = list(devices if devices is not None else jax.devices())
        self.interrupts = InterruptController()
        # flight recorder (obs/, DESIGN.md §11): one shared handle for the
        # whole shell — regions, the reconfig engine, the pool, and the
        # scheduler all emit into it.  None disables tracing at zero cost.
        self.tracer = tracer
        # live metrics registry (obs/registry.py, DESIGN.md §12): fanned
        # out exactly like the tracer — regions, the reconfig engine, and
        # the scheduler all update the same labeled instruments
        self.metrics = metrics
        self.engine = ReconfigEngine(simulate_partial_s=simulate_partial_s,
                                     simulate_full_s=simulate_full_s,
                                     cache_capacity=cache_capacity,
                                     device=self.devices[0])
        self.engine.tracer = tracer
        self.engine.metrics = metrics
        # the worker thread starts lazily with the scheduler's first hint
        self.prefetcher = BitstreamPrefetcher(
            self.engine, max_queue=prefetch_max_queue, auto_start=False)
        self.prefetch_enabled = prefetch
        self.chunk_budget = chunk_budget
        # region execution engine mode (DESIGN.md §8/§10): "sync" |
        # "pipelined" | "megakernel".  ``engine`` wins when given; the
        # ``pipeline`` boolean is the pre-megakernel selector, kept for
        # existing callers (False forces the synchronous reference path)
        self.engine_mode = engine or ("pipelined" if pipeline else "sync")
        self.pipeline = self.engine_mode == "pipelined"
        # megakernel regions need the "mega" program kind prefetched/compiled
        self.prefetcher.program = (
            "mega" if self.engine_mode == "megakernel" else "chunk")
        # test/bench hook inherited by regions added later (elastic grow)
        self.region_slowdown_s: float = 0.0
        self.floorplanner = Floorplanner(self.devices,
                                         allow_overlap=allow_overlap)
        self.regions: List[Region] = []     # active (non-retired) regions
        self._by_rid: Dict[int, Region] = {}  # every region ever created
        self._next_rid = 0
        self._shutdown = False

        for devs in self.floorplanner.initial_plan(n_regions,
                                                   widths=region_widths):
            self.add_region(devices=devs)

    # -- dynamic region pool (DESIGN.md §6.1) ---------------------------
    def add_region(self, devices=None, width: int = 1) -> Region:
        """Create and start a new region on a floorplanned device slice
        (``devices=None`` asks the floorplanner for a ``width``-wide one).
        Region ids are monotonic and never reused; use ``region(rid)`` for
        lookups — list position is not the id once the pool has resized."""
        if devices is None:
            devices = self.floorplanner.allocate(width)
        rid = self._next_rid
        self._next_rid += 1
        r = Region(rid, self.engine, self.interrupts,
                   devices=list(devices), geometry=(len(devices),),
                   chunk_budget=self.chunk_budget,
                   engine_mode=self.engine_mode,
                   tracer=self.tracer, metrics=self.metrics)
        r.slowdown_s = self.region_slowdown_s
        self.floorplanner.bind(rid, devices)
        self.regions.append(r)
        self._by_rid[rid] = r
        return r

    def retire_region(self, rid: int) -> Region:
        """Shut a region down and return its devices to the floorplanner.
        Callers must have drained it first (``RegionPool`` does the safe
        checkpoint-preempt drain); the object stays reachable via
        ``region(rid)`` so late interrupts can still resolve it."""
        r = self._by_rid[rid]
        r.retire()
        self.regions = [x for x in self.regions if x.rid != rid]
        self.floorplanner.release(rid)
        return r

    def region(self, rid: int) -> Region:
        """Region by id, including retired ones (interrupts may outlive the
        region that raised them)."""
        return self._by_rid[rid]

    # -- resets (paper: global reset + per-RR GPIO reset) -----------------
    def global_reset(self):
        """Stop everything, clear queues and banks (full-FPGA reset)."""
        for r in self.regions:
            r.shutdown()
        for r in self.regions:
            r.bank.reset()
            r.loaded = None
            r.executable = None
            r.current_task = None
            r.start()
        self.interrupts.drain()

    def region_reset(self, rid: int):
        """Per-region reset: preempt whatever is running there."""
        self.region(rid).request_preempt()

    def shutdown(self):
        """Stop every background thread this shell owns: the prefetcher and
        all region workers — including retired/failed regions, whose join
        is a no-op.  Idempotent: cluster teardown and test ``finally``
        blocks may both call it."""
        if self._shutdown:
            return
        self._shutdown = True
        self.prefetcher.stop()
        for r in self._by_rid.values():
            r.shutdown()

    def alive_regions(self) -> List[Region]:
        return [r for r in self.regions if r.alive]

    def placements(self) -> List[tuple]:
        """``(geometry, devices)`` of each alive region — what a bitstream
        is generated for (prefetch and prewarm targets; the prefetcher
        drops repeats, and a repeated prewarm is a cache hit)."""
        return [(r.geometry, r.devices) for r in self.alive_regions()]

    def reconfig_report(self) -> dict:
        """Engine + prefetcher + per-region reconfiguration statistics
        (``report_version`` stamped — see ``core/reporting.py``)."""
        from repro.core.reporting import stamp

        rep = self.engine.report()
        rep["prefetcher"] = {
            "enabled": self.prefetch_enabled,
            "submitted": self.prefetcher.stats.submitted,
            "processed": self.prefetcher.stats.processed,
            "dropped_full": self.prefetcher.stats.dropped_full,
        }
        rep["regions"] = {
            r.rid: {"reconfigs": r.stats.reconfigs,
                    "reconfig_s": r.stats.reconfig_s,
                    "chunks": r.stats.chunks,
                    "chunks_pipelined": r.stats.chunks_pipelined,
                    "chunks_discarded": r.stats.chunks_discarded,
                    "host_spills_avoided": r.stats.host_spills_avoided,
                    "megakernel_launches": r.stats.megakernel_launches,
                    "flag_poll_exits": r.stats.flag_poll_exits,
                    "pallas_mode": r.stats.pallas_mode}
            for r in self.regions
        }
        return stamp("shell_reconfig", rep)
