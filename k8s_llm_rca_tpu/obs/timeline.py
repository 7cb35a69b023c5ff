"""Engine tick timeline: per-tick gauge samples in a bounded ring.

One ``TickSample`` per engine tick while a tracer is active (EngineBase
``step`` records it after the tick body): scheduler occupancy (running /
queued sequences), paged-pool pressure (free vs evictable pages), and the
engine's cumulative per-engine token counters (prefill vs decode tokens,
prefix hits, preemptions, admission rejections).  Cumulative values —
rather than per-tick deltas — keep samples cheap to record and are what
Chrome/Perfetto counter tracks want; consumers diff endpoints
(``flight_summary``) or plot the track directly.

The ring is bounded (``capacity``) so an always-on recorder in a long
soak keeps the newest window; ``total`` counts every tick ever recorded
(exact, like Metrics counts), so dropping old samples never skews rates.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional


@dataclass(frozen=True)
class TickSample:
    """Gauges at the end of one engine tick.  ``free_pages`` /
    ``evictable_pages``: the allocator's free count and the prefix
    cache's refcount-0 residency."""

    tick: int
    ts: float
    running: int
    queued: int
    free_pages: Optional[int] = None
    evictable_pages: Optional[int] = None
    prefill_tokens: float = 0.0
    decode_tokens: float = 0.0
    prefix_hit_tokens: float = 0.0
    preemptions: float = 0.0
    admission_rejections: float = 0.0
    # host<->device traffic (cumulative, docs/performance.md): full-array
    # uploads of cur_tokens/lengths/block_tables, coalesced device->host
    # fetch groups, and device dispatches (prefill/decode/scan/verify)
    h2d_uploads: float = 0.0
    d2h_syncs: float = 0.0
    dispatches: float = 0.0
    # chunked-prefill dispatches this tick (EngineConfig
    # .prefill_chunk_budget): how many in-progress long prompts advanced
    # one <=budget chunk — nonzero ticks are the spread-out prefill the
    # budget bought instead of a monolithic stall
    prefill_chunks: float = 0.0
    # cluster attribution (cluster/): which replica's engine recorded
    # this sample (0 outside a cluster — also the Chrome counter-track
    # tid, so per-replica tracks separate in Perfetto), plus the
    # router's view of that replica at its last dispatch: live runs
    # queued on it and the fraction of batch slots occupied
    engine_id: int = 0
    cluster_queue_depth: float = 0.0
    cluster_occupancy: float = 0.0
    # overload survival (docs/serving.md "overload & priorities"):
    # cumulative KV pages spilled to host / restored from host and
    # deadline-expired sequences reaped by the engine tick, plus the
    # instantaneous pending-queue depth per priority class (CRITICAL /
    # NORMAL / BATCH buckets of GenOptions.priority)
    spilled_pages: float = 0.0
    restored_pages: float = 0.0
    deadline_expirations: float = 0.0
    queued_critical: int = 0
    queued_normal: int = 0
    queued_batch: int = 0
    # tiered prefix cache (docs/performance.md "tiered prefix cache"):
    # cumulative match hits by tier in PAGES (L0 = resident HBM chain,
    # L1 = host-RAM PrefixStore, L2 = disk), pages demoted store-ward by
    # eviction/flush, pages promoted back by h2d restore, and the bytes
    # those promotions scattered — the counters that prove a warm-start
    # served pages instead of re-prefilling
    prefix_hits_l0: float = 0.0
    prefix_hits_l1: float = 0.0
    prefix_hits_l2: float = 0.0
    prefix_demotions: float = 0.0
    prefix_promoted_pages: float = 0.0
    prefix_bytes_restored: float = 0.0
    # cache fabric (docs/cluster.md "Cache fabric"): cumulative store
    # ops that silently degraded to cold misses (a dead / partitioned /
    # faulted RemoteStore — the fabric's only failure mode) and pages
    # demoted autonomously because free HBM pages dipped below
    # EngineConfig.prefix_hbm_watermark at a tick boundary
    prefix_store_misses_remote: float = 0.0
    prefix_watermark_demotions: float = 0.0
    # pipelined sweep (serve/backend.py): cumulative pumps that found
    # live handles but nothing decodable — the WAITED ticks the sweep
    # scheduler exists to eliminate (docs/performance.md "Pipelined
    # sweep")
    idle_ticks: float = 0.0


class TickTimeline:
    """Bounded ring of TickSamples; ``total`` is the exact tick count."""

    def __init__(self, capacity: int = 4096):
        self.capacity = capacity
        self.total = 0
        self._ring: List[TickSample] = []
        self._i = 0

    def record(self, sample: TickSample) -> None:
        if len(self._ring) < self.capacity:
            self._ring.append(sample)
        else:
            self._ring[self._i] = sample
            self._i = (self._i + 1) % self.capacity
        self.total += 1

    def samples(self) -> List[TickSample]:
        """Retained samples in tick order (oldest first)."""
        return self._ring[self._i:] + self._ring[:self._i]

    def __len__(self) -> int:
        return len(self._ring)
