"""Fleet-scale cache fabric (docs/cluster.md "Cache fabric"): the
cross-host PrefixStore service (cluster/store.py), pressure-driven
watermark demotion, and store-backed instant recovery.

Three bars, mirroring the tiered-cache suite's (tests/test_prefix_tiers
.py) because the fabric IS the L1/L2 tier moved out of process:

- BYTE PARITY: an engine whose prefix store is a RemoteStore over a
  real server subprocess must generate exactly what the in-process
  PrefixStore engine and the store-less engine generate — the wire
  moves the same encode_page_record bytes the disk tier persists, so
  the promoted pages hold identical KV.
- SILENT DEGRADATION: every fabric failure (dead server, torn frame,
  drop/corrupt/delay/partition faults on SITE_STORE) is a counted cold
  miss (engine.prefix_store_misses_remote), never an engine error.
- BYTE-IDENTITY UNDER CHAOS: a seeded soak with the fabric attached
  and a StoreKiller SIGKILLing/respawning the store mid-sweep settles
  report_bytes byte-identical to the store-less run — fabric outcomes
  live on the fabric object, never in the report.

Everything runs on the 8-virtual-device CPU platform the conftest pins;
engines are single-device (test_prefix_tiers.py rationale).
"""

import os

import jax
import numpy as np
import pytest

from k8s_llm_rca_tpu.cluster import wire
from k8s_llm_rca_tpu.cluster.store import (
    RemoteStore, StoreFabric, StoreServer, build_store_fabric,
)
from k8s_llm_rca_tpu.config import TINY, EngineConfig
from k8s_llm_rca_tpu.engine import make_engine
from k8s_llm_rca_tpu.engine.prefix import PrefixStore
from k8s_llm_rca_tpu.faults import inject
from k8s_llm_rca_tpu.faults.plan import FaultPlan, VirtualClock
from k8s_llm_rca_tpu.models import llama
from k8s_llm_rca_tpu.utils import pages, wal
from k8s_llm_rca_tpu.utils.tokenizer import get_tokenizer

pytestmark = pytest.mark.storefab


@pytest.fixture(scope="module")
def setup():
    cfg = TINY.replace(max_seq_len=128)
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    tok = get_tokenizer(vocab_size=cfg.vocab_size)
    return cfg, params, tok


# the RCA-agent shape (test_prefix_tiers.py): one long shared preamble,
# short per-run suffixes — byte-level tokenizer, 4 full pages of
# preamble at page_size=16
_PRE = "shared incident preamble " * 3
PROMPTS = (_PRE + "kubelet crashloop on node-7",
           _PRE + "etcd leader lost quorum",
           _PRE + "pvc unbound on nfs chain")


def _ecfg(**over):
    base = dict(max_batch=2, max_seq_len=128, prefill_buckets=(64, 128),
                max_new_tokens=16, temperature=0.0,
                page_size=16, num_pages=40, prefix_cache=True,
                decode_chunk=4)
    base.update(over)
    return EngineConfig(**base)


def _drive(eng, sids):
    out = {}
    while eng.has_work:
        for r in eng.step():
            out[r.seq_id] = r
    eng.allocator.check()
    resident = eng.prefix_cache.n_resident if eng.prefix_cache else 0
    assert (eng.allocator.n_free + resident
            == eng.engine_cfg.num_pages - 1)
    return [out[s].token_ids for s in sids]


def _run(eng, tok, prompts=PROMPTS):
    return _drive(eng, [eng.submit(tok.encode(p)) for p in prompts])


def _rec(seed=0, n_pages=1):
    """A synthetic page record in the pool's (layers, n_pages, ...)
    layout — structurally valid for the codec, no engine needed."""
    rng = np.random.default_rng(seed)
    return {"n_pages": n_pages,
            "k": rng.standard_normal((2, n_pages, 4, 8)).astype(np.float32),
            "v": rng.standard_normal((2, n_pages, 4, 8)).astype(np.float32)}


def _same_rec(a, b):
    assert a is not None and b is not None
    assert a["n_pages"] == b["n_pages"]
    for f in ("k", "v"):
        np.testing.assert_array_equal(a[f], b[f])


# ---------------------------------------------------------------------------
# satellite: ONE frame header shared by WAL, disk tier and wire
# ---------------------------------------------------------------------------


class TestSharedHeader:
    def test_header_objects_are_identical(self):
        """wire.py re-exports wal.py's header/size-guard OBJECTS — not
        copies — so the disk and wire formats cannot drift."""
        assert wire.HEADER is wal.HEADER
        assert wire.HEADER_SIZE == wal.HEADER_SIZE == wal.HEADER.size
        assert wire.MAX_FRAME_SIZE == wal.MAX_RECORD_SIZE

    def test_disk_record_served_verbatim_over_wire(self, tmp_path):
        """A ``.page`` record written by the in-process L2 disk tier
        must be servable byte-for-byte by a store server pointed at
        the same directory: one format, three consumers (WAL framing,
        durable disk entries, wire frames)."""
        disk = str(tmp_path / "l2")
        local = PrefixStore(host_pages=0, disk_dir=disk, disk_pages=8)
        key = b"\x42" * 20
        rec = _rec(seed=3)
        local.put(key, rec)
        (entry,) = [f for f in os.listdir(disk) if f.endswith(".page")]
        assert entry == key.hex() + ".page"
        raw = open(os.path.join(disk, entry), "rb").read()
        # the durable bytes ARE exactly one legal WAL record
        (payload, end), = list(wal.iter_records(raw))
        assert end == len(raw) and payload
        assert pages.decode_page_record(raw) is not None
        # and a server re-indexing that directory serves them verbatim
        server = StoreServer(host_pages=0, disk_dir=disk, disk_pages=8,
                             transport="pipe")
        try:
            remote = RemoteStore(server=server)
            assert remote.contains(key)
            got, tier = remote.get(key)
            assert tier == 2                  # served from the disk tier
            _same_rec(got, rec)
        finally:
            server.close()


# ---------------------------------------------------------------------------
# store-op units over the wire codec
# ---------------------------------------------------------------------------


class TestStoreOps:
    @pytest.mark.parametrize("transport", ["pipe", "socket"])
    def test_put_get_probe_stats_roundtrip(self, transport):
        server = StoreServer(host_pages=8, transport=transport)
        try:
            remote = RemoteStore(server=server)
            key = b"\x07" * 20
            assert not remote.contains(key)
            assert remote.get(key) is None    # honest miss
            rec = _rec(seed=1)
            remote.put(key, rec)
            assert remote.contains(key)
            got, tier = remote.get(key)
            assert tier == 1
            _same_rec(got, rec)
            stats = remote.stats()
            assert stats["puts"] == 1 and stats["n_host"] == 1
            assert stats["hits_l1"] == 1 and stats["misses"] == 1
        finally:
            server.close()

    def test_addr_client_shares_server(self):
        """A second client dialing the socket address sees the first
        client's pages — the cross-host fleet shape."""
        server = StoreServer(host_pages=8, transport="socket")
        try:
            first = RemoteStore(server=server)
            key = b"\x11" * 20
            first.put(key, _rec(seed=2))
            second = RemoteStore(addr=server.addr)
            assert second.contains(key)
            _same_rec(second.get(key)[0], _rec(seed=2))
        finally:
            server.close()

    def test_host_lru_overflow_without_disk_drops(self):
        """The server's host tier is LRU-capped; with no disk tier the
        evicted page is simply gone — a later get is an honest miss."""
        server = StoreServer(host_pages=2, transport="pipe")
        try:
            remote = RemoteStore(server=server)
            keys = [bytes([i]) * 20 for i in range(3)]
            for i, k in enumerate(keys):
                remote.put(k, _rec(seed=i))
            assert remote.n_host == 2
            assert remote.get(keys[0]) is None        # LRU victim
            _same_rec(remote.get(keys[2])[0], _rec(seed=2))
        finally:
            server.close()

    def test_host_overflow_demotes_to_disk_and_survives_kill(self,
                                                             tmp_path):
        """Overflowed pages land in the durable disk tier and survive a
        SIGKILL + respawn of the server process."""
        disk = str(tmp_path / "store_l2")
        server = StoreServer(host_pages=1, disk_dir=disk, disk_pages=8,
                             transport="socket")
        try:
            remote = RemoteStore(server=server)
            keys = [bytes([0x20 + i]) * 20 for i in range(3)]
            for i, k in enumerate(keys):
                remote.put(k, _rec(seed=10 + i))
            assert remote.n_disk == 2
            server.kill()
            assert remote.get(keys[0]) is None        # dead: cold miss
            server.respawn()
            got, tier = remote.get(keys[0])
            assert tier == 2
            _same_rec(got, _rec(seed=10))
        finally:
            server.close()

    def test_dead_server_every_op_is_counted_cold_miss(self):
        """The failure contract: with the server SIGKILLed, put/get/
        probe/stats all degrade silently — no exception escapes, and
        every degraded op lands in the miss counter."""
        server = StoreServer(host_pages=8, transport="pipe")
        try:
            counted = []
            remote = RemoteStore(server=server,
                                 count=lambda n, v=1.0:
                                 counted.append((n, v)))
            server.kill()
            remote.put(b"\x01" * 20, _rec())
            assert remote.get(b"\x01" * 20) is None
            assert remote.contains(b"\x01" * 20) is False
            assert remote.stats() == {}
            misses = [c for c in counted
                      if c[0] == "engine.prefix_store_misses_remote"]
            assert len(misses) == 3           # put + get + probe
        finally:
            server.close()

    def test_corrupt_disk_entry_is_cold_miss(self, tmp_path):
        """A torn durable entry (host died mid-write) is dropped and
        unlinked at serve time — identical cold miss, never garbage."""
        disk = str(tmp_path / "l2")
        os.makedirs(disk)
        key = b"\x33" * 20
        frame = pages.encode_page_record(_rec(seed=4))
        with open(os.path.join(disk, key.hex() + ".page"), "wb") as f:
            f.write(frame[:len(frame) // 2])          # torn tail
        server = StoreServer(host_pages=0, disk_dir=disk, disk_pages=8,
                             transport="pipe")
        try:
            remote = RemoteStore(server=server)
            assert remote.get(key) is None
            assert not os.path.exists(
                os.path.join(disk, key.hex() + ".page"))
        finally:
            server.close()

    def test_oversized_record_is_local_drop(self):
        """A record past the shared size guard never reaches the wire:
        put degrades locally (encode raises, caught) with one counted
        miss."""
        server = StoreServer(host_pages=8, transport="pipe")
        try:
            counted = []
            remote = RemoteStore(server=server,
                                 count=lambda n, v=1.0:
                                 counted.append(n))
            big = wal.MAX_RECORD_SIZE // 4 + 1
            remote.put(b"\x44" * 20, {"n_pages": 1,
                                      "k": np.zeros((1, 1, 1, big),
                                                    np.float32),
                                      "v": np.zeros((1, 1, 1, 1),
                                                    np.float32)})
            assert "engine.prefix_store_misses_remote" in counted
            assert remote.stats()["puts"] == 0
        finally:
            server.close()


# ---------------------------------------------------------------------------
# the SITE_STORE fault seam (RemoteStore's OWN plan)
# ---------------------------------------------------------------------------


class TestStoreFaults:
    def _store(self, server, spec, seed=0, clock=None):
        plan = FaultPlan.from_spec(
            seed, {inject.SITE_STORE: spec},
            clock=clock or VirtualClock())
        counted = []
        remote = RemoteStore(server=server, plan=plan,
                             count=lambda n, v=1.0:
                             counted.append((n, v)))
        return remote, counted, plan

    def test_drop_is_counted_miss_then_heals_by_index(self):
        server = StoreServer(host_pages=8, transport="pipe")
        try:
            remote, counted, _ = self._store(
                server, {"indices": {0: "drop"}})
            key = b"\x05" * 20
            remote.put(key, _rec())           # op 0: dropped on the floor
            assert remote.stats()["puts"] == 0
            remote.put(key, _rec())           # op 1: clean
            assert remote.get(key) is not None
            assert counted[0][0] == "engine.prefix_store_misses_remote"
        finally:
            server.close()

    def test_corrupt_put_cannot_poison_corrupt_get_is_miss(self):
        """A corrupt fault on put flips a payload byte — the server's
        CRC check refuses the frame, so the store never holds garbage.
        On get the flip happens client-side after a clean serve — the
        record decoder rejects it: both directions are cold misses."""
        server = StoreServer(host_pages=8, transport="pipe")
        try:
            remote, counted, _ = self._store(
                server, {"indices": {0: "corrupt", 2: "corrupt"}})
            key = b"\x06" * 20
            remote.put(key, _rec(seed=5))     # op 0: corrupt -> refused
            assert remote.stats()["rejected"] == 1
            remote.put(key, _rec(seed=5))     # op 1: clean
            assert remote.get(key) is None    # op 2: corrupt -> miss
            _same_rec(remote.get(key)[0], _rec(seed=5))   # op 3: clean
            assert len([c for c in counted]) == 2
        finally:
            server.close()

    def test_delay_advances_the_plan_virtual_clock(self):
        clock = VirtualClock()
        server = StoreServer(host_pages=8, transport="pipe")
        try:
            remote, counted, _ = self._store(
                server, {"indices": {0: "delay"}, "delay_s": 0.25},
                clock=clock)
            remote.put(b"\x07" * 20, _rec())
            assert clock.time() == pytest.approx(0.25)
            assert not counted                # delayed, not degraded
            assert remote.get(b"\x07" * 20) is not None
        finally:
            server.close()

    def test_partition_is_sticky_until_heal(self):
        server = StoreServer(host_pages=8, transport="pipe")
        try:
            remote, counted, _ = self._store(
                server, {"indices": {1: "partition", 4: "heal"}})
            key = b"\x08" * 20
            remote.put(key, _rec(seed=6))     # op 0: clean
            assert remote.get(key) is None    # op 1: partition fires
            assert remote.get(key) is None    # op 2: still severed
            assert not remote.contains(key)   # op 3: still severed
            _same_rec(remote.get(key)[0], _rec(seed=6))   # op 4: healed
            assert len(counted) == 3
        finally:
            server.close()


# ---------------------------------------------------------------------------
# tentpole: greedy byte-parity — no-store vs local store vs REMOTE store
# ---------------------------------------------------------------------------


class TestRemoteParity:
    # engine-feature compositions the fabric must be invisible to
    # (test_prefix_tiers.py MATRIX, remote edition)
    MATRIX = {
        "base": dict(),
        "overlap": dict(decode_chunk=1, host_overlap=True),
        "chunked": dict(prefill_chunk_budget=32),
        "spill": dict(max_spilled_pages=64),
        "all": dict(decode_chunk=1, host_overlap=True,
                    prefill_chunk_budget=32, max_spilled_pages=64),
    }

    @pytest.mark.parametrize("feature", sorted(MATRIX))
    def test_remote_store_byte_parity(self, setup, feature):
        """Cold baseline (no cache), local-store engine and remote-store
        engine must agree byte-for-byte; after demoting every resident
        page through the WIRE, a re-run must still agree — and the
        promoted pages must be real L1 hits served by the subprocess."""
        cfg, params, tok = setup
        kw = self.MATRIX[feature]
        baseline = _run(make_engine(
            cfg, _ecfg(prefix_cache=False, **kw), params, tok,
            use_kernel=False), tok)
        local = make_engine(
            cfg, _ecfg(prefix_host_pages=64, **kw), params, tok,
            use_kernel=False)
        assert _run(local, tok) == baseline
        server = StoreServer(host_pages=64, transport="pipe")
        try:
            remote_eng = make_engine(
                cfg, _ecfg(**kw), params, tok, use_kernel=False,
                prefix_store=RemoteStore(server=server))
            assert _run(remote_eng, tok) == baseline
            assert remote_eng.prefix_cache.evict(10 ** 6) > 0
            assert server.rpc({"op": "stats"})["stats"]["n_host"] > 0
            assert _run(remote_eng, tok) == baseline
            counts = remote_eng._counts or {}
            assert counts.get("engine.prefix_hits_l1", 0) > 0
            assert counts.get("engine.prefix_store_misses_remote", 0) == 0
        finally:
            server.close()

    def test_dead_store_mid_run_is_cold_only_and_parity_holds(self, setup):
        """SIGKILL the store under a warm engine: every later op is a
        counted cold miss, outputs stay byte-identical, and a respawned
        (empty) server picks service back up without any client work."""
        cfg, params, tok = setup
        baseline = _run(make_engine(
            cfg, _ecfg(prefix_cache=False), params, tok,
            use_kernel=False), tok)
        server = StoreServer(host_pages=64, transport="socket")
        try:
            remote = RemoteStore(server=server)
            eng = make_engine(cfg, _ecfg(), params, tok,
                              use_kernel=False, prefix_store=remote)
            assert _run(eng, tok) == baseline
            assert eng.prefix_cache.evict(10 ** 6) > 0
            server.kill()
            assert _run(eng, tok) == baseline             # cold, no error
            counts = eng._counts or {}
            assert counts.get("engine.prefix_store_misses_remote", 0) > 0
            server.respawn()
            assert _run(eng, tok) == baseline             # healed
        finally:
            server.close()


# ---------------------------------------------------------------------------
# pressure-driven demotion: EngineConfig.prefix_hbm_watermark
# ---------------------------------------------------------------------------


class TestWatermark:
    def test_exact_deficit_demotion(self, setup):
        """The tick-boundary sweep demotes EXACTLY the page deficit
        below the watermark — oldest refcount-0 pages first, through
        the coalesced _demote gather — and the freed pages land back
        in the allocator."""
        cfg, params, tok = setup
        eng = make_engine(cfg, _ecfg(prefix_hbm_watermark=4,
                                     prefix_host_pages=64),
                          params, tok, use_kernel=False)
        assert _run(eng, tok)                 # leaves resident r/c-0 pages
        free0 = eng.allocator.n_free
        evictable = eng.prefix_cache.n_evictable
        assert evictable >= 3
        eng._hbm_watermark = free0 + 3        # manufacture a 3-page deficit
        eng._tick_pressure()
        assert eng.allocator.n_free == free0 + 3
        assert (eng._counts or {}).get(
            "engine.prefix_watermark_demotions") == 3.0
        eng._tick_pressure()                  # deficit cleared: no-op
        assert (eng._counts or {}).get(
            "engine.prefix_watermark_demotions") == 3.0
        eng.allocator.check()

    def test_watermark_under_pressure_parity_and_determinism(self, setup):
        """A tight-pool engine under a high watermark demotes
        autonomously DURING the run, stays byte-identical to the
        store-less run, and two identical runs count identically."""
        cfg, params, tok = setup
        baseline = _run(make_engine(
            cfg, _ecfg(prefix_cache=False, num_pages=24), params, tok,
            use_kernel=False), tok)

        def one():
            eng = make_engine(
                cfg, _ecfg(num_pages=24, prefix_hbm_watermark=16,
                           prefix_host_pages=64),
                params, tok, use_kernel=False)
            out = _run(eng, tok)
            return out, (eng._counts or {}).get(
                "engine.prefix_watermark_demotions", 0.0)

        out1, demoted1 = one()
        out2, demoted2 = one()
        assert out1 == baseline and out2 == baseline
        assert demoted1 == demoted2 > 0

    def test_demoted_pages_promote_back_from_remote_store(self, setup):
        """Watermark demotions through a RemoteStore are real L1 pages:
        a warm re-run promotes them back over the wire."""
        cfg, params, tok = setup
        server = StoreServer(host_pages=64, transport="pipe")
        try:
            eng = make_engine(
                cfg, _ecfg(num_pages=24, prefix_hbm_watermark=16),
                params, tok, use_kernel=False,
                prefix_store=RemoteStore(server=server))
            first = _run(eng, tok)
            counts = eng._counts or {}
            assert counts.get("engine.prefix_watermark_demotions", 0) > 0
            assert _run(eng, tok) == first
            assert (eng._counts or {}).get("engine.prefix_hits_l1", 0) > 0
        finally:
            server.close()


# ---------------------------------------------------------------------------
# store-backed instant recovery
# ---------------------------------------------------------------------------


class TestInstantRestore:
    def _interrupt(self, eng, tok, steps=2):
        sids = [eng.submit(tok.encode(p)) for p in PROMPTS]
        out = {}
        for _ in range(steps):
            for r in eng.step():
                out[r.seq_id] = r
        return sids, out

    def test_snapshot_publishes_and_fresh_engine_restores_hot(self, setup):
        """Crash/drain recovery: snapshot_sequences publishes every
        active sequence's full written pages (prompt AND generated)
        into the fabric; a FRESH engine sharing only the store restores
        and finishes byte-identically, re-prefilling from store hits
        instead of recomputing."""
        cfg, params, tok = setup
        baseline = _run(make_engine(
            cfg, _ecfg(prefix_cache=False), params, tok,
            use_kernel=False), tok)
        server = StoreServer(host_pages=64, transport="socket")
        try:
            remote = RemoteStore(server=server)
            src = make_engine(cfg, _ecfg(), params, tok,
                              use_kernel=False, prefix_store=remote)
            sids, out = self._interrupt(src, tok)
            snap = src.snapshot_sequences()
            assert (src._counts or {}).get(
                "engine.prefix_snapshot_published", 0) > 0
            assert server.rpc({"op": "stats"})["stats"]["n_host"] > 0
            fresh = make_engine(cfg, _ecfg(), params, tok,
                                use_kernel=False, prefix_store=remote)
            fresh.restore_sequences(snap)
            while fresh.has_work:
                for r in fresh.step():
                    out[r.seq_id] = r
            fresh.allocator.check()
            assert [out[s].token_ids for s in sids] == baseline
            counts = fresh._counts or {}
            assert counts.get("engine.prefix_hits_l1", 0) > 0
        finally:
            server.close()

    def test_restore_parity_survives_store_death(self, setup):
        """The store dying between snapshot and restore degrades the
        instant restore to a plain re-prefill — byte-identical output,
        counted cold misses, zero errors."""
        cfg, params, tok = setup
        baseline = _run(make_engine(
            cfg, _ecfg(prefix_cache=False), params, tok,
            use_kernel=False), tok)
        server = StoreServer(host_pages=64, transport="pipe")
        try:
            remote = RemoteStore(server=server)
            src = make_engine(cfg, _ecfg(), params, tok,
                              use_kernel=False, prefix_store=remote)
            sids, out = self._interrupt(src, tok)
            snap = src.snapshot_sequences()
            server.kill()
            fresh = make_engine(cfg, _ecfg(), params, tok,
                                use_kernel=False, prefix_store=remote)
            fresh.restore_sequences(snap)
            while fresh.has_work:
                for r in fresh.step():
                    out[r.seq_id] = r
            assert [out[s].token_ids for s in sids] == baseline
            counts = fresh._counts or {}
            assert counts.get("engine.prefix_hits_l1", 0.0) == 0.0
            assert counts.get("engine.prefix_store_misses_remote", 0) > 0
        finally:
            server.close()

    def test_writethrough_makes_peer_fallback_a_store_hit(self, setup):
        """The disagg fallback shape at engine level: a write-through
        engine (the prefill peer) publishes its resident chains every
        growth tick WITHOUT freeing them; after the peer dies, a fresh
        replica re-running the same prompts serves the prefix from the
        fabric — the fallback re-prefill is a store HIT, not a cold
        recompute."""
        cfg, params, tok = setup
        baseline = _run(make_engine(
            cfg, _ecfg(prefix_cache=False), params, tok,
            use_kernel=False), tok)
        server = StoreServer(host_pages=64, transport="socket")
        try:
            peer = make_engine(
                cfg, _ecfg(prefix_store_writethrough=True), params, tok,
                use_kernel=False, prefix_store=RemoteStore(server=server))
            assert _run(peer, tok) == baseline
            assert (peer._counts or {}).get(
                "engine.prefix_writethrough_pages", 0) > 0
            del peer                          # the peer is gone; store lives
            survivor = make_engine(
                cfg, _ecfg(), params, tok, use_kernel=False,
                prefix_store=RemoteStore(server=server))
            assert _run(survivor, tok) == baseline
            counts = survivor._counts or {}
            assert counts.get("engine.prefix_hits_l1", 0) > 0
        finally:
            server.close()


# ---------------------------------------------------------------------------
# satellite: loud exclusions
# ---------------------------------------------------------------------------


class TestExclusions:
    def test_remote_store_requires_prefix_cache(self, setup):
        cfg, params, tok = setup
        server = StoreServer(host_pages=4, transport="pipe")
        try:
            with pytest.raises(ValueError, match="prefix_cache=True"):
                make_engine(cfg, _ecfg(prefix_cache=False), params, tok,
                            use_kernel=False,
                            prefix_store=RemoteStore(server=server))
        finally:
            server.close()

    @pytest.mark.parametrize("knobs,refusal", [
        (dict(prefix_hbm_watermark=-1), ">= 0"),
        (dict(prefix_hbm_watermark=40), "over capacity"),
        (dict(prefix_cache=False, prefix_hbm_watermark=4),
         "prefix_cache=True")])
    def test_watermark_validation(self, setup, knobs, refusal):
        cfg, params, tok = setup
        with pytest.raises(ValueError, match=refusal):
            make_engine(cfg, _ecfg(**knobs), params, tok, use_kernel=False)

    def test_writethrough_requires_a_store(self, setup):
        cfg, params, tok = setup
        with pytest.raises(ValueError, match="write-through"):
            make_engine(cfg, _ecfg(prefix_store_writethrough=True),
                        params, tok, use_kernel=False)

    def test_store_server_validation(self, tmp_path):
        with pytest.raises(ValueError, match="transport"):
            StoreServer(transport="carrier-pigeon")
        with pytest.raises(ValueError, match=">= 0"):
            StoreServer(host_pages=-1)
        with pytest.raises(ValueError, match="disk_dir"):
            StoreServer(host_pages=4, disk_pages=4)
        with pytest.raises(ValueError, match="zero host AND disk"):
            StoreServer(host_pages=0, disk_pages=0)

    def test_remote_store_needs_exactly_one_endpoint(self):
        with pytest.raises(ValueError, match="exactly one"):
            RemoteStore()
        server = StoreServer(host_pages=4, transport="socket")
        try:
            with pytest.raises(ValueError, match="exactly one"):
                RemoteStore(server=server, addr=server.addr)
        finally:
            server.close()

    def test_store_killer_refusals(self):
        from k8s_llm_rca_tpu.faults.soak import run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import StoreKiller

        # unbound killer: no store process to kill
        bare = StoreKiller(FaultPlan.from_spec(
            0, {inject.SITE_STORE: {"indices": {0: "crash"}}}))
        with pytest.raises(ValueError, match="no store bound"):
            bare.checkpoint()
        # soak-level: a StoreKiller without a fabric is refused before
        # any worker spawns
        with pytest.raises(ValueError, match="requires store_fabric"):
            run_chaos_soak(seed=0, n_incidents=1, backend="cluster-oracle",
                           plan_spec={}, killer=bare)
        # SITE_STORE on the ARMED plan is refused: it belongs on the
        # store's own plan
        with pytest.raises(ValueError, match="OWN plan"):
            run_chaos_soak(seed=0, n_incidents=1, plan_spec={
                inject.SITE_STORE: {"indices": {0: "drop"}}})
        # two killers on SITE_STORE: pairwise-disjoint check fires
        other = StoreKiller(FaultPlan.from_spec(1, {}))
        with pytest.raises(ValueError, match="pairwise-disjoint"):
            run_chaos_soak(seed=0, n_incidents=1, backend="cluster-oracle",
                           plan_spec={}, killer=[bare, other])


# ---------------------------------------------------------------------------
# the soak bar: byte-identity with the fabric attached and dying
# ---------------------------------------------------------------------------


class TestSoakByteIdentity:
    def _fabric(self, seed=3):
        return build_store_fabric(
            transport="socket", host_pages=64,
            plan=FaultPlan.from_spec(seed, {inject.SITE_STORE: {
                "indices": {5: "drop", 9: "corrupt"}}}))

    def test_fabric_soak_report_byte_identical(self):
        """A socket fleet with the fabric attached and a StoreKiller
        SIGKILLing/respawning the store mid-sweep must settle
        report_bytes byte-identical to the store-less in-process run —
        kill/heal/miss evidence lives on the killer and fabric objects,
        never in the report."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import StoreKiller

        n = 8
        base = report_bytes(run_chaos_soak(
            seed=5, n_incidents=n, backend="cluster-oracle",
            plan_spec={}))
        fabric = self._fabric()
        killer = StoreKiller(FaultPlan.from_spec(7, {inject.SITE_STORE: {
            "indices": {2: "crash", 5: "heal"}}}))
        try:
            rep = run_chaos_soak(
                seed=5, n_incidents=n, backend="net-cluster",
                plan_spec={}, killer=killer, store_fabric=fabric)
            assert report_bytes(rep) == base
            assert killer.kills == [2] and killer.heals == [5]
            assert fabric.exercised == n
            assert fabric.misses > 0          # the dead window missed
            assert fabric.hits > 0            # the healed window hit
        finally:
            fabric.close()

    def test_dead_fabric_soak_is_cold_only_and_byte_identical(self):
        """The store dead for the WHOLE sweep: every exercise is a cold
        miss, zero engine errors, and the report still matches."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak

        n = 4
        base = report_bytes(run_chaos_soak(
            seed=6, n_incidents=n, backend="cluster-oracle",
            plan_spec={}))
        fabric = build_store_fabric(transport="socket", host_pages=64)
        try:
            fabric.server.kill()
            rep = run_chaos_soak(
                seed=6, n_incidents=n, backend="net-cluster",
                plan_spec={}, killer=None, store_fabric=fabric)
            assert report_bytes(rep) == base
            assert rep["failed"] == 0
            assert fabric.exercised == n
            assert fabric.misses == n and fabric.hits == 0
        finally:
            fabric.close()

    @pytest.mark.slow
    def test_hundred_incident_store_chaos_soak_twice(self):
        """The acceptance bar: 100 seeded incidents on a socket fleet
        with the fabric attached, a StoreKiller (own plan) plus a
        ProcKiller on a DISJOINT site, the store dying and healing
        repeatedly mid-sweep — report_bytes must equal the store-less
        in-process run's, twice over."""
        from k8s_llm_rca_tpu.faults.soak import report_bytes, run_chaos_soak
        from k8s_llm_rca_tpu.faults.supervisor import ProcKiller, StoreKiller

        n = 100
        base = report_bytes(run_chaos_soak(
            seed=11, n_incidents=n, backend="cluster-oracle",
            plan_spec={}))

        def chaos_run():
            fabric = build_store_fabric(
                transport="socket", host_pages=64,
                plan=FaultPlan.from_spec(13, {inject.SITE_STORE: {
                    "rate": 0.1, "horizon": n,
                    "kinds": ("drop", "corrupt", "delay")}}))
            killers = [
                StoreKiller(FaultPlan.from_spec(17, {inject.SITE_STORE: {
                    "indices": {10: "crash", 25: "heal",
                                55: "crash", 70: "heal"}}})),
                ProcKiller(FaultPlan.from_spec(19, {inject.SITE_PROC: {
                    "indices": {40: "crash"}}})),
            ]
            try:
                rep = run_chaos_soak(
                    seed=11, n_incidents=n, backend="net-cluster",
                    plan_spec={}, killer=killers, store_fabric=fabric,
                    selfheal=True)
                return (report_bytes(rep), tuple(killers[0].kills),
                        tuple(killers[0].heals), fabric.exercised,
                        fabric.hits, fabric.misses)
            finally:
                fabric.close()

        r1 = chaos_run()
        r2 = chaos_run()
        assert r1[0] == base
        assert r1 == r2                       # twice over, all evidence
        assert r1[1] == (10, 55) and r1[2] == (25, 70)
        assert r1[3] == n and r1[5] > 0 and r1[4] > 0


# ---------------------------------------------------------------------------
# StoreFabric bundle
# ---------------------------------------------------------------------------


class TestStoreFabric:
    def test_exercise_counts_and_close(self):
        fabric = build_store_fabric(transport="pipe", host_pages=8)
        for i in range(3):
            fabric.exercise(i)
        assert fabric.exercised == 3
        assert fabric.put_ok == 3 and fabric.hits == 3
        assert fabric.misses == 0
        fabric.close()
        assert not fabric.server.alive()

    def test_fabric_remote_store_survives_respawn(self):
        """The fabric's RemoteStore holds the SERVER handle (not a
        frozen address), so a kill/respawn cycle heals transparently."""
        fabric = build_store_fabric(transport="socket", host_pages=8)
        try:
            fabric.exercise(0)
            fabric.server.kill()
            fabric.exercise(1)                # dead: counted miss
            fabric.server.respawn()
            fabric.exercise(2)                # healed: hit again
            assert fabric.misses == 1 and fabric.hits == 2
        finally:
            fabric.close()
