"""Sharded serving fleet: ``shard_map`` over the live query axis.

The scaling companion paper's deployment shape (and this repo's ROADMAP
"sharded serving" item): cross-camera inference spreads across a worker
fleet while the tiny correlation model M stays replicated on every worker.
``ShardedServingEngine`` realizes that split on a jax device mesh:

  * the batched ``PhaseState`` (the per-query search state) is SHARDED over
    the mesh's data axis — each worker owns a contiguous block of query
    rows, padded per shard to a uniform power of two,
  * M, the phase windows, the geo adjacency and the per-round deduplicated
    gallery are REPLICATED (a few small dense arrays — the paper's §7 point
    that the control plane's only persistent state is tiny),
  * the EMBEDDING plane is fleet-shared: by default the fleet injects a
    ``runtime.gallery.ShardedGalleryStore`` behind its ``FrameStore``, so
    the (camera, frame) embedding cache is partitioned over the same data
    axis (camera-hash owner shards, blocks resident on the owner's device)
    instead of replicated per process — one gallery for the whole fleet,
    and fleet-global embed calls match the single engine's exactly (no
    per-shard re-embedding),
  * every device round runs the SAME step bodies as the single-process
    ``ServingEngine`` (``policy.admit``, ``engine.rank_advance_round``)
    wrapped in ``jax.shard_map`` — so the fleet is
    trace-identical to one engine by construction, which the differential
    harness in ``tests/test_sharded_engine.py`` pins down.

Host-side placement is the control plane's job: queries are placed on the
least-loaded worker at submit time (O(1): per-worker live-query counters
are maintained on submit / completion / rebalance, not recounted by
scanning the placement map), and ``lose_worker`` shrinks the data axis via
``runtime.cluster.ElasticMesh`` (largest surviving grid, shardings rebuilt),
re-scatters ONLY the orphaned queries AND re-homes the lost worker's
gallery shards onto the survivors — an elastic scale-down, not a restart.
An optional ``HeartbeatMonitor`` drives the same path from
liveness/straggler signals via ``poll_health``.

What crosses the host link, per round (counted by the fleet, see
``shard_report``; the profiler sees ``rex.fleet.layout`` around the
placement grouping and ``rex.fleet.dispatch`` around each step call):

  * every rank dispatch sends the round gallery and its tags REPLICATED:
    each live worker receives the whole padded (Gp, D) float32 gallery and
    its int32 tag vectors (``replicated_upload_bytes``, counted once per
    receiving worker: 256 rows at D=2,048 is 2 MiB a worker, 8 MiB over
    four).  The geo adjacency is a (C, C) device array on one chip that the
    admit dispatch re-broadcasts, uncounted;
  * the state columns, the query features and segment ids go SHARDED: each
    worker receives its block of rows;
  * the sharded outputs the host reads back (the camera admission mask,
    match and top-k columns, the advanced state but its ``live_f``;
    ``sharded_readback_bytes``) come one block from each worker; the
    matched embeddings and the tile round's fused (camera, tile) mask stay
    on the devices, and the tile counts come back as two replicated ints;
  * the embedding plane's own transfers are the gallery store's
    (``ShardedGalleryStore``: a block put on its owner's chip for every
    fresh embedding, a blocking read back for every cache hit).

Because admission, ranking and the phase machine are pure per-query maps
(the gallery is shared, not recomputed), placement never changes results —
worker loss mid-run keeps the trace bit-identical.  What sharding buys is
capacity: each worker ranks only its block of queries against the round's
gallery, and holds only its cameras' slice of the embedding cache.
"""
from __future__ import annotations

from typing import Iterable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.policy import admit, admit_tiles, tile_counts
from repro.runtime.cluster import ElasticMesh, HeartbeatMonitor
from repro.runtime.engine import (EngineConfig, QueryState, RoundPlan,
                                  ServingEngine, _pow2, advance_round,
                                  rank_advance_round, rank_advance_round_seg,
                                  rank_advance_round_tiles)
from repro.runtime.gallery import (OWNER_COUNTERS, GalleryStore,
                                   LocalGalleryStore, ShardedGalleryStore)

_span = jax.profiler.TraceAnnotation


def make_sharded_step_fns(mesh, policy, topk: int, topk_rerank: bool = False,
                          n_cams: int = 0):
    """The fleet's six jitted shard_map step bodies for ``mesh`` — query
    rows shard over the data axis, model/windows/gallery ride replicated.
    Returned as (admit, rank_advance, rank_advance_seg, advance,
    admit_tiles, rank_advance_tiles); the segment variant is the
    consolidated round's ONE ranking pass, with the per-query segment ids
    sharding alongside the state rows and the gallery's segment tags
    replicated like its cam/frame tags; the tile pair refines camera
    admission to fused (camera, tile) cells — the (Q, C*T*T) mask shards
    with the state rows, the gallery's cell tags replicate.  A segment's
    queries may sit on several shards, so the tile admit gathers the mask
    and segment ids over the data axis and every chip counts the round's
    tiles whole, returned replicated.
    Module-level (not a method) so the static invariant plane
    (``repro.analysis``) can trace and audit the EXACT jaxprs the fleet
    dispatches, on any mesh."""
    Pd, Pr = P("data"), P()

    def _admit(model, state, geo_adj):
        return admit(model, policy, state, geo_adj)

    def _admit_tiles(model, state, geo_adj, tile_q, q_seg):
        mask, mask_ct = admit_tiles(model, policy, state, geo_adj, tile_q)
        counts = tile_counts(
            jax.lax.all_gather(mask_ct, "data", tiled=True),
            jax.lax.all_gather(q_seg, "data", tiled=True))
        return mask, mask_ct, jnp.stack(counts)

    def _rank_advance(windows, state, q_feat, mask, gal, gal_cam, gal_frame):
        return rank_advance_round(policy, windows, state, q_feat, mask, gal,
                                  gal_cam, gal_frame, topk, topk_rerank)

    def _rank_advance_seg(windows, state, q_feat, q_seg, mask, gal, gal_cam,
                          gal_frame, gal_seg):
        return rank_advance_round_seg(policy, windows, state, q_feat, q_seg,
                                      mask, gal, gal_cam, gal_frame, gal_seg,
                                      topk, topk_rerank)

    def _rank_advance_tiles(windows, state, q_feat, q_seg, mask_ct, gal,
                            gal_ct, gal_cam, gal_frame, gal_seg):
        return rank_advance_round_tiles(policy, windows, state, q_feat,
                                        q_seg, mask_ct, gal, gal_ct, gal_cam,
                                        gal_frame, gal_seg, topk, n_cams,
                                        topk_rerank)

    def _advance(windows, state):
        return advance_round(policy, windows, state)

    return (
        jax.jit(shard_map(_admit, mesh=mesh,
                          in_specs=(Pr, Pd, Pr), out_specs=Pd,
                          check_vma=False)),
        jax.jit(shard_map(_rank_advance, mesh=mesh,
                          in_specs=(Pr, Pd, Pd, Pd, Pr, Pr, Pr),
                          out_specs=(Pd,) * 8,
                          check_vma=False)),
        jax.jit(shard_map(_rank_advance_seg, mesh=mesh,
                          in_specs=(Pr, Pd, Pd, Pd, Pd, Pr, Pr, Pr, Pr),
                          out_specs=(Pd,) * 8,
                          check_vma=False)),
        jax.jit(shard_map(_advance, mesh=mesh,
                          in_specs=(Pr, Pd), out_specs=Pd,
                          check_vma=False)),
        jax.jit(shard_map(_admit_tiles, mesh=mesh,
                          in_specs=(Pr, Pd, Pr, Pd, Pd),
                          out_specs=(Pd, Pd, Pr), check_vma=False)),
        jax.jit(shard_map(_rank_advance_tiles, mesh=mesh,
                          in_specs=(Pr, Pd, Pd, Pd, Pd, Pr, Pr, Pr, Pr, Pr),
                          out_specs=(Pd,) * 8,
                          check_vma=False)),
    )


class ShardedServingEngine(ServingEngine):
    """A serving fleet: one controller, ``n_shards`` workers, one trace."""

    def __init__(self, model, embed_fn, cfg: EngineConfig, geo_adj=None, *,
                 shards: int | None = None, devices: Iterable | None = None,
                 monitor: HeartbeatMonitor | None = None,
                 cluster: ElasticMesh | None = None):
        devs = list(devices if devices is not None else jax.devices())
        if shards is not None:
            if shards < 1 or shards > len(devs):
                raise ValueError(
                    f"shards={shards} infeasible: {len(devs)} devices visible")
            devs = devs[:shards]
        if monitor is not None:
            # fail loudly at construction, not as a silent poll_health no-op:
            # every fleet worker id must be a name the monitor tracks
            missing = [f"w{i}" for i in range(len(devs))
                       if f"w{i}" not in monitor.workers]
            if missing:
                raise ValueError(
                    f"HeartbeatMonitor does not track fleet workers "
                    f"{missing} — fleet worker ids are 'w0'..'w{len(devs)-1}'")
        # stable worker identities: position in the ORIGINAL device list.
        # Topology must exist before super().__init__ — the base constructor
        # calls _make_gallery(), and the fleet's gallery shards over it.
        self._device_of = {f"w{i}": d for i, d in enumerate(devs)}
        self._all_workers = list(self._device_of)
        self._workers = list(self._all_workers)        # live, data-axis order
        super().__init__(model, embed_fn, cfg, geo_adj=geo_adj)
        self.cluster = cluster or ElasticMesh(model_parallel=1)
        self.monitor = monitor
        self._placement: dict[int, str] = {}           # qid -> worker
        # O(1) placement: live (not-done) query count per worker, maintained
        # on submit_query / _on_query_done / lose_worker — never recounted
        # by scanning the placement map
        self._live_load = {w: 0 for w in self._all_workers}
        # query_rounds = per-query rounds DISPATCHED for this worker's
        # queries (not engine ticks; skip-mode rounds short-circuited on
        # the host are charged to content_steps but never reach a worker,
        # so sum(query_rounds) == content_steps - skipped_steps).
        # unique_frames is the worker's shard-LOCAL deduplicated demand;
        # owned_frames is its slice of the fleet-GLOBAL dedup set (which
        # camera-owner would serve each deduplicated frame) — the two cost
        # views the gallery plane distinguishes.
        # replicated_upload_bytes / sharded_readback_bytes: the host-link
        # bytes each worker received replicated / sent back (module doc)
        self._shard_stats = {w: dict(admitted_steps=0, unique_frames=0,
                                     owned_frames=0, query_rounds=0,
                                     replicated_upload_bytes=0,
                                     sharded_readback_bytes=0)
                             for w in self._all_workers}
        self.replicated_upload_bytes = 0
        self.sharded_readback_bytes = 0
        self.rebalances = 0
        self._block_hwm = 1          # per-shard batch rows high-water mark
        # transport dead-peer signal: a fetch whose retry budget exhausts
        # mid-round re-homes the gallery IMMEDIATELY (so the blocked fetch
        # can retry against the new owner) and defers the full mesh
        # scale-down to the end of the tick (the mesh must not shrink while
        # a round's shard_map dispatch is in flight)
        self._pending_loss: list[str] = []
        tr = getattr(self.gallery, "transport", None)
        if tr is not None:
            tr.on_dead = self._on_transport_dead
        self._refresh_mesh()

    # -- the gallery plane -------------------------------------------------
    def _make_gallery(self) -> GalleryStore:
        """gallery="auto"/"sharded": ONE fleet-wide embedding plane,
        partitioned over the data axis (camera-hash owner shards, blocks on
        the owner's device).  gallery="local" keeps the replicated-baseline
        semantics (a private host-side cache, as if each engine re-embedded
        for itself) — what ``gallery_sweep`` compares against."""
        if self.cfg.gallery in ("auto", "sharded"):
            return ShardedGalleryStore(self.C, self.cfg.retention,
                                       self._all_workers, self._device_of,
                                       transport=self.cfg.transport)
        if self.cfg.gallery == "local":
            if self.cfg.transport is not None:
                raise ValueError(
                    "transport= requires the sharded gallery "
                    "(gallery='auto'/'sharded'): the replicated-local "
                    "baseline has no remote owners to fetch from")
            return LocalGalleryStore(self.C, self.cfg.retention)
        raise ValueError(f"unknown gallery mode {self.cfg.gallery!r} "
                         f"(expected 'auto', 'local' or 'sharded')")

    def gallery_report(self) -> dict:
        rep = super().gallery_report()
        if isinstance(self.gallery, ShardedGalleryStore):
            rep["per_worker"] = self.gallery.per_worker_report()
        return rep

    # -- fleet topology ----------------------------------------------------
    @property
    def n_shards(self) -> int:
        return len(self._workers)

    def _refresh_mesh(self) -> None:
        """(Re)build the mesh over the surviving workers: the data axis
        shrinks to the live count (``ElasticMesh.grid_for``), and the cached
        shard_map callables are invalidated so the next round lowers onto
        the new grid.  The replicated control-plane state (M + the phase
        windows) is re-committed to the new mesh so a fleet that already
        hot-swapped its model never dispatches arrays committed to a dead
        device."""
        if not self._workers:
            raise RuntimeError("serving fleet has no surviving workers")
        self.mesh = self.cluster.make_mesh(
            [self._device_of[w] for w in self._workers])
        self._shard_of = {w: i for i, w in enumerate(self._workers)}
        self._sharded_fns = None
        self._replicate_control_plane()

    def _replicate_control_plane(self) -> None:
        """Commit M and the phase windows replicated onto every shard of the
        CURRENT mesh — one transfer at swap/re-mesh time instead of an
        implicit broadcast on every dispatch."""
        rep = NamedSharding(self.mesh, P())
        self.model = jax.device_put(self.model, rep)
        self._windows = jax.device_put(self._windows, rep)

    def swap_model(self, model) -> int:
        """Fleet hot-swap: the base swap (atomic between rounds — the
        mid-round guard is what makes 'every shard sees one M per round'
        hold), then the new M/windows are re-replicated onto every live
        shard of the mesh in one device_put.  The shard_map callables are
        untouched: M rides in as a replicated ARGUMENT, so a swap never
        recompiles or re-lowers the step bodies."""
        epoch = super().swap_model(model)
        self._replicate_control_plane()
        return epoch

    def _load(self, worker: str) -> int:
        """Live (not-done) queries placed on ``worker`` — O(1), from the
        maintained counters (equal to scanning the placement map, which the
        load-accounting test pins)."""
        return self._live_load.get(worker, 0)

    def _least_loaded(self) -> str:
        return min(self._workers, key=lambda w: (self._load(w),
                                                 self._shard_of[w]))

    def submit_query(self, qid: int, feat, cam: int, frame: int):
        if qid in self._placement:     # resubmission: retire the old count
            old = self._placement[qid]
            q_old = self.queries.get(qid)
            if q_old is not None and not q_old.done:
                self._live_load[old] -= 1
        super().submit_query(qid, feat, cam, frame)
        w = self._least_loaded()
        self._placement[qid] = w
        self._live_load[w] += 1

    def _on_query_done(self, q: QueryState) -> None:
        self._live_load[self._placement[q.qid]] -= 1

    def lose_worker(self, worker: str | int) -> list[int]:
        """Elastic scale-down: drop one worker, shrink the data axis,
        re-scatter its orphaned queries over the survivors (least-loaded
        first, round-robin via ``ElasticMesh.rebalance_streams``) and
        re-home its gallery shards (camera ownership + device-resident
        blocks migrate; the shared cache survives the worker).  Returns
        the re-placed qids."""
        w = f"w{worker}" if isinstance(worker, int) else worker
        if w not in self._workers:
            raise KeyError(f"{w!r} is not a live worker (live: {self._workers})")
        if len(self._workers) == 1:
            raise RuntimeError("cannot lose the last worker of the fleet")
        self._workers.remove(w)
        self._refresh_mesh()
        tr = getattr(self.gallery, "transport", None)
        if tr is not None:
            # in-flight fetches (prefetch handles included) to the lost
            # worker now fail fast with PeerDeadError instead of timing out
            tr.mark_dead(w)
        if isinstance(self.gallery, ShardedGalleryStore):
            self.gallery.rehome(w, list(self._workers))
        orphans = sorted(qid for qid, pw in self._placement.items() if pw == w)
        self._live_load[w] = 0
        targets = sorted(self._workers,
                         key=lambda t: (self._load(t), self._shard_of[t]))
        for tw, group in zip(targets,
                             self.cluster.rebalance_streams(orphans,
                                                            len(targets))):
            for qid in group:
                self._placement[qid] = tw
                q = self.queries.get(qid)
                if q is not None and not q.done:
                    self._live_load[tw] += 1
        self.rebalances += 1
        return orphans

    def _on_transport_dead(self, w: str) -> None:
        """The transport's dead-peer signal: a fetch to ``w`` exhausted its
        retry budget.  Mid-round the mesh cannot shrink (a shard_map
        dispatch may be in flight), but the gallery CAN re-home immediately
        — ownership remapping touches no mesh state, and it is exactly what
        lets the blocked fetch retry against the block's new owner instead
        of failing the round.  The full scale-down (mesh shrink + orphan
        re-scatter) runs at the end of the tick."""
        if w not in self._workers or len(self._workers) == 1:
            return
        if self.monitor is not None and w in self.monitor.workers:
            self.monitor.quarantine(w)
        if self._in_round:
            if w not in self._pending_loss:
                self._pending_loss.append(w)
                self.gallery.rehome(
                    w, [x for x in self._workers if x != w])
        else:
            self.lose_worker(w)

    def tick(self, record_trace: list | None = None) -> dict:
        stats = super().tick(record_trace)
        # drain transport-discovered worker deaths: the gallery already
        # re-homed mid-round; now the mesh shrinks and queries re-scatter
        # (lose_worker's own rehome is a no-op — ownership moved already)
        while self._pending_loss:
            w = self._pending_loss.pop(0)
            if w in self._workers and len(self._workers) > 1:
                self.lose_worker(w)
        return stats

    def poll_health(self) -> list[str]:
        """Drive elastic scale-down from the HeartbeatMonitor: dead workers
        and (quarantined) stragglers leave the fleet, their queries
        re-scatter.  No-op without a monitor."""
        if self.monitor is None:
            return []
        removed = []
        for w in self.monitor.stragglers():
            # quarantine only workers this fleet actually removes — the
            # monitor may track a superset, and the last worker stays
            if w in self._workers and len(self._workers) > 1:
                self.monitor.quarantine(w)
                self.lose_worker(w)
                removed.append(w)
        for w in self.monitor.dead():
            if w in self._workers and len(self._workers) > 1:
                self.lose_worker(w)
                removed.append(w)
        return removed

    # -- sharded layout + dispatch ----------------------------------------
    def _layout(self, qs: list[QueryState]) -> tuple[int, np.ndarray]:
        """Group batch rows by worker placement: shard s owns rows
        [s*block, (s+1)*block) with block a fleet-uniform power of two, so
        ``shard_map`` splits the padded batch into exactly the host-side
        placement.  Padding rows are ``done`` (admit nothing, rank to
        (NEG_INF, -1)) just like the single engine's."""
        with _span("rex.fleet.layout"):
            groups: list[list[int]] = [[] for _ in self._workers]
            for i, q in enumerate(qs):
                groups[self._shard_of[self._placement[q.qid]]].append(i)
            block = _pow2(max(max((len(g) for g in groups), default=0), 1))
            # shard-block high-water mark: a shrinking cohort keeps the
            # compiled per-shard block (padding rows are done), so steady
            # state never mints a smaller shard_map signature
            # (RecompileGuard's contract)
            self._block_hwm = max(self._block_hwm, block)
            block = self._block_hwm
            slots = np.zeros(len(qs), np.int64)
            for s, g in enumerate(groups):
                slots[g] = s * block + np.arange(len(g))
            return len(self._workers) * block, slots

    def prime_batch(self, n_queries: int) -> None:
        """Fleet variant of the single engine's ``prime_batch``: pre-size
        the per-shard block for ``n_queries`` spread over the current
        workers (balanced placement; a later imbalance can still grow the
        block, which the guard's one-new-signature allowance covers)."""
        per = -(-max(int(n_queries), 1) // max(len(self._workers), 1))
        self._block_hwm = max(self._block_hwm, _pow2(per))

    def _fns(self):
        """shard_map-wrapped step bodies for the CURRENT mesh (lazily built;
        invalidated on every elastic re-mesh).  State rows shard over the
        data axis; model/windows/geo/gallery ride along replicated."""
        if self._sharded_fns is None:
            self._sharded_fns = make_sharded_step_fns(
                self.mesh, self.policy, self.cfg.topk,
                topk_rerank=self.cfg.topk_rerank, n_cams=self.C)
        return self._sharded_fns

    def _uploaded(self, *arrays) -> None:
        """Charge arrays one dispatch sends replicated: every live worker
        receives each one whole."""
        nbytes = sum(a.nbytes for a in arrays)
        for w in self._workers:
            self._shard_stats[w]["replicated_upload_bytes"] += nbytes
        self.replicated_upload_bytes += nbytes * len(self._workers)

    def _read_back(self, *arrays) -> None:
        """Charge sharded outputs the round copies to the host: each live
        worker sends its equal block of rows."""
        nbytes = sum(a.nbytes for a in arrays)
        for w in self._workers:
            self._shard_stats[w]["sharded_readback_bytes"] += \
                nbytes // len(self._workers)
        self.sharded_readback_bytes += nbytes

    @staticmethod
    def _state_read(ps):
        """The columns of an advanced state the round scatters back to its
        queries (all but ``live_f``)."""
        return ps.f_q, ps.c_q, ps.f_curr, ps.phase, ps.done

    def _rank_read(self, out):
        """Charge a rank step's read-back outputs; the matched embeddings
        (index 3) stay on the devices."""
        nxt, m, mc, _, tv, ti, tc, tf = out
        self._read_back(*self._state_read(nxt), m, mc, tv, ti, tc, tf)
        return out

    def _dispatch_admit(self, ps):
        with _span("rex.fleet.dispatch"):
            m = self._fns()[0](self.model, ps, self._geo_adj)
        self._read_back(m)
        return m

    def _dispatch_admit_tiles(self, ps, tile_q, q_seg):
        with _span("rex.fleet.dispatch"):
            m, m_ct, counts = self._fns()[4](self.model, ps, self._geo_adj,
                                             tile_q, q_seg)
        # the fused mask stays on the devices for the rank step; the
        # replicated counts are no sharded output
        self._read_back(m)
        return m, m_ct, counts

    def _dispatch_rank_advance(self, ps, q_feat, mask, gallery, gal_cam,
                               gal_frame):
        self._uploaded(gallery, gal_cam, gal_frame)
        with _span("rex.fleet.dispatch"):
            out = self._fns()[1](self._windows, ps, q_feat, mask, gallery,
                                 gal_cam, gal_frame)
        return self._rank_read(out)

    def _dispatch_rank_advance_seg(self, ps, q_feat, q_seg, mask, gallery,
                                   gal_cam, gal_frame, gal_seg):
        self._uploaded(gallery, gal_cam, gal_frame, gal_seg)
        with _span("rex.fleet.dispatch"):
            out = self._fns()[2](self._windows, ps, q_feat, q_seg, mask,
                                 gallery, gal_cam, gal_frame, gal_seg)
        return self._rank_read(out)

    def _dispatch_rank_advance_tiles(self, ps, q_feat, q_seg, mask_ct,
                                     gallery, gal_ct, gal_cam, gal_frame,
                                     gal_seg):
        self._uploaded(gallery, gal_ct, gal_cam, gal_frame, gal_seg)
        with _span("rex.fleet.dispatch"):
            out = self._fns()[5](self._windows, ps, q_feat, q_seg, mask_ct,
                                 gallery, gal_ct, gal_cam, gal_frame,
                                 gal_seg)
        return self._rank_read(out)

    def _dispatch_advance(self, ps):
        with _span("rex.fleet.dispatch"):
            nxt = self._fns()[3](self._windows, ps)
        self._read_back(*self._state_read(nxt))
        return nxt

    # -- per-shard cost accounting ----------------------------------------
    def _account_round(self, plan: RoundPlan) -> None:
        """Per-worker view of the round, in BOTH cost conventions the
        gallery plane distinguishes: ``unique_frames`` is the worker's
        shard-LOCAL deduplicated (cam, frame) demand — what it would embed
        if every worker kept a private replicated cache; ``owned_frames``
        is the worker's slice of ``plan.work``, the round's fleet-GLOBAL
        dedup set (the frames whose camera it owns in the sharded
        gallery), which tiles the engine's ``unique_frames`` exactly."""
        qs, cams_by_q = plan.qs, plan.cams_by_q
        by_worker: dict[str, list[int]] = {}
        for i, q in enumerate(qs):
            by_worker.setdefault(self._placement[q.qid], []).append(i)
        for w, idxs in by_worker.items():
            st = self._shard_stats[w]
            st["query_rounds"] += len(idxs)
            st["admitted_steps"] += sum(len(cams_by_q[i]) for i in idxs)
            pairs = {(int(cam), qs[i].f_curr)
                     for i in idxs for cam in cams_by_q[i]}
            st["unique_frames"] += len(pairs)
        if isinstance(self.gallery, ShardedGalleryStore):
            # plan.work is already camera-major sorted, so owned_frames
            # counts never depend on hash-iteration order
            for cam, _f in plan.work:
                owner = self.gallery.owner_of(cam)
                self._shard_stats[owner]["owned_frames"] += 1

    def shard_report(self) -> list[dict]:
        """One row per worker (including lost ones, stats frozen): placement
        load and the cost conventions — ``admitted_steps`` (tiles the engine
        total), ``unique_frames`` (shard-local demand: what a replicated
        per-worker cache would embed) and ``owned_frames`` (the worker's
        slice of the fleet-global dedup set; sums to the engine's
        ``unique_frames`` when the gallery is sharded) — and its host-link
        bytes: ``replicated_upload_bytes``, ``sharded_readback_bytes`` and,
        with the sharded gallery, its owner transfers (``OWNER_COUNTERS``).
        Each byte column sums to the fleet's counter."""
        live = set(self._workers)
        rows = [dict(worker=w, device=self._device_of[w].id,
                     alive=w in live,
                     queries=self._load(w) if w in live else 0,
                     **self._shard_stats[w])
                for w in self._all_workers]
        if isinstance(self.gallery, ShardedGalleryStore):
            per_w = self.gallery.per_worker_report()
            keys = OWNER_COUNTERS
            if self.gallery.transport is not None:
                # fetch-plane traffic per owner peer: prefetch efficiency
                # and fault pressure are observable per worker
                keys += ("remote_fetches", "retries", "timeouts")
            for row in rows:
                row.update((k, per_w[row["worker"]][k]) for k in keys)
        return rows
