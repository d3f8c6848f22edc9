"""The gallery/embedding plane: one feature store behind the engines.

The paper keeps "the last few minutes" of video hot (§5.3); its scaling
companion (Jain et al., *Scaling Video Analytics Systems to Large Camera
Deployments*) argues cross-camera workloads should SHARE inference state
across workers instead of recomputing it per process.  This module is that
shared state: the (camera, frame) -> embedding-block cache the serving
engines consult before calling ``embed_fn``, extracted out of ``FrameStore``
so one fleet can put a single gallery plane behind every engine.

Two implementations of one ``GalleryStore`` contract:

* ``LocalGalleryStore`` — host-resident per-camera dicts, exactly the
  per-engine semantics ``FrameStore`` used to hard-code.  The single-process
  engine's default, and the fleet's "replicated baseline" mode.
* ``ShardedGalleryStore`` — the (camera, frame) key space partitioned over
  the fleet's data axis: each camera hashes to one OWNER worker, and that
  camera's embedding blocks live on the owner's device (``jax.device_put``),
  row-padded to a power of two like the engines' round galleries so device
  buffer shapes stay bounded.  Hit/miss/eviction counters are fleet-wide —
  the whole fleet shares one gallery, so a frame embedded for a query on
  shard 0 is cache-hot for a query on shard 3.

Both share the base class's retention bookkeeping, which mirrors
``FrameStore``: a per-camera monotonic key deque gives O(1) amortized
retention-horizon eviction on ``put``; an out-of-order ``put`` stays correct
(``get`` re-checks the horizon) but its eviction may be deferred until the
deque head catches up to it.  ``FrameStore`` additionally calls ``drop`` for
every frame key it evicts, so embeddings never outlive their frames.
"""
from __future__ import annotations

import collections
from typing import Any

import numpy as np

from repro.runtime.transport import LocalFetchHandle, PeerDeadError


def pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1) — the shared padding rule for jit
    shapes and device-resident gallery blocks."""
    return 1 << max(n - 1, 0).bit_length()


def l2_normalize(a: np.ndarray, eps: float = 1e-9) -> np.ndarray:
    """Row-unit-normalize 1-D or 2-D embeddings (zero rows stay zero).

    The embedding plane's ONE normalization rule: the engines call this at
    ingest/update time so the hot round bodies never run host-numpy
    reductions per round (lint rule REX001)."""
    a = np.asarray(a, np.float32)
    if a.ndim == 1:
        return a / max(float(np.linalg.norm(a)), eps)
    return a / np.maximum(np.linalg.norm(a, axis=-1, keepdims=True), eps)


# ShardedGalleryStore's transfer counters, per owner worker
OWNER_COUNTERS = ("owner_puts", "owner_put_bytes", "owner_reads",
                  "owner_read_bytes")


def _cam_hash(cam: int) -> int:
    """Stable camera hash (Knuth multiplicative) for owner-shard choice —
    spreads consecutive camera ids instead of striping them."""
    return ((cam + 1) * 2654435761) & 0xFFFFFFFF


class GalleryStore:
    """The embedding-plane contract both engines program to.

    ``put(cam, t, emb) -> bool`` caches one (camera, frame) embedding block
    (False = rejected: already behind the retention horizon), ``get`` returns
    the cached block or None (miss / evicted), ``drop`` removes one key (the
    frame-eviction driven path).  Subclasses implement the storage backend
    (``_store`` / ``_fetch`` / ``_drop``); retention bookkeeping and the
    hit/miss/eviction/put/rejected counters live here so every backend
    behaves identically.
    """

    kind = "base"

    def __init__(self, n_cams: int, retention: int):
        self.n_cams = n_cams
        self.retention = retention
        self._keys: list[collections.deque] = [collections.deque()
                                               for _ in range(n_cams)]
        self._latest = np.full(n_cams, -1, np.int64)
        self.hits = 0        # get() served from the store
        self.misses = 0      # get() found nothing (uncached or evicted)
        self.evictions = 0   # cached blocks dropped (horizon or frame-evict)
        self.puts = 0        # blocks accepted
        self.rejected = 0    # puts refused (behind the retention horizon)
        self.prefetch_hits = 0    # blocks served from the prefetch buffer
        self.prefetch_wasted = 0  # prefetched blocks discarded (misspeculation)

    # -- retention bookkeeping (FrameStore-identical) ----------------------
    def _horizon(self, cam: int) -> int:
        return int(self._latest[cam]) - self.retention

    def _evict_horizon(self, cam: int) -> None:
        horizon = self._horizon(cam)
        keys = self._keys[cam]
        while keys and keys[0] < horizon:
            key = keys.popleft()
            if self._drop(cam, key):
                self.evictions += 1

    # -- the contract ------------------------------------------------------
    def put(self, cam: int, t: int, emb: Any) -> bool:
        """Cache one embedding block; False when t is already behind the
        retention horizon (the write would be dead on arrival)."""
        if t > self._latest[cam]:
            self._latest[cam] = t
        if t < self._horizon(cam):
            self.rejected += 1
            return False
        if not self._has(cam, t):
            self._keys[cam].append(t)
        self._store(cam, t, emb)
        self.puts += 1
        self._evict_horizon(cam)
        return True

    def get(self, cam: int, t: int) -> Any:
        """Cached block for (cam, t), or None.  Re-checks the horizon so an
        out-of-order put whose eviction is deferred never serves stale data."""
        if t < self._horizon(cam):
            self.misses += 1
            return None
        emb = self._fetch(cam, t)
        if emb is None:
            self.misses += 1
        else:
            self.hits += 1
        return emb

    def cached(self, cam: int, t: int) -> bool:
        """Whether a retained block for (cam, t) is resident right now —
        the prefetch plane's validity check, no counters tick."""
        return t >= self._horizon(cam) and self._has(cam, t)

    def fetch_async(self, cam: int, t: int):
        """Issue an async fetch for a CACHED (cam, t) block: a handle for
        ``wait_fetch``, or None when the block is uncached / behind the
        horizon.  No hit/miss counters tick at issue time — the consumer
        accounts at consume time (``PrefetchPipeline``), so speculation
        never skews the cache statistics."""
        if t < self._horizon(cam) or not self._has(cam, t):
            return None
        return self._fetch_async(cam, t)

    def wait_fetch(self, handle) -> Any:
        """Deliver an async fetch.  May return None (the block vanished
        between issue and wait) or raise ``PeerDeadError`` (remote owner
        lost mid-fetch); the caller falls back to the blocking path."""
        if isinstance(handle, LocalFetchHandle):
            if handle.t < self._horizon(handle.cam):
                return None
            return self._fetch(handle.cam, handle.t)
        raise TypeError(f"unknown fetch handle {handle!r}")

    def drop(self, cam: int, t: int) -> bool:
        """Remove one key (frame-eviction driven: ``FrameStore`` calls this
        for every frame it evicts so embeddings never outlive frames).  The
        deque entry stays; popping it later is a no-op."""
        removed = self._drop(cam, t)
        if removed:
            self.evictions += 1
        return removed

    # -- backend hooks -----------------------------------------------------
    def _store(self, cam: int, t: int, emb: Any) -> None:
        raise NotImplementedError

    def _fetch(self, cam: int, t: int) -> Any:
        raise NotImplementedError

    def _drop(self, cam: int, t: int) -> bool:
        raise NotImplementedError

    def _has(self, cam: int, t: int) -> bool:
        raise NotImplementedError

    def _fetch_async(self, cam: int, t: int) -> Any:
        """Backend async fetch for a known-resident key.  The base path is
        the degenerate immediate handle (re-reads the store at wait time);
        a transport-backed store returns a real in-flight handle."""
        return LocalFetchHandle(cam, t)

    # -- accounting --------------------------------------------------------
    def cached_embeddings(self) -> int:
        raise NotImplementedError

    def memory_bytes(self) -> int:
        raise NotImplementedError

    def counters(self) -> dict:
        # transport-era keys are zeros here; a transport-backed store
        # overrides them with the live fetch-plane stats
        return dict(hits=self.hits, misses=self.misses,
                    evictions=self.evictions, puts=self.puts,
                    rejected=self.rejected, cached=self.cached_embeddings(),
                    bytes=self.memory_bytes(),
                    prefetch_hits=self.prefetch_hits,
                    prefetch_wasted=self.prefetch_wasted,
                    remote_fetches=0, retries=0, timeouts=0)


class LocalGalleryStore(GalleryStore):
    """Host-resident per-camera dicts — today's per-engine semantics."""

    kind = "local"

    def __init__(self, n_cams: int, retention: int):
        super().__init__(n_cams, retention)
        self._emb: list[dict[int, Any]] = [dict() for _ in range(n_cams)]

    def _store(self, cam, t, emb):
        self._emb[cam][t] = emb

    def _fetch(self, cam, t):
        return self._emb[cam].get(t)

    def _drop(self, cam, t):
        return self._emb[cam].pop(t, None) is not None

    def _has(self, cam, t):
        return t in self._emb[cam]

    def cached_embeddings(self):
        return sum(len(e) for e in self._emb)

    def memory_bytes(self):
        return sum(getattr(e, "nbytes", 0)
                   for d in self._emb for e in d.values())


class ShardedGalleryStore(GalleryStore):
    """One fleet-wide gallery: camera-hash owner shards over the data axis.

    Every camera maps to one owner worker (``_cam_hash(cam) % live``) and
    that camera's blocks are ``jax.device_put`` onto the owner's device,
    rows padded to a power of two (bounded device buffer shapes — the same
    rule the engines use for round galleries).  ``rehome`` migrates a lost
    worker's cameras (and their resident blocks) onto the survivors, the
    gallery-plane counterpart of the fleet's orphan-query re-scatter;
    surviving owners keep their cameras, so only the lost shard moves.

    Blocks must be numpy arrays (the engines' (n, D) float32 embedding
    batches); values round-trip the device bit-exactly, which is what keeps
    the sharded-gallery fleet trace-identical to the single engine.

    What each transfer costs, and when it happens:

    * ``put`` — every freshly embedded (camera, frame) block: one
      host-to-device copy of the padded block, pow2(n) * D * 4 bytes, onto
      the owner's chip (8 KiB a row at D=2,048).  The round that embeds a
      frame ranks it from the engine's own host copy, so the owner copy is
      written for later rounds only.
    * ``get`` hit — any later round asking for the frame again: a replay
      re-read, a rewound cursor, or, on live traffic, a query a step behind
      the live edge ranking a frame others embedded the tick before.  One
      blocking device-to-host copy of the whole padded block
      (``np.asarray``), sliced to its n rows.
    * ``rehome`` — a device-to-host-to-device round trip per migrated block,
      once per worker loss; not counted below.

    ``counters()`` and ``per_worker_report()`` count the first two per
    owner: ``owner_puts`` / ``owner_put_bytes`` and ``owner_reads`` /
    ``owner_read_bytes``, bytes at the padded device shape.  The profiler
    sees them as the ``rex.fleet.owner_put`` and ``rex.fleet.owner_read``
    spans.

    With a ``transport`` (``runtime.transport``), every fetch of an
    owner-resident block goes through the fetch plane addressed to the
    block's owner peer — in-proc that is a zero-copy read, fake-RPC it
    pays injected latency and may retry/time out.  A ``PeerDeadError``
    during a blocking fetch re-resolves ownership: if the dead-peer signal
    re-homed the camera (the fleet's ``on_dead`` wiring), the fetch retries
    against the block's new owner; otherwise it surfaces.
    """

    kind = "sharded"

    def __init__(self, n_cams: int, retention: int, workers: list[str],
                 device_of: dict[str, Any], transport: Any = None):
        super().__init__(n_cams, retention)
        if not workers:
            raise ValueError("sharded gallery needs at least one worker")
        missing = [w for w in workers if w not in device_of]
        if missing:
            raise ValueError(f"workers {missing} have no device mapping")
        self._device_of = dict(device_of)
        self._owner = {cam: workers[_cam_hash(cam) % len(workers)]
                       for cam in range(n_cams)}
        # (cam, t) -> (device-resident padded block, valid row count)
        self._blocks: dict[tuple[int, int], tuple[Any, int]] = {}
        self.rehomed_blocks = 0
        self.transport = transport
        # per owner: blocks put / read back and their padded device bytes
        self._owner_io = {w: dict.fromkeys(OWNER_COUNTERS, 0)
                          for w in self._device_of}

    def owner_of(self, cam: int) -> str:
        return self._owner[cam]

    def _store(self, cam, t, emb):
        import jax

        emb = np.asarray(emb)
        n = emb.shape[0]
        rows = pow2(n)
        if rows > n:
            emb = np.concatenate(
                [emb, np.zeros((rows - n,) + emb.shape[1:], emb.dtype)])
        owner = self._owner[cam]
        with jax.profiler.TraceAnnotation("rex.fleet.owner_put"):
            arr = jax.device_put(emb, self._device_of[owner])
        self._blocks[(cam, t)] = (arr, n)
        io = self._owner_io[owner]
        io["owner_puts"] += 1
        io["owner_put_bytes"] += emb.nbytes

    def _read_block(self, owner, blk):
        """Copy one resident block back to the host (blocking) and count it
        against ``owner``."""
        import jax

        arr, n = blk
        with jax.profiler.TraceAnnotation("rex.fleet.owner_read"):
            host = np.asarray(arr)
        io = self._owner_io[owner]
        io["owner_reads"] += 1
        io["owner_read_bytes"] += host.nbytes
        return host[:n]

    def _fetch(self, cam, t):
        while True:
            blk = self._blocks.get((cam, t))
            if blk is None:
                return None
            owner = self._owner[cam]
            if self.transport is None:
                return self._read_block(owner, blk)
            try:
                return self.transport.fetch(
                    owner, (cam, t),
                    lambda o=owner, b=blk: self._read_block(o, b))
            except PeerDeadError:
                if self._owner[cam] == owner:
                    raise          # nobody re-homed the camera: surface it
                # the dead-peer signal re-homed it mid-fetch — retry against
                # the new owner (the block moved with the camera)

    def _fetch_async(self, cam, t):
        if self.transport is None:
            return super()._fetch_async(cam, t)
        blk = self._blocks[(cam, t)]
        owner = self._owner[cam]
        return self.transport.fetch_async(
            owner, (cam, t), lambda: self._read_block(owner, blk))

    def wait_fetch(self, handle):
        if isinstance(handle, LocalFetchHandle):
            return super().wait_fetch(handle)
        return self.transport.wait(handle)

    def _drop(self, cam, t):
        return self._blocks.pop((cam, t), None) is not None

    def _has(self, cam, t):
        return (cam, t) in self._blocks

    def rehome(self, lost: str, survivors: list[str]) -> int:
        """Re-home the lost worker's cameras onto the survivors (camera-hash
        over the surviving list) and migrate their resident blocks.  Returns
        the number of blocks moved."""
        import jax

        if not survivors:
            raise RuntimeError("cannot re-home the gallery: no survivors")
        remap = {cam: survivors[_cam_hash(cam) % len(survivors)]
                 for cam, w in self._owner.items() if w == lost}
        self._owner.update(remap)
        moved = 0
        for key, (arr, n) in list(self._blocks.items()):
            if key[0] in remap:
                self._blocks[key] = (
                    jax.device_put(np.asarray(arr),
                                   self._device_of[remap[key[0]]]), n)
                moved += 1
        self.rehomed_blocks += moved
        return moved

    def cached_embeddings(self):
        return len(self._blocks)

    def memory_bytes(self):
        return sum(arr.nbytes for arr, _ in self._blocks.values())

    def counters(self):
        c = dict(super().counters(), rehomed_blocks=self.rehomed_blocks)
        for k in OWNER_COUNTERS:
            c[k] = sum(io[k] for io in self._owner_io.values())
        if self.transport is not None:
            c.update(self.transport.counters())
        return c

    def per_worker_report(self) -> dict[str, dict]:
        """Owner-resident cache memory, per worker: cameras owned, resident
        blocks/rows/bytes, blocks ``misplaced`` off the owner's device
        (always 0 unless placement is broken), the owner transfers so far
        (``OWNER_COUNTERS``, kept by a lost worker), plus the fetch plane's
        per-peer traffic when a transport is attached.  Lost workers report
        zero residency after ``rehome``."""
        rep = {w: dict(cameras=0, blocks=0, rows=0, bytes=0, misplaced=0,
                       remote_fetches=0, retries=0, timeouts=0,
                       **self._owner_io[w])
               for w in self._device_of}
        for w in self._owner.values():
            rep[w]["cameras"] += 1
        for (cam, _t), (arr, n) in self._blocks.items():
            owner = self._owner[cam]
            r = rep[owner]
            r["blocks"] += 1
            r["rows"] += n
            r["bytes"] += arr.nbytes
            r["misplaced"] += arr.devices() != {self._device_of[owner]}
        if self.transport is not None:
            for w, st in self.transport.peer_counters().items():
                if w in rep:
                    rep[w]["remote_fetches"] = st["fetches"]
                    rep[w]["retries"] = st["retries"]
                    rep[w]["timeouts"] = st["timeouts"]
        return rep


class RoundStaging:
    """Host buffers one engine stages every round's inputs in, reused from
    round to round: the padded round gallery ``gal`` (rows, D) float32 with
    its per-row tags ``cam``, ``frame``, ``seg`` (compact segment id) and
    ``ct`` (fused camera-tile cell), and the (N, D) float32 query features
    ``q_feat``.  Buffers grow to a new high-water mark and never shrink, so
    a primed engine allocates them once; ``allocs`` counts every allocation
    or growth.  Rows past the last fill are zero with tags -1 (the padding
    contract), kept so by resetting only the rows the previous round wrote
    and the current one does not.

    A buffer is refilled only once the round that read it has its results
    back on the host (that read blocks on the step that consumed the
    upload), so no array built from a buffer may outlive its round."""

    def __init__(self):
        self.gal = np.zeros((0, 0), np.float32)
        self.cam = self.frame = self.seg = self.ct = np.zeros(0, np.int32)
        self.rows = 0             # real gallery rows of the last fill
        self.q_feat = np.zeros((0, 0), np.float32)
        self._q_used = np.zeros(0, bool)   # q_feat rows the last fill wrote
        self.allocs = 0

    def reserve_gallery(self, rows: int, dim: int) -> None:
        """Hold at least ``rows`` gallery rows of width ``dim``."""
        if rows <= self.gal.shape[0] and dim == self.gal.shape[1]:
            return
        rows = max(rows, self.gal.shape[0])
        self.gal = np.zeros((rows, dim), np.float32)
        self.cam, self.frame, self.seg, self.ct = (
            np.full(rows, -1, np.int32) for _ in range(4))
        self.rows = 0
        self.allocs += 1

    def stage_queries(self, n: int, slots: np.ndarray, feats: list):
        """Write ``feats[i]`` into row ``slots[i]`` of the (n, D) query
        block, zero the rows the previous fill wrote and this one does not,
        and return the block."""
        dim = len(feats[0])
        if n > self.q_feat.shape[0] or dim != self.q_feat.shape[1]:
            self.q_feat = np.zeros((max(n, self.q_feat.shape[0]), dim),
                                   np.float32)
            self._q_used = np.zeros(self.q_feat.shape[0], bool)
            self.allocs += 1
        used = np.zeros_like(self._q_used)
        used[slots] = True
        self.q_feat[self._q_used & ~used] = 0.0
        self._q_used = used
        for j, f in zip(slots, feats):
            self.q_feat[j] = f
        return self.q_feat[:n]


def assemble_round_gallery(batch_keys: list[tuple[int, int]],
                           key_emb: dict[tuple[int, int], np.ndarray],
                           min_rows: int = 1,
                           out: RoundStaging | None = None):
    """One round's deduplicated gallery, engine-ready: concatenate the
    per-key embedding blocks IN ``batch_keys`` ORDER (the engines pass
    camera-major sorted keys, which is what keeps the kernel's flat-argmin
    tie-breaking bit-identical to the tracker), tag every row with its
    (camera, frame), and pad rows to a power of two so jit shapes stay
    bounded — padded rows carry cam/frame -1 and rank to (NEG_INF, -1)
    inside the kernels.  ``min_rows`` lets the engines hold the row count at
    its high-water mark (growth-only padding, so the jitted rank signature
    stays frozen when a round's gallery shrinks — padded rows can never win
    a tie, the kernel's flat argmin always resolves equal scores to the
    lowest real column).  The rows are written in place into ``out`` (a
    fresh ``RoundStaging`` when None), whose padding rows past the real
    ones come back zero with every tag -1.  Returns views of ``out``:
    (gallery (Gp, D), gal_cam (Gp,), gal_frame (Gp,))."""
    out = RoundStaging() if out is None else out
    counts = [len(key_emb[k]) for k in batch_keys]
    G = sum(counts)
    Gp = max(pow2(G), pow2(min_rows))
    out.reserve_gallery(Gp, key_emb[batch_keys[0]].shape[1])
    np.concatenate([key_emb[k] for k in batch_keys], out=out.gal[:G])
    out.cam[:G] = np.repeat([k[0] for k in batch_keys], counts)
    out.frame[:G] = np.repeat([k[1] for k in batch_keys], counts)
    if out.rows > G:
        stale = slice(G, out.rows)
        out.gal[stale] = 0.0
        for tag in (out.cam, out.frame, out.seg, out.ct):
            tag[stale] = -1
    out.rows = G
    return out.gal[:Gp], out.cam[:Gp], out.frame[:Gp]
