"""Plan-driven chunk prefetcher + streamed executors (out-of-core serving).

``ChunkPrefetcher`` executes a ``core.scheduler.ChunkSchedule`` against a
fixed-budget device chunk cache:

* **budget** — the cache is ``num_slots`` shape-stable slots of
  ``chunk_rows`` feature rows; ``num_slots = budget_bytes // chunk_bytes``
  (min 1). A tile whose working set exceeds the cache is served in *waves*:
  each wave pins at most ``num_slots`` chunks, gathers its lanes into the
  tile's gather buffer by masked select, and hands the slots back — so any
  budget down to a single chunk completes, it just streams more bytes
  (thrashing is visible in telemetry, exactly the trade-off the
  ``bench_outofcore`` sweep measures).
* **reuse-distance eviction** — the schedule is known ahead of time, so
  eviction is Belady-optimal: the resident chunk with the farthest next use
  goes first.
* **overlapped staging** — with ``prefetch_depth > 0`` a background host
  worker runs an exact *shadow copy* of the cache state machine a few tiles
  ahead of the consumer, gathering upcoming chunks (and sparse row residues)
  and fencing their device copies off the critical path. The consumer takes
  staged copies by key; every copy carries wall-clock start/stop timestamps
  (``jax.block_until_ready`` fenced), so ``StreamStats.copy_ms`` is the true
  cost of the copies and ``stall_ms`` the time the consumer actually blocked
  — ``prefetch_overlap = 1 - stall/copy`` is measured, not inferred. Slot
  decisions are made by the deterministic host state machine alone, so
  outputs are bitwise-identical with staging on or off.
* **sparse residue** — a visit whose chunk loses the Belady comparison (its
  next use is farther than every resident chunk's) bypasses the cache: only
  the rows the tile actually gathers move, as a padded row block scattered
  into the gather buffer — same values as a full-chunk upload, a small
  fraction of the bytes. Thrashing budgets stop streaming whole chunks to
  serve a handful of lanes (the reddit 1/8-budget pathology).

Bitwise contract: the streamed executors reproduce the in-memory engine
paths bit for bit. Gathered rows are exact copies of the dense rows (f32
chunks are row slices; int8 chunks match ``quantization.quantize`` under the
store's aggregation scale), tiles execute with the same per-tile op sequence
as the ``aggregate_edge_tiles`` scan body, and the schedule's reordering
permutes whole runs only, preserving every output row's scatter-add order
(see ``scheduler.tile_runs``). The FTE stream exploits exactness instead:
int8 matmuls accumulate in int32 (associativity-free), so chunk-blocked
execution equals the monolithic matmul, while the small float-protected
block is gathered and transformed in one piece.
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from functools import partial
from typing import (
    Callable,
    Dict,
    FrozenSet,
    List,
    Mapping,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scheduler as sched
from repro.core.quantization import INT8_MAX, QuantParams
from repro.core.transformation import transform_dense
from repro.memory.feature_store import FeatureStore
from repro.observe import trace as otrace

__all__ = [
    "StreamStats",
    "StreamedFeatures",
    "DeviceTileStream",
    "make_device_tile_stream",
    "ChunkPrefetcher",
    "aggregate_streamed",
    "transform_streamed",
    "scale_add_streamed",
]

_INF = np.iinfo(np.int64).max


class DeviceTileStream(NamedTuple):
    """Device-resident per-tile plan arrays for the streamed executor.

    The instruction stream of one (plan, chunking) pair: coefficient /
    segment / scatter arrays plus the within-chunk lane offsets, uploaded
    once and indexed per tile on device. An engine caches one of these per
    (mode, tag, chunk_rows, reorder), so warm streamed requests move feature
    chunks only — zero plan bytes (regression-tested via
    ``StreamStats.instr_bytes``).
    """

    coeff: jnp.ndarray  # f32[T, E]
    seg_ids: jnp.ndarray  # int32[T, E]
    out_node: jnp.ndarray  # int32[T, S]
    lane_off: jnp.ndarray  # int32[T, E] row offset within the lane's chunk
    nbytes: int  # host->device bytes the upload cost (charged once, by owner)


def make_device_tile_stream(
    plan: "sched.EdgeTilePlan", schedule: "sched.ChunkSchedule"
) -> DeviceTileStream:
    """Upload one plan's tile arrays (+ the schedule's lane offsets)."""
    nbytes = (
        plan.coeff.nbytes
        + plan.seg_ids.nbytes
        + plan.out_node.nbytes
        + schedule.lane_off.nbytes
    )
    return DeviceTileStream(
        coeff=jnp.asarray(plan.coeff, jnp.float32),
        seg_ids=jnp.asarray(plan.seg_ids, jnp.int32),
        out_node=jnp.asarray(plan.out_node, jnp.int32),
        lane_off=jnp.asarray(schedule.lane_off, jnp.int32),
        nbytes=int(nbytes),
    )


@dataclasses.dataclass
class StreamStats:
    """Telemetry of one (or several merged) streamed executions.

    ``accesses = chunk_hits + chunk_misses`` counts tile→chunk visits;
    ``uploads = chunk_misses + prefetched`` counts non-hit servings (full
    chunk copies plus sparse-residue visits; a prefetched chunk's later
    visit is a hit). ``stall_ms``/``copy_ms`` are wall-clock: every feature
    copy is timestamped and device-fenced, and ``stall_ms`` accumulates only
    the time the consuming thread actually blocked, so
    ``prefetch_overlap = 1 - stall/copy`` reports how much of the copy cost
    was hidden behind compute. Both stay 0 on the synchronous path
    (``prefetch_depth == 0`` or ``async_stage=False``), where no overlap
    claim is made.
    """

    bytes_streamed: int = 0  # feature bytes moved host->device
    instr_bytes: int = 0  # per-tile plan arrays (the instruction stream)
    chunk_hits: int = 0
    chunk_misses: int = 0  # demand servings (visit found chunk absent)
    prefetched: int = 0  # uploads issued ahead of their first visit
    evictions: int = 0
    waves: int = 0
    tiles: int = 0
    fallbacks: int = 0  # dense materializations (budget violated, loud)
    fallback_bytes: int = 0
    sparse_rows: int = 0  # rows served as sparse residue (cache bypassed)
    stall_ms: float = 0.0  # consumer wall time blocked on feature copies
    copy_ms: float = 0.0  # wall time of the copies themselves (fenced)

    @property
    def accesses(self) -> int:
        return self.chunk_hits + self.chunk_misses

    @property
    def uploads(self) -> int:
        return self.chunk_misses + self.prefetched

    @property
    def hit_rate(self) -> float:
        return self.chunk_hits / self.accesses if self.accesses else 0.0

    @property
    def prefetch_overlap(self) -> float:
        """Wall-clock fraction of copy time hidden behind compute."""
        if self.copy_ms <= 0.0:
            return 0.0
        return min(max(1.0 - self.stall_ms / self.copy_ms, 0.0), 1.0)

    def merge(self, other: "StreamStats") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name, getattr(self, f.name) + getattr(other, f.name))

    def as_dict(self) -> Dict[str, float]:
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        d["hit_rate"] = self.hit_rate
        d["prefetch_overlap"] = self.prefetch_overlap
        return d


class StreamedFeatures:
    """Handle standing in for a dense feature matrix on the streamed path.

    Carries the host store, the device feature budget and the telemetry the
    serving layer reads back. The engine's ``aggregate``/``transform`` accept
    it wherever they accept a dense array; arithmetic consumers use
    :func:`scale_add_streamed`.
    """

    def __init__(
        self,
        store: FeatureStore,
        budget_bytes: int,
        *,
        prefetch_depth: int = 1,
        reorder: bool = True,
        packing: bool = False,
        async_stage: bool = True,
    ):
        self.store = store
        self.budget_bytes = int(budget_bytes)
        self.prefetch_depth = int(prefetch_depth)
        self.reorder = bool(reorder)
        # packing: serve through chunk-packed tile plans
        # (scheduler.pack_tiles_by_chunk) instead of only reordering runs.
        self.packing = bool(packing)
        # async_stage: overlap host gathers/uploads with compute via the
        # staging worker (wall-clock stall/copy telemetry); False keeps the
        # fully synchronous path (same outputs bit for bit).
        self.async_stage = bool(async_stage)
        self.stats = StreamStats()
        # Per-request correlation id (observe.trace): stamped by the serving
        # engine before the forward pass, read by the prefetchers so every
        # copy/stall span carries the request it served.
        self.trace_id = ""

    @property
    def shape(self) -> Tuple[int, int]:
        return self.store.shape

    @property
    def ndim(self) -> int:
        return 2

    @property
    def nbytes(self) -> int:
        return self.store.nbytes

    def agg_qp(self) -> QuantParams:
        """The aggregation-stream QuantParams — bitwise-equal to
        ``compute_scale_zp(dense_x, symmetric=True)``."""
        scale = jnp.asarray(self.store.agg_scale, jnp.float32)
        return QuantParams(scale=scale, zero_point=jnp.zeros_like(scale))


# --------------------------------------------------------------- device ops
@partial(jax.jit, donate_argnums=(0,))
def _upload_slot(buf: jnp.ndarray, chunk: jnp.ndarray, slot: jnp.ndarray) -> jnp.ndarray:
    return jax.lax.dynamic_update_slice(buf, chunk[None], (slot, 0, 0))


@partial(jax.jit, donate_argnums=(0,))
def _gather_wave(
    gathered: jnp.ndarray,
    buf: jnp.ndarray,
    slot_idx: jnp.ndarray,
    off: jnp.ndarray,
    mask: jnp.ndarray,
) -> jnp.ndarray:
    rows = buf[slot_idx, off]
    return jnp.where(mask[:, None], rows, gathered)


@partial(jax.jit, static_argnames=("segments_per_tile",), donate_argnums=(0,))
def _tile_step_f32(
    out: jnp.ndarray,
    gathered: jnp.ndarray,
    coeff: jnp.ndarray,
    seg_ids: jnp.ndarray,
    out_node: jnp.ndarray,
    *,
    segments_per_tile: int,
) -> jnp.ndarray:
    partial_sums = jax.ops.segment_sum(
        gathered * coeff[:, None], seg_ids, num_segments=segments_per_tile
    )
    return out.at[out_node].add(partial_sums)


@partial(jax.jit, static_argnames=("segments_per_tile",), donate_argnums=(0,))
def _tile_step_i8(
    out: jnp.ndarray,
    gathered_q: jnp.ndarray,
    scale: jnp.ndarray,
    zero_point: jnp.ndarray,
    coeff: jnp.ndarray,
    seg_ids: jnp.ndarray,
    out_node: jnp.ndarray,
    *,
    segments_per_tile: int,
) -> jnp.ndarray:
    # On-chip dequant after the 1-byte gather — same elementwise chain as the
    # in-memory path's whole-matrix dequantize followed by gather.
    gathered = ((gathered_q.astype(jnp.float32) - zero_point) * scale).astype(
        jnp.float32
    )
    partial_sums = jax.ops.segment_sum(
        gathered * coeff[:, None], seg_ids, num_segments=segments_per_tile
    )
    return out.at[out_node].add(partial_sums)


@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(
    gathered: jnp.ndarray, rows: jnp.ndarray, lanes: jnp.ndarray
) -> jnp.ndarray:
    # Sparse residue: rows gathered host-side land directly on their lanes.
    # Padding entries carry an out-of-bounds lane index and are dropped.
    return gathered.at[lanes].set(rows, mode="drop")


# --------------------------------------------------------- cache state model
class _TileMoves(NamedTuple):
    hits: Tuple[int, ...]  # chunks already resident (pinned for the wave)
    uploads: Tuple[Tuple[int, int], ...]  # (chunk, slot) admitted this tile
    sparse: Tuple[int, ...]  # chunks served as row residue (not admitted)


class _CacheState:
    """Pure host model of the chunk cache: slot map + Belady cursors.

    Every decision is a deterministic function of (schedule, visit order),
    which is what makes staging exact: the worker advances a ``clone()`` of
    this state a few tiles ahead and the real execution replays the same
    moves. It is also why staged and unstaged runs are bitwise-identical —
    slot assignment never depends on timing.
    """

    __slots__ = (
        "num_slots", "positions", "cursor", "slot_of", "chunk_in", "free",
        "evictions",
    )

    def __init__(self, num_slots: int, positions: Dict[int, np.ndarray]):
        self.num_slots = int(num_slots)
        self.positions = positions  # shared, read-only
        self.cursor = {c: 0 for c in positions}
        self.slot_of: Dict[int, int] = {}
        self.chunk_in: List[int] = [-1] * self.num_slots
        self.free: List[int] = list(range(self.num_slots))
        self.evictions = 0

    def clone(self) -> "_CacheState":
        st = object.__new__(_CacheState)
        st.num_slots = self.num_slots
        st.positions = self.positions
        st.cursor = dict(self.cursor)
        st.slot_of = dict(self.slot_of)
        st.chunk_in = list(self.chunk_in)
        st.free = list(self.free)
        st.evictions = self.evictions
        return st

    def next_use(self, c: int) -> int:
        p = self.positions.get(c)
        if p is None:
            return _INF
        k = self.cursor[c]
        return int(p[k]) if k < p.size else _INF

    def _next_use_after(self, c: int) -> int:
        """Next visit position strictly after the one being served now."""
        p = self.positions.get(c)
        if p is None:
            return _INF
        k = self.cursor[c] + 1
        return int(p[k]) if k < p.size else _INF

    def _evict(self, pinned: set, *, min_use: int) -> Optional[int]:
        """Free the resident chunk with the farthest next use (Belady).

        A victim is taken only when its next use is strictly beyond
        ``min_use`` — callers pass the incoming chunk's next use, so an
        admission never displaces hotter data. Returns None when no
        admissible victim exists.
        """
        victim, victim_use = -1, min_use
        for slot, c in enumerate(self.chunk_in):
            if c < 0 or c in pinned:
                continue
            use = self.next_use(c)
            if use > victim_use:
                victim, victim_use = slot, use
        if victim < 0:
            return None
        del self.slot_of[self.chunk_in[victim]]
        self.chunk_in[victim] = -1
        self.evictions += 1
        return victim

    def _admit(self, c: int, slot: int) -> None:
        self.slot_of[c] = slot
        self.chunk_in[slot] = c

    def decide_tile(self, chunks: Sequence[int]) -> _TileMoves:
        """Serve one tile's chunk visits; commits slot/cursor state.

        Missing chunks are admitted into free slots, else over a Belady
        victim whose next use is strictly beyond the chunk's *own* next use
        after this visit (true Belady: if the incoming chunk is the
        farthest-future of all, admitting it would be the wrong eviction) —
        losers are served as sparse residue instead of thrashing a slot.
        """
        hits: List[int] = []
        uploads: List[Tuple[int, int]] = []
        sparse: List[int] = []
        pinned: set = set()
        for c in chunks:
            c = int(c)
            if c in self.slot_of:
                hits.append(c)
                pinned.add(c)
        for c in chunks:
            c = int(c)
            if c in pinned:
                continue
            if self.free:
                slot: Optional[int] = self.free.pop()
            else:
                slot = self._evict(pinned, min_use=self._next_use_after(c))
            if slot is None:
                sparse.append(c)
            else:
                self._admit(c, slot)
                uploads.append((c, slot))
                pinned.add(c)
        for c in chunks:
            c = int(c)
            if c in self.cursor:
                self.cursor[c] += 1
        return _TileMoves(tuple(hits), tuple(uploads), tuple(sparse))

    def prefetch_moves(
        self,
        pos: int,
        order: np.ndarray,
        tile_chunks: Sequence[np.ndarray],
        depth: int,
    ) -> List[Tuple[int, int]]:
        """Admissions for the next ``depth`` tiles' chunks; commits state.

        Free slots first, else a Belady-conditional eviction (victim's next
        use strictly beyond the prefetched chunk's); stops at the first
        chunk no slot will take.
        """
        moves: List[Tuple[int, int]] = []
        if depth <= 0:
            return moves
        for p in range(pos + 1, min(pos + 1 + depth, order.size)):
            for c in tile_chunks[int(order[p])]:
                c = int(c)
                if c in self.slot_of:
                    continue
                if self.free:
                    slot: Optional[int] = self.free.pop()
                else:
                    slot = self._evict(set(), min_use=self.next_use(c))
                    if slot is None:
                        return moves
                self._admit(c, slot)
                moves.append((c, slot))
        return moves


# ------------------------------------------------------------ staging worker
class _StagedItem:
    __slots__ = ("event", "value", "error", "build_ms")

    def __init__(self):
        self.event = threading.Event()
        self.value = None
        self.error: Optional[BaseException] = None
        self.build_ms = 0.0


class _StageWorker:
    """Background host thread building keyed feature copies ahead of use.

    Each request's build (host gather + device put) is timed and fenced
    with ``jax.block_until_ready`` inside the worker, so a consumed item's
    ``build_ms`` is the true wall cost of that copy and the consumer's
    event wait is the true stall. Items are one-shot: ``take`` removes the
    key, so a chunk uploaded, evicted, and staged again later gets a fresh
    build. A build that raises hands its exception to the consumer, which
    re-raises it from ``take``: a failed host gather or device put is an
    error of the request, not a reason to rebuild the copy inline.
    """

    def __init__(self, build_fn: Callable[[tuple], object]):
        self._build = build_fn
        self._q: "queue.SimpleQueue" = queue.SimpleQueue()
        self._items: Dict[tuple, _StagedItem] = {}
        self._lock = threading.Lock()
        self._dead = False
        self._thread = threading.Thread(
            target=self._run, name="chunk-stage", daemon=True
        )
        self._thread.start()

    @property
    def outstanding(self) -> int:
        with self._lock:
            return len(self._items)

    def request(self, key: tuple) -> bool:
        """Enqueue a build for ``key``; False if already staged/in flight."""
        with self._lock:
            if key in self._items:
                return False
            self._items[key] = _StagedItem()
        self._q.put(key)
        return True

    def take(self, key: tuple):
        """Blocking claim: ``(value, build_ms, wait_ms)`` or None.

        None means nothing was staged under ``key`` (or the worker stopped
        before building it); a build that raised re-raises here."""
        with self._lock:
            item = self._items.get(key)
        if item is None:
            return None
        t0 = time.perf_counter()
        item.event.wait()
        wait_ms = (time.perf_counter() - t0) * 1e3
        with self._lock:
            self._items.pop(key, None)
        if item.error is not None:
            raise item.error
        if item.value is None:
            return None
        return item.value, item.build_ms, wait_ms

    def _run(self) -> None:
        while True:
            key = self._q.get()
            if key is None:
                return
            with self._lock:
                item = self._items.get(key)
            if item is None or self._dead:
                if item is not None:
                    item.event.set()
                continue
            t0 = time.perf_counter()
            try:
                item.value = self._build(key)
            except Exception as e:  # re-raised by the consumer's take()
                item.error = e
            item.build_ms = (time.perf_counter() - t0) * 1e3
            item.event.set()

    def stop(self) -> None:
        self._dead = True
        self._q.put(None)
        self._thread.join()


# ------------------------------------------------------------- chunk cache
class ChunkPrefetcher:
    """Fixed-budget device chunk cache executing one plan stream.

    One instance serves one precision stream of one aggregation call; the
    float and int8 streams run sequentially, so each gets the full budget.
    ``stream`` selects the representation: ``"f32"`` gathers raw rows,
    ``"i8"`` gathers 1-byte rows quantized under the store's agg scale.
    """

    def __init__(
        self,
        store: FeatureStore,
        schedule: sched.ChunkSchedule,
        *,
        stream: str,
        budget_bytes: int,
        prefetch_depth: int = 1,
        stats: Optional[StreamStats] = None,
        quant_scale=None,
        tiles: Optional[DeviceTileStream] = None,
        async_stage: bool = True,
        trace_id: str = "",
    ):
        if schedule.chunk_rows != store.chunk_rows:
            raise ValueError(
                f"schedule chunk_rows {schedule.chunk_rows} != store "
                f"{store.chunk_rows}"
            )
        if stream not in ("f32", "i8"):
            raise ValueError(f"unknown stream {stream!r}")
        self.store = store
        self.schedule = schedule
        self.stream = stream
        # The int8 stream must be quantized under the SAME scale it is later
        # dequantized with. A warm engine's static slot calibration may carry
        # an earlier request's scale; when it differs from this store's own,
        # chunks are re-quantized host-side on upload (bitwise-equal to
        # quantize(x, slot_qp) on the dense matrix) instead of using the
        # store's precomputed int8 representation.
        self.quant_scale = (
            np.float32(store.agg_scale) if quant_scale is None else np.float32(quant_scale)
        )
        self.prefetch_depth = max(int(prefetch_depth), 0)
        self.async_stage = bool(async_stage)
        self.stats = stats if stats is not None else StreamStats()
        self.trace_id = trace_id  # request id stamped on copy/stall spans
        # Device-cached instruction stream (owner charged its upload once);
        # None = upload per-tile plan slices per call (the uncached path,
        # used by direct ChunkPrefetcher construction).
        self.tiles = tiles
        self.chunk_bytes = (
            store.chunk_bytes_f32 if stream == "f32" else store.chunk_bytes_i8
        )
        slots = max(int(budget_bytes) // self.chunk_bytes, 1)
        self.num_slots = int(min(slots, max(schedule.num_chunks, 1)))
        dtype = jnp.float32 if stream == "f32" else jnp.int8
        self._buf = jnp.zeros(
            (self.num_slots, store.chunk_rows, store.dim), dtype
        )
        # Belady bookkeeping: per-chunk sorted visit positions + a cursor,
        # held by the deterministic cache state machine (the staging worker
        # simulates a clone of it a few tiles ahead).
        positions: Dict[int, List[int]] = {}
        for pos, t in enumerate(schedule.order):
            for c in schedule.tile_chunks[int(t)]:
                positions.setdefault(int(c), []).append(pos)
        self._state = _CacheState(
            self.num_slots,
            {c: np.asarray(p, np.int64) for c, p in positions.items()},
        )
        self._worker: Optional[_StageWorker] = None
        # Predicted sparse chunk set per schedule position (written by the
        # shadow pass, read by the worker's build and validated on consume).
        self._sparse_sets: Dict[int, FrozenSet[int]] = {}

    # -------------------------------------------------------------- metrics
    def stats_dict(self) -> Dict[str, float]:
        """Telemetry snapshot (counters + wall-clock stall/copy/overlap)."""
        return self.stats.as_dict()

    # ------------------------------------------------------------ plumbing
    def _host_chunk(self, c: int) -> np.ndarray:
        if self.stream == "f32":
            return self.store.chunk_f32(c)
        if self.quant_scale == self.store.agg_scale:
            return self.store.chunk_i8(c)  # precomputed under the same scale
        return FeatureStore._quantize_block(self.store.chunk_f32(c), self.quant_scale)

    def _host_rows(self, c: int, offs: np.ndarray) -> np.ndarray:
        """Row gather from one chunk in the stream's representation —
        bitwise the rows a full-chunk upload would have served (the int8
        re-quantization is elementwise, so a row subset quantizes
        identically to the same rows of the whole chunk)."""
        if self.stream == "f32":
            return self.store.chunk_f32(c)[offs]
        if self.quant_scale == self.store.agg_scale:
            return self.store.chunk_i8(c)[offs]
        return FeatureStore._quantize_block(
            self.store.chunk_f32(c)[offs], self.quant_scale
        )

    def _host_sparse(self, t: int, chunks: FrozenSet[int]):
        """Stage one tile's sparse residue: (lanes, rows, real row count).

        Gathers exactly the lanes whose source chunk was not admitted, pads
        the row count to a power-of-two bucket (stable device shapes) with
        out-of-bounds lane indices that the scatter drops, and fences the
        device copies so the caller's timestamps bound the true copy cost.
        """
        lane_chunk = self.schedule.lane_chunk[t]
        lane_off = self.schedule.lane_off[t]
        cs = np.fromiter(chunks, np.int64, len(chunks))
        sel = np.flatnonzero(np.isin(lane_chunk, cs))
        k = int(sel.size)
        kp = 1 << max(k - 1, 0).bit_length() if k else 1
        dtype = np.float32 if self.stream == "f32" else np.int8
        rows = np.zeros((kp, self.store.dim), dtype)
        sel_chunk = lane_chunk[sel]
        for c in sorted(chunks):
            m = np.flatnonzero(sel_chunk == c)
            if m.size:
                rows[m] = self._host_rows(int(c), lane_off[sel[m]])
        lanes = np.full(kp, lane_chunk.size, np.int32)  # OOB pad -> dropped
        lanes[:k] = sel
        staged = (jnp.asarray(lanes), jnp.asarray(rows), k)
        jax.block_until_ready(staged[:2])
        return staged

    def _build_staged(self, key: tuple):
        """Worker-side build: fenced device copies keyed like the consumer
        will claim them. The copy span is recorded here, on the staging
        thread's own timeline (lane "copy"), at the stamps the copy really
        occupied — which is what lets an exported trace show copies
        overlapping the consumer's compute."""
        t0 = time.perf_counter()
        if key[0] == "chunk":
            val = jax.block_until_ready(jnp.asarray(self._host_chunk(key[1])))
            name = "copy:chunk"
        else:
            _, pos, t = key
            val = self._host_sparse(t, self._sparse_sets.get(pos, frozenset()))
            name = "copy:rows"
        rec = otrace.get_recorder()
        if rec.enabled:
            rec.add_span(
                name, t0, time.perf_counter(), cat="stream", lane="copy",
                trace_id=self.trace_id, args={"stream": self.stream},
            )
        return val

    def _upload(self, c: int, slot: int, *, prefetch: bool) -> None:
        """Device copy of one admitted chunk (slot already committed by the
        state machine). Staged copies are claimed by key; unstaged ones are
        built inline and count fully as stall (the consumer blocked for the
        whole copy)."""
        rec = otrace.get_recorder()
        staged = (
            self._worker.take(("chunk", c)) if self._worker is not None else None
        )
        if staged is not None:
            dev, build_ms, wait_ms = staged
            self.stats.copy_ms += build_ms
            self.stats.stall_ms += wait_ms
            if rec.enabled and wait_ms > 0.0:
                # The wait just ended: reconstruct [t1 - wait, t1] from the
                # same measurement stall_ms accumulated.
                t1 = time.perf_counter()
                rec.add_span("stall", t1 - wait_ms / 1e3, t1, cat="stream",
                             trace_id=self.trace_id, args={"chunk": int(c)})
        elif self._worker is not None:
            t0 = time.perf_counter()
            dev = jax.block_until_ready(jnp.asarray(self._host_chunk(c)))
            t1 = time.perf_counter()
            dt = (t1 - t0) * 1e3
            self.stats.copy_ms += dt
            self.stats.stall_ms += dt
            if rec.enabled:
                # Unstaged inline build: one interval is both the copy and
                # the stall (the consumer blocked for the whole copy). Its
                # copy span gets its own lane — the staging thread may be
                # mid-copy on "copy" at the same instant.
                rec.add_span("copy:chunk", t0, t1, cat="stream",
                             lane="copy-inline", trace_id=self.trace_id,
                             args={"stream": self.stream, "inline": True})
                rec.add_span("stall", t0, t1, cat="stream",
                             trace_id=self.trace_id, args={"chunk": int(c)})
        else:  # synchronous path: untimed, no overlap claim
            dev = jnp.asarray(self._host_chunk(c))
        self._buf = _upload_slot(self._buf, dev, jnp.int32(slot))
        self.stats.bytes_streamed += self.chunk_bytes
        if prefetch:
            self.stats.prefetched += 1
        else:
            self.stats.chunk_misses += 1

    def _sparse_pass(
        self, pos: int, t: int, sparse: Tuple[int, ...], gathered: jnp.ndarray
    ) -> jnp.ndarray:
        """Scatter the tile's non-admitted chunks' rows onto their lanes."""
        chunks = frozenset(sparse)
        rec = otrace.get_recorder()
        staged = None
        if self._worker is not None and self._sparse_sets.get(pos) == chunks:
            staged = self._worker.take(("rows", pos, t))
        if staged is not None:
            (lanes_dev, rows_dev, k), build_ms, wait_ms = staged
            self.stats.copy_ms += build_ms
            self.stats.stall_ms += wait_ms
            if rec.enabled and wait_ms > 0.0:
                t1 = time.perf_counter()
                rec.add_span("stall", t1 - wait_ms / 1e3, t1, cat="stream",
                             trace_id=self.trace_id, args={"tile": int(t)})
        elif self._worker is not None:
            t0 = time.perf_counter()
            lanes_dev, rows_dev, k = self._host_sparse(t, chunks)
            t1 = time.perf_counter()
            dt = (t1 - t0) * 1e3
            self.stats.copy_ms += dt
            self.stats.stall_ms += dt
            if rec.enabled:
                rec.add_span("copy:rows", t0, t1, cat="stream",
                             lane="copy-inline", trace_id=self.trace_id,
                             args={"stream": self.stream, "inline": True})
                rec.add_span("stall", t0, t1, cat="stream",
                             trace_id=self.trace_id, args={"tile": int(t)})
        else:
            lanes_dev, rows_dev, k = self._host_sparse(t, chunks)
        self.stats.bytes_streamed += int(rows_dev.nbytes)
        self.stats.sparse_rows += k
        self.stats.chunk_misses += len(sparse)
        return _scatter_rows(gathered, rows_dev, lanes_dev)

    def _stage_ahead(
        self, shadow: _CacheState, shadow_pos: int, pos: int
    ) -> int:
        """Advance the shadow state machine so tiles up to ``pos + depth``
        have their demand uploads, prefetches and sparse residues staged.
        The shadow replays exactly the decisions the real state will make
        (both are deterministic), so every request key matches a future
        consume. Pauses when too many items are outstanding."""
        order = self.schedule.order
        cap = 2 * (self.prefetch_depth + 1) + self.num_slots + 8
        while shadow_pos < order.size and shadow_pos <= pos + self.prefetch_depth:
            if self._worker.outstanding >= cap:
                break
            t = int(order[shadow_pos])
            mv = shadow.decide_tile(self.schedule.tile_chunks[t])
            for c, _slot in mv.uploads:
                self._worker.request(("chunk", c))
            if mv.sparse:
                self._sparse_sets[shadow_pos] = frozenset(mv.sparse)
                self._worker.request(("rows", shadow_pos, t))
            for c, _slot in shadow.prefetch_moves(
                shadow_pos, order, self.schedule.tile_chunks, self.prefetch_depth
            ):
                self._worker.request(("chunk", c))
            shadow_pos += 1
        return shadow_pos

    # ----------------------------------------------------------- execution
    def aggregate(
        self,
        plan: sched.EdgeTilePlan,
        *,
        qp: Optional[QuantParams] = None,
    ) -> jnp.ndarray:
        """Stream one plan's tiles through the cache; returns f32[N, D].

        Bitwise-identical to ``aggregate_edge_tiles`` on the dense matrix
        (f32 stream) / on the dequantized matrix (i8 stream): same gathered
        values (resident chunks by masked select, sparse residues by row
        scatter onto disjoint lanes), same per-tile op sequence, per-row
        scatter order preserved by the run-respecting schedule. Staging
        changes when copies happen, never what the device computes.
        """
        if self.stream == "i8" and qp is None:
            raise ValueError("int8 stream needs the aggregation QuantParams")
        S = plan.segments_per_tile
        n = plan.num_nodes
        lanes = plan.gather_idx.shape[1]
        out = jnp.zeros((n + 1, self.store.dim), jnp.float32)
        lane_bytes = plan.gather_idx[0].nbytes + plan.coeff[0].nbytes + (
            plan.seg_ids[0].nbytes + plan.out_node[0].nbytes
        )
        order = self.schedule.order
        state = self._state
        shadow: Optional[_CacheState] = None
        shadow_pos = 0
        if self.async_stage and self.prefetch_depth > 0 and order.size > 1:
            self._worker = _StageWorker(self._build_staged)
            shadow = state.clone()
        rec = otrace.get_recorder()
        agg_t0 = time.perf_counter() if rec.enabled else 0.0
        try:
            for pos, t in enumerate(order):
                t = int(t)
                if shadow is not None:
                    shadow_pos = self._stage_ahead(shadow, shadow_pos, pos)
                # (chunk, offset) lane splits are plan-static — precomputed
                # on the schedule at plan time, not re-derived per request.
                lane_chunk = self.schedule.lane_chunk[t]
                lane_off = (
                    self.tiles.lane_off[t]
                    if self.tiles is not None
                    else jnp.asarray(self.schedule.lane_off[t], jnp.int32)
                )
                gathered = jnp.zeros(
                    (lanes,) + (self.store.dim,),
                    jnp.float32 if self.stream == "f32" else jnp.int8,
                )
                self.stats.tiles += 1
                ev0 = state.evictions
                moves = state.decide_tile(self.schedule.tile_chunks[t])
                self.stats.evictions += state.evictions - ev0
                self.stats.chunk_hits += len(moves.hits)
                for c, slot in moves.uploads:
                    self._upload(c, slot, prefetch=False)
                wave = moves.hits + tuple(c for c, _ in moves.uploads)
                if wave:
                    slot_lut = np.zeros(self.schedule.num_chunks, np.int32)
                    in_wave = np.zeros(self.schedule.num_chunks, bool)
                    for c in wave:
                        slot_lut[c] = state.slot_of[c]
                        in_wave[c] = True
                    mask = in_wave[lane_chunk]
                    slot_idx = jnp.asarray(slot_lut[lane_chunk], jnp.int32)
                    gathered = _gather_wave(
                        gathered, self._buf, slot_idx, lane_off, jnp.asarray(mask)
                    )
                    self.stats.waves += 1
                if moves.sparse:
                    gathered = self._sparse_pass(pos, t, moves.sparse, gathered)
                if self.tiles is not None:
                    # Device-resident instruction stream: indexing a cached
                    # array is a device-side slice, not an upload — warm
                    # requests move zero plan bytes.
                    coeff = self.tiles.coeff[t]
                    seg_ids = self.tiles.seg_ids[t]
                    out_node = self.tiles.out_node[t]
                else:
                    coeff = jnp.asarray(plan.coeff[t])
                    seg_ids = jnp.asarray(plan.seg_ids[t])
                    out_node = jnp.asarray(plan.out_node[t])
                    self.stats.instr_bytes += lane_bytes
                if self.stream == "f32":
                    out = _tile_step_f32(
                        out, gathered, coeff, seg_ids, out_node,
                        segments_per_tile=S,
                    )
                else:
                    out = _tile_step_i8(
                        out, gathered, qp.scale, qp.zero_point, coeff, seg_ids,
                        out_node, segments_per_tile=S,
                    )
                ev0 = state.evictions
                for c, slot in state.prefetch_moves(
                    pos, order, self.schedule.tile_chunks, self.prefetch_depth
                ):
                    self._upload(c, slot, prefetch=True)
                self.stats.evictions += state.evictions - ev0
        finally:
            if self._worker is not None:
                self._worker.stop()
                self._worker = None
            if rec.enabled:
                rec.add_span(
                    f"stream:{self.stream}", agg_t0, time.perf_counter(),
                    cat="stream", trace_id=self.trace_id,
                    args={"tiles": int(order.size),
                          "staged": self.async_stage and self.prefetch_depth > 0},
                )
        return out[:n]


# -------------------------------------------------------- streamed executors
def aggregate_streamed(
    sf: StreamedFeatures,
    plans: Mapping[str, sched.EdgeTilePlan],
    schedules: Mapping[str, sched.ChunkSchedule],
    *,
    num_nodes: int,
    mixed: bool,
    qp: Optional[QuantParams] = None,
    tiles: Optional[Mapping[str, DeviceTileStream]] = None,
) -> jnp.ndarray:
    """Chunk-streamed mirror of the engine's aggregation dispatch.

    ``mixed`` replays ``aggregate_mixed_precision``'s combine order exactly
    (zeros + float stream + int8 stream); non-mixed returns the float stream
    alone, matching the engine's direct ``aggregate_edge_tiles`` call.
    ``tiles`` carries the caller's device-cached instruction streams per tag
    (warm requests then re-upload zero plan bytes).
    """
    for tag in plans:
        if tag not in ("float", "int8"):
            raise ValueError(f"unknown precision tag {tag!r}")

    def run(tag: str, stream: str, qp_: Optional[QuantParams]) -> jnp.ndarray:
        pf = ChunkPrefetcher(
            sf.store,
            schedules[tag],
            stream=stream,
            budget_bytes=sf.budget_bytes,
            prefetch_depth=sf.prefetch_depth,
            stats=sf.stats,
            quant_scale=(
                np.float32(np.asarray(qp_.scale)) if qp_ is not None else None
            ),
            tiles=tiles.get(tag) if tiles is not None else None,
            async_stage=sf.async_stage,
            trace_id=sf.trace_id,
        )
        return pf.aggregate(plans[tag], qp=qp_)

    if not mixed:
        return run("float", "f32", None)
    out = jnp.zeros((num_nodes, sf.store.dim), jnp.float32)
    if "float" in plans:
        out = out + run("float", "f32", None)
    if "int8" in plans:
        out = out + run("int8", "i8", qp if qp is not None else sf.agg_qp())
    return out


def _host_fte_qp(amax: np.float32) -> QuantParams:
    """Host mirror of ``compute_scale_zp(rows, symmetric=True)`` given the
    exact row-set amax (max never rounds, the scalar ops are IEEE-exact)."""
    scale = np.maximum(
        np.float32(amax / np.float32(INT8_MAX)), np.float32(1e-8)
    )
    scale_j = jnp.asarray(scale, jnp.float32)
    return QuantParams(scale=scale_j, zero_point=jnp.zeros_like(scale_j))


def transform_streamed(
    sf: StreamedFeatures,
    node_group_ids: Mapping[str, np.ndarray],
    w: jnp.ndarray,
    b: Optional[jnp.ndarray] = None,
    activation: Optional[Callable[[jnp.ndarray], jnp.ndarray]] = None,
    *,
    w_q: jnp.ndarray,
    w_qp: QuantParams,
    a_qp: Optional[QuantParams] = None,
) -> jnp.ndarray:
    """Mixed-precision FTE over stored features, bitwise-equal to
    ``transform_mixed_precision`` on the dense matrix.

    The float-protected block (a few % of nodes under Degree-Quant) is
    host-gathered and transformed in one matmul — identical shape and values
    to the in-memory group matmul. The int8 block streams chunk-blocked:
    rows are quantized host-side under ``a_qp`` and move as 1-byte elements,
    and the int8×int8→int32 matmul accumulates exactly, so per-chunk blocks
    equal the monolithic matmul row for row.
    """
    store = sf.store
    rec = otrace.get_recorder()
    fte_t0 = time.perf_counter() if rec.enabled else 0.0
    out = jnp.zeros((store.num_rows, w.shape[1]), jnp.float32)
    for tag, ids in node_group_ids.items():
        if ids.size == 0:
            continue
        ids = np.asarray(ids, np.int64)
        if tag == "float":
            rows = jnp.asarray(store.gather_rows_f32(ids))
            sf.stats.bytes_streamed += int(rows.size) * 4
            y = transform_dense(rows, w, b, activation)
            out = out.at[jnp.asarray(ids, jnp.int32)].set(y)
        elif tag == "int8":
            if a_qp is None:
                a_qp = _host_fte_qp(store.amax_rows(ids))
            scale_np = np.float32(np.asarray(a_qp.scale))
            # Same expression as transform_int8's dequant coefficient.
            deq = a_qp.scale * w_qp.scale.reshape(1, -1)
            chunk_of = np.unique(ids // store.chunk_rows)
            for c in chunk_of:
                _, local = store.chunk_row_selection(int(c), ids)
                if local.size == 0:
                    continue
                lo, hi = store.chunk_range(int(c))
                blk = store.chunk_f32(int(c))[: hi - lo]
                # Host quantize under the FTE scale (shared helper, bitwise
                # == quantization.quantize with zp=0); whole-chunk rows keep
                # the device shapes stable, non-group rows are computed and
                # discarded (matmul rows independent).
                hq = jnp.asarray(FeatureStore._quantize_block(blk, scale_np))
                sf.stats.bytes_streamed += int(hq.size)
                acc = jnp.dot(
                    hq.astype(jnp.int32),
                    w_q.astype(jnp.int32),
                    preferred_element_type=jnp.int32,
                )
                y = acc.astype(jnp.float32) * deq
                if b is not None:
                    y = y + b
                if activation is not None:
                    y = activation(y)
                out = out.at[jnp.asarray(lo + local, jnp.int32)].set(
                    y[jnp.asarray(local, jnp.int32)]
                )
        else:
            raise ValueError(f"unknown precision tag {tag!r}")
    if rec.enabled:
        rec.add_span(
            "stream:fte", fte_t0, time.perf_counter(), cat="stream",
            trace_id=sf.trace_id,
        )
    return out


def scale_add_streamed(
    sf: StreamedFeatures, alpha, m: jnp.ndarray
) -> jnp.ndarray:
    """Chunk-streamed ``alpha * x + m`` (GIN's aggregation-side residual).

    Elementwise per row, so chunk blocks concatenate to the exact dense
    result; streams the f32 representation once.
    """
    store = sf.store
    if m.shape[0] != store.num_rows:
        raise ValueError(
            f"residual rows {m.shape[0]} != store rows {store.num_rows}"
        )
    parts = []
    for c in range(store.num_chunks):
        lo, hi = store.chunk_range(c)
        blk = jnp.asarray(store.chunk_f32(c)[: hi - lo])
        sf.stats.bytes_streamed += int(blk.size) * 4
        parts.append(alpha * blk + m[lo:hi])
    return jnp.concatenate(parts, axis=0)
