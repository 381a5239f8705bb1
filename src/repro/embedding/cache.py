"""Host-offloaded, frequency-aware embedding cache (§4.3.1 regime).

TurboGR's sparse side assumes the fp32 master + bf16 shadow fit in device
HBM; production GR vocabularies (hundreds of millions of users/items) do
not. :class:`CachedShadowedTable` breaks that ceiling: the full table
lives in host RAM and the device holds only a *window* of hot row-chunks
— a plain :class:`~repro.embedding.tables.ShadowedTable` whose arrays are
logically ``(capacity_chunks, chunk_rows, D)`` flattened to
``(capacity_chunks * chunk_rows, D)``. Because the window *is* a
ShadowedTable, every existing consumer — the staged train-step functions,
the fused negative-sampling gather, :func:`repro.training.optim.
adagrad_sparse_update`, strip/rebuild-shadow checkpointing — runs on it
unchanged; the only new moving part is the id→slot translation performed
on the host where the batch already is.

Chunk manager (all host-side numpy, one lock):

  * id→chunk is ``id // chunk_rows``; chunk→slot / slot→chunk maps track
    residency (−1 = absent/free).
  * Admission and eviction are frequency-weighted LFU: per-chunk
    cumulative id-frequency counters, fed by the per-batch candidate
    counts the host ``unique`` stage already produces
    (:func:`repro.training.trainer.host_unique_candidates`), seeded by
    :meth:`warm_up` from an id-frequency histogram
    (:func:`repro.data.freq.batch_id_histogram`). Eviction picks the
    lowest-frequency *unpinned* resident chunk.
  * Chunks referenced by an in-flight batch are pinned from
    :meth:`prepare` until :meth:`release` (or, for a batch whose τ=1
    pairs are still pending, :meth:`defer_release` →
    :meth:`release_pending`), so a swap can never pull a row out from
    under an in-flight gather or a not-yet-landed sparse update.
  * Row-sparse AdaGrad is the only mutation and it touches gathered rows
    only, so writeback is naturally *row*-sparse and deferred to
    eviction: a released batch marks its chunks dirty and records which
    rows it actually touched (the unique candidate ids from
    :meth:`prepare`); evicting a dirty chunk copies only its touched
    window rows back to host RAM — untouched rows are bitwise equal to
    the host copy already, so skipping them changes writeback *bytes*,
    never the master state (`eviction never drops a dirty chunk` and the
    sparse-touch byte reduction are both property-tested). A chunk dirty
    without a recorded row set (e.g. after crash recovery) conservatively
    writes back whole.

Overlap: :meth:`prepare` runs inside the engine's host ``unique`` hook on
a worker thread — it stages the missing chunks' host rows as device
arrays (the H2D transfer dispatches asynchronously under the *previous*
batch's dense stages) and the cheap :meth:`splice` scatter lands them in
the ``emb_fwd`` hook, so on the Algorithm-1 schedule a cache miss costs
approximately zero wall time.

Bit-identity: translation only permutes *where* rows live; gathers and
the per-row AdaGrad arithmetic are row-local, so training math is
unchanged. With ``capacity_chunks >= num_chunks`` (and
``vocab % chunk_rows == 0``) the default warm-up admits every chunk at
slot == chunk and the window is *literally* the full table — the engine
then reproduces the all-resident ShadowedTable bit-for-bit
(tests/test_cache_embedding.py).
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.embedding import tables as ET


@dataclass
class CacheStats:
    """Cumulative counters (id-occurrence-weighted hits/misses)."""
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    writebacks: int = 0
    swap_in_bytes: int = 0
    swap_out_bytes: int = 0
    warmup_bytes: int = 0
    # row-sparse writeback accounting: rows actually copied D2H vs. the
    # rows a chunk-granular writeback would have copied
    writeback_rows_dirty: int = 0
    writeback_rows_total: int = 0

    @property
    def hit_rate(self) -> float:
        seen = self.hits + self.misses
        return self.hits / seen if seen else 0.0


class PrefetchPlan(NamedTuple):
    """Staged H2D payload for one batch's missing chunks: apply with
    :meth:`CachedShadowedTable.splice` (slots are window chunk-slots)."""
    slots: jax.Array                # (n,) int32
    master: jax.Array               # (n, chunk_rows, D) fp32
    accum: jax.Array                # (n, chunk_rows, D) fp32


class CacheThrash(RuntimeError):
    """A batch needs more chunks than capacity minus pinned chunks — the
    window is too small for the in-flight working set (shrink the batch,
    raise ``capacity_chunks``, or reduce the pipeline depth)."""


class CachedShadowedTable:
    """Host-resident full table + device-resident hot-chunk window.

    ``master`` is the full ``(V, D)`` fp32 table (numpy or jax; copied to
    host RAM). The device window is created by :meth:`init_window` after
    :meth:`warm_up` and updated in place through
    :meth:`prepare`/:meth:`splice`; :meth:`materialize` reassembles the
    full table (flushing dirty chunks from a window snapshot) for
    checkpointing.
    """

    def __init__(self, master, *, capacity_chunks: int,
                 chunk_rows: int = 1024, qdtype=ET.SHADOW_DTYPE,
                 accum=None):
        m = np.asarray(jax.device_get(master), np.float32)
        if m.ndim != 2:
            raise ValueError(f"master must be (V, D), got {m.shape}")
        if capacity_chunks < 1 or chunk_rows < 1:
            raise ValueError("capacity_chunks and chunk_rows must be >= 1")
        self.vocab, self.dim = int(m.shape[0]), int(m.shape[1])
        self.chunk_rows = int(chunk_rows)
        self.capacity_chunks = int(capacity_chunks)
        self.num_chunks = -(-self.vocab // self.chunk_rows)   # ceil
        self.qdtype = qdtype
        vpad = self.num_chunks * self.chunk_rows
        self.host_master = np.zeros((vpad, self.dim), np.float32)
        self.host_master[:self.vocab] = m
        self.host_accum = np.zeros((vpad, self.dim), np.float32)
        if accum is not None:
            self.host_accum[:self.vocab] = np.asarray(
                jax.device_get(accum), np.float32)
        self.chunk_slot = np.full(self.num_chunks, -1, np.int64)
        self.slot_chunk = np.full(self.capacity_chunks, -1, np.int64)
        self.freq = np.zeros(self.num_chunks, np.int64)
        self.dirty = np.zeros(self.num_chunks, bool)
        # chunk id → (chunk_rows,) bool mask of touched rows; present only
        # for dirty chunks with a recorded touch set
        self.dirty_rows: Dict[int, np.ndarray] = {}
        self.pins = np.zeros(self.num_chunks, np.int64)
        self.stats = CacheStats()
        self._batch_chunks: Dict[int, np.ndarray] = {}
        self._batch_rows: Dict[int, np.ndarray] = {}
        self._pending_chunks: Optional[np.ndarray] = None
        self._pending_rows: Optional[np.ndarray] = None
        self._window_ref: Optional[ET.ShadowedTable] = None
        self._lock = threading.Lock()

    # -- capacity accounting ------------------------------------------------
    @property
    def rows(self) -> int:
        """Device-resident row budget (window height)."""
        return self.capacity_chunks * self.chunk_rows

    # -- warm-up / window ---------------------------------------------------
    def warm_up(self, hist=None) -> np.ndarray:
        """Admit the ``capacity_chunks`` hottest chunks by histogram.

        ``hist`` is a ``(vocab,)`` id-frequency histogram (e.g. summed
        :func:`repro.data.freq.batch_id_histogram` over a prefix of the
        stream); its counts seed the LFU frequency counters. ``None``
        admits chunks in id order — with ``capacity_chunks >=
        num_chunks`` that is the identity chunk→slot mapping (the
        all-resident bit-identity configuration). Returns the admitted
        chunk ids. Must run before any window exists.
        """
        with self._lock:
            if self._window_ref is not None or self._batch_chunks:
                raise RuntimeError("warm_up must precede init_window/prepare")
            if hist is not None:
                h = np.zeros(self.num_chunks * self.chunk_rows, np.int64)
                h[:self.vocab] = np.asarray(hist, np.int64)[:self.vocab]
                self.freq += h.reshape(self.num_chunks,
                                       self.chunk_rows).sum(axis=1)
                # stable sort: ties admit in chunk-id order
                order = np.argsort(-self.freq, kind="stable")
            else:
                order = np.arange(self.num_chunks)
            admit = np.sort(order[:min(self.capacity_chunks,
                                       self.num_chunks)])
            self.chunk_slot[:] = -1
            self.slot_chunk[:] = -1
            self.chunk_slot[admit] = np.arange(admit.size)
            self.slot_chunk[:admit.size] = admit
            return admit

    def init_window(self) -> ET.ShadowedTable:
        """Build (and publish) the device window from current residency."""
        with self._lock:
            win = self._window_from_host_locked()
            self._window_ref = win
            return win

    def _window_from_host_locked(self) -> ET.ShadowedTable:
        R, D = self.chunk_rows, self.dim
        wm = np.zeros((self.capacity_chunks, R, D), np.float32)
        wa = np.zeros((self.capacity_chunks, R, D), np.float32)
        res = np.flatnonzero(self.chunk_slot >= 0)
        if res.size:
            slots = self.chunk_slot[res]
            wm[slots] = self.host_master.reshape(-1, R, D)[res]
            wa[slots] = self.host_accum.reshape(-1, R, D)[res]
            self.stats.warmup_bytes += int(wm[slots].nbytes * 2)
        master = jnp.asarray(wm.reshape(self.rows, D))
        accum = jnp.asarray(wa.reshape(self.rows, D))
        shadow = (None if self.qdtype is None
                  else ET.shadow_of(master, self.qdtype))
        return ET.ShadowedTable(master=master, shadow=shadow, accum=accum)

    def publish(self, window: ET.ShadowedTable) -> None:
        """Record the latest landed window — the array writebacks and
        :meth:`materialize` read dirty chunks from. The engine publishes
        after every table-changing dispatch (splice, sparse landings)."""
        with self._lock:
            self._window_ref = window

    # -- id translation -----------------------------------------------------
    def translate(self, ids) -> np.ndarray:
        """Global ids → window row ids (host-side, numpy).

        Ids are clamped to ``[0, vocab)`` first — exactly the clip-mode
        index handling ``jnp.take`` applies on device, so out-of-range
        and negative ids keep resolving to the same rows they already
        did. Every referenced chunk must be resident (call after
        :meth:`prepare` for the batch).
        """
        a = np.clip(np.asarray(ids, np.int64), 0, self.vocab - 1)
        slots = self.chunk_slot[a // self.chunk_rows]
        if (slots < 0).any():
            missing = np.unique(a[slots < 0] // self.chunk_rows)
            raise KeyError(f"non-resident chunks {missing.tolist()} — "
                           "prepare() the batch before translating")
        out = slots * self.chunk_rows + a % self.chunk_rows
        return out.astype(np.int32).reshape(np.shape(ids))

    def slotize_pending(self, pending_ids) -> np.ndarray:
        """:meth:`translate` preserving the −1 empty-pair sentinel."""
        p = np.asarray(pending_ids, np.int64)
        out = np.full(p.shape, -1, np.int32)
        live = p >= 0
        if live.any():
            out[live] = self.translate(p[live])
        return out

    def globalize_pending_pairs(self, slot_ids, rows
                                ) -> Tuple[np.ndarray, np.ndarray]:
        """Slot-space τ=1 pending pairs → the exact global-space layout
        an uncached run produces.

        The pending arrays hold the unique ids in ascending order, then
        −1 / zero-rows. Translation is order-preserving only *within* a
        chunk, so the slot-space order block-permutes the ids relative to
        the global-id order; this globalizes the ids and re-sorts them, so
        a cached checkpoint is bitwise identical to the uncached one — not
        merely equivalent up to permutation."""
        p = np.asarray(slot_ids, np.int64).reshape(-1)
        r = np.asarray(rows)
        live = np.flatnonzero(p >= 0)
        gids = self.globalize_pending(p[live])
        order = np.argsort(gids, kind="stable")
        out_ids = np.full(p.shape, -1, np.int32)
        out_rows = np.zeros_like(r)
        out_ids[:live.size] = gids[order]
        out_rows[:live.size] = r[live][order]
        return out_ids, out_rows

    def globalize_pending(self, slot_ids) -> np.ndarray:
        """Window row ids → global ids (−1 sentinel preserved)."""
        s = np.asarray(slot_ids, np.int64)
        out = np.full(s.shape, -1, np.int32)
        live = s >= 0
        if live.any():
            chunks = self.slot_chunk[s[live] // self.chunk_rows]
            if (chunks < 0).any():
                raise KeyError("slot id maps to a free slot")
            out[live] = (chunks * self.chunk_rows
                         + s[live] % self.chunk_rows).astype(np.int32)
        return out

    # -- per-batch protocol -------------------------------------------------
    def prepare(self, batch: int, uids, counts=None
                ) -> Tuple[Optional[PrefetchPlan], Dict[str, int]]:
        """Pin batch ``batch``'s chunks, swapping in the missing ones.

        ``uids`` are the batch's unique candidate ids (global, in-vocab —
        the host ``unique`` stage's output) and ``counts`` their
        per-batch multiplicities (LFU admission weight; default 1).
        Returns ``(plan, step_stats)``: the plan stages the missing
        chunks' host rows as device arrays (H2D dispatch starts here, on
        the worker thread) and must be landed with :meth:`splice` before
        the batch's first gather. Dirty eviction victims are written back
        to host RAM before their slot is reused.
        """
        uids = np.asarray(uids, np.int64).reshape(-1)
        w = (np.ones(uids.shape, np.int64) if counts is None
             else np.asarray(counts, np.int64).reshape(-1))
        cid = uids // self.chunk_rows
        chunks, inv = np.unique(cid, return_inverse=True)
        weight = np.zeros(chunks.size, np.int64)
        np.add.at(weight, inv, w)
        with self._lock:
            prev = self._batch_chunks.pop(batch, None)
            if prev is not None:            # stage retry: re-prepare
                self.pins[prev] -= 1
                self._batch_rows.pop(batch, None)
            self.freq[chunks] += weight
            resident = self.chunk_slot[chunks] >= 0
            hits = int(weight[resident].sum())
            misses = int(weight[~resident].sum())
            self.stats.hits += hits
            self.stats.misses += misses
            missing = chunks[~resident]
            plan = None
            evicted = swap_in = swap_out = 0
            # pin BEFORE assigning slots: the batch's hit chunks must not
            # be eviction victims for its own missing chunks
            self.pins[chunks] += 1
            self._batch_chunks[batch] = chunks
            # the rows the sparse update will touch — release() turns this
            # into the per-row dirty record the eviction writeback reads
            self._batch_rows[batch] = np.unique(uids)
            if missing.size:
                out0 = self.stats.swap_out_bytes
                try:
                    slots, evicted = self._assign_slots_locked(missing)
                except CacheThrash:
                    self.pins[chunks] -= 1      # unwind: nothing resident
                    del self._batch_chunks[batch]
                    raise
                swap_out = self.stats.swap_out_bytes - out0
                R, D = self.chunk_rows, self.dim
                rows_m = self.host_master.reshape(-1, R, D)[missing]
                rows_a = self.host_accum.reshape(-1, R, D)[missing]
                swap_in = int(rows_m.nbytes + rows_a.nbytes)
                if self.qdtype is not None:
                    swap_in += rows_m.size * jnp.dtype(self.qdtype).itemsize
                self.stats.swap_in_bytes += swap_in
                plan = PrefetchPlan(slots=jnp.asarray(slots, jnp.int32),
                                    master=jnp.asarray(rows_m),
                                    accum=jnp.asarray(rows_a))
        step = {"hits": hits, "misses": misses,
                "hit_rate": hits / max(hits + misses, 1),
                "loaded_chunks": int(missing.size),
                "evicted_chunks": evicted,
                "swap_in_bytes": swap_in, "swap_out_bytes": swap_out}
        return plan, step

    def _assign_slots_locked(self, missing: np.ndarray
                             ) -> Tuple[np.ndarray, int]:
        free = np.flatnonzero(self.slot_chunk < 0)
        evicted = 0
        if free.size < missing.size:
            need = missing.size - free.size
            cand = np.flatnonzero((self.chunk_slot >= 0) & (self.pins == 0))
            if cand.size < need:
                raise CacheThrash(
                    f"need {missing.size} chunk slots but only {free.size} "
                    f"free + {cand.size} evictable of "
                    f"{self.capacity_chunks} (pinned in-flight working set "
                    "exceeds capacity)")
            # frequency-weighted LFU: evict the coldest unpinned chunks
            order = cand[np.argsort(self.freq[cand], kind="stable")]
            for victim in order[:need]:
                if self.dirty[victim]:
                    self._writeback_locked(victim)
                slot = self.chunk_slot[victim]
                self.chunk_slot[victim] = -1
                self.slot_chunk[slot] = -1
                evicted += 1
            self.stats.evictions += evicted
            free = np.flatnonzero(self.slot_chunk < 0)
        slots = np.sort(free[:missing.size])
        self.chunk_slot[missing] = slots
        self.slot_chunk[slots] = missing
        return slots, evicted

    def _writeback_locked(self, chunk: int) -> None:
        win = self._window_ref
        if win is None:
            raise RuntimeError("dirty chunk eviction before any window "
                               "was published")
        R, D = self.chunk_rows, self.dim
        s = int(self.chunk_slot[chunk])
        # row-sparse D2H: only the rows the sparse updates touched differ
        # from the host copy — untouched rows are bitwise equal already.
        # No recorded touch set (crash recovery / legacy release) → whole
        # chunk, conservatively.
        mask = self.dirty_rows.pop(int(chunk), None)
        rows = np.flatnonzero(mask) if mask is not None else np.arange(R)
        self.stats.writeback_rows_dirty += int(rows.size)
        self.stats.writeback_rows_total += R
        if rows.size:
            idx = jnp.asarray(s * R + rows, jnp.int32)
            m = np.asarray(jax.device_get(jnp.take(win.master, idx, axis=0)))
            a = np.asarray(jax.device_get(jnp.take(win.accum, idx, axis=0)))
            self.host_master[chunk * R + rows] = m
            self.host_accum[chunk * R + rows] = a
            self.stats.swap_out_bytes += int(m.nbytes + a.nbytes)
        self.dirty[chunk] = False
        self.stats.writebacks += 1

    def splice(self, table: ET.ShadowedTable,
               plan: Optional[PrefetchPlan]) -> ET.ShadowedTable:
        """Land a prepared plan's chunks into the window (device scatter).

        Cheap async-dispatched `.at[slots].set` over the chunk-major view;
        the shadow slice is made from the spliced master rows, preserving
        ``shadow == shadow_of(master, qdtype)`` bitwise. The touched slots
        belong to chunks no in-flight batch reads or writes (they were
        just non-resident and everything in flight is pinned), so the
        splice commutes with concurrent sparse landings.
        """
        if plan is None:
            return table
        R, D = self.chunk_rows, self.dim
        C = table.master.shape[0] // R
        master = (table.master.reshape(C, R, D)
                  .at[plan.slots].set(plan.master).reshape(C * R, D))
        accum = (table.accum.reshape(C, R, D)
                 .at[plan.slots].set(plan.accum).reshape(C * R, D))
        shadow = table.shadow
        if shadow is not None:
            row = shadow.shape[1:]
            shadow = (shadow.reshape(C, R, *row)
                      .at[plan.slots].set(
                          ET.shadow_of(plan.master, ET.shadow_dtype(shadow)))
                      .reshape(C * R, *row))
        return ET.ShadowedTable(master=master, shadow=shadow, accum=accum)

    def _mark_rows_dirty_locked(self, uids: Optional[np.ndarray]) -> None:
        """Fold a batch's touched global ids into the per-chunk row masks
        (``None`` = unknown touch set: drop to whole-chunk granularity by
        discarding any partial mask for the affected chunks)."""
        if uids is None or uids.size == 0:
            return
        cid = uids // self.chunk_rows
        loc = uids % self.chunk_rows
        for c in np.unique(cid):
            c = int(c)
            mask = self.dirty_rows.get(c)
            if mask is None:
                # a chunk already dirty WITHOUT a mask stays whole-chunk
                if self.dirty[c]:
                    continue
                mask = self.dirty_rows[c] = np.zeros(self.chunk_rows, bool)
            mask[loc[cid == c]] = True

    def release(self, batch: int, *, dirty: bool = True) -> None:
        """Unpin a batch whose sparse update has landed (``dirty=True``)
        or that was dropped without touching the table."""
        with self._lock:
            chunks = self._batch_chunks.pop(batch, None)
            rows = self._batch_rows.pop(batch, None)
            if chunks is None:
                return
            self.pins[chunks] -= 1
            if dirty:
                self._mark_rows_dirty_locked(rows)
                self.dirty[chunks] = True

    def defer_release(self, batch: int) -> None:
        """τ=1: the batch's pairs are pending — keep its chunks pinned
        until :meth:`release_pending` (the deferred landing)."""
        with self._lock:
            if batch not in self._batch_chunks:
                return
            if self._pending_chunks is not None:
                raise RuntimeError("two batches with pending pairs — the "
                                   "τ=1 carry holds at most one")
            self._pending_chunks = self._batch_chunks.pop(batch)
            self._pending_rows = self._batch_rows.pop(batch, None)

    def release_pending(self) -> None:
        """The deferred τ=1 pairs landed: unpin + mark dirty."""
        with self._lock:
            chunks, self._pending_chunks = self._pending_chunks, None
            rows, self._pending_rows = self._pending_rows, None
            if chunks is not None:
                self.pins[chunks] -= 1
                self._mark_rows_dirty_locked(rows)
                self.dirty[chunks] = True

    def reset_pins(self) -> None:
        """Drop every in-flight pin (crash-recovery path: the run that
        took them is gone; dirty flags are kept)."""
        with self._lock:
            self._batch_chunks.clear()
            self._batch_rows.clear()
            self._pending_chunks = None
            self._pending_rows = None
            self.pins[:] = 0

    # -- full-table assembly (checkpointing) --------------------------------
    def materialize(self, window: Optional[ET.ShadowedTable] = None,
                    ) -> ET.ShadowedTable:
        """Reassemble the full ``(V, D)`` table: host rows overlaid with
        the dirty chunks of ``window`` (default: the latest published
        window). Non-mutating — host state and dirty flags are untouched,
        so a mid-run snapshot can be materialized from its own
        carry-convention window without disturbing training. The shadow
        is a 0-row stripped placeholder (checkpoints never store it)."""
        with self._lock:
            m, a = self._flush_into_locked(window, self.host_master.copy(),
                                           self.host_accum.copy())
        master = jnp.asarray(m[:self.vocab])
        accum = jnp.asarray(a[:self.vocab])
        shadow = (None if self.qdtype is None
                  else ET.shadow_of(jnp.zeros((0, self.dim)), self.qdtype))
        return ET.ShadowedTable(master=master, shadow=shadow, accum=accum)

    def flush(self, window: Optional[ET.ShadowedTable] = None) -> None:
        """Write every dirty chunk's window rows back to host RAM and
        clear the dirty flags (end-of-run host-master extraction)."""
        with self._lock:
            self._flush_into_locked(window, self.host_master,
                                    self.host_accum)
            self.dirty[:] = False
            self.dirty_rows.clear()

    def _flush_into_locked(self, window, m: np.ndarray, a: np.ndarray):
        win = window if window is not None else self._window_ref
        d = np.flatnonzero(self.dirty)
        if d.size:
            if win is None:
                raise RuntimeError("dirty chunks but no window to flush "
                                   "from")
            R, D = self.chunk_rows, self.dim
            C = win.master.shape[0] // R
            slots = jnp.asarray(self.chunk_slot[d])
            m.reshape(-1, R, D)[d] = np.asarray(
                jax.device_get(win.master.reshape(C, R, D)[slots]))
            a.reshape(-1, R, D)[d] = np.asarray(
                jax.device_get(win.accum.reshape(C, R, D)[slots]))
        return m, a

    def adopt(self, table: ET.ShadowedTable, pending_ids=None
              ) -> Tuple[ET.ShadowedTable, np.ndarray]:
        """Load a full ``(V, D)`` table (a restored checkpoint) into the
        host store and rebuild residency from the accumulated frequency
        counters; chunks referenced by live ``pending_ids`` (global, −1 =
        empty) are force-admitted and pinned as the τ=1 pending carry.
        Returns ``(window, slot_pending_ids)``; the window is published.
        """
        p = (np.asarray(pending_ids, np.int64).reshape(-1)
             if pending_ids is not None else np.empty(0, np.int64))
        live = p[p >= 0]
        forced = np.unique(np.clip(live, 0, self.vocab - 1)
                           // self.chunk_rows)
        if forced.size > self.capacity_chunks:
            raise CacheThrash(f"{forced.size} pending-pair chunks exceed "
                              f"capacity {self.capacity_chunks}")
        with self._lock:
            self.host_master[:self.vocab] = np.asarray(
                jax.device_get(table.master), np.float32)
            self.host_master[self.vocab:] = 0.0
            self.host_accum[:self.vocab] = np.asarray(
                jax.device_get(table.accum), np.float32)
            self.host_accum[self.vocab:] = 0.0
            self.dirty[:] = False
            self.dirty_rows.clear()
            self.pins[:] = 0
            self._batch_chunks.clear()
            self._batch_rows.clear()
            self._pending_chunks = None
            self._pending_rows = None
            # admission: forced pending chunks + hottest fill
            admit = list(forced)
            taken = set(admit)
            for c in np.argsort(-self.freq, kind="stable"):
                if len(admit) >= min(self.capacity_chunks, self.num_chunks):
                    break
                if int(c) not in taken:
                    admit.append(int(c))
                    taken.add(int(c))
            admit = np.sort(np.asarray(admit, np.int64))
            self.chunk_slot[:] = -1
            self.slot_chunk[:] = -1
            self.chunk_slot[admit] = np.arange(admit.size)
            self.slot_chunk[:admit.size] = admit
            win = self._window_from_host_locked()
            self._window_ref = win
            if forced.size:
                self.pins[forced] += 1
                self._pending_chunks = forced
                self._pending_rows = np.unique(
                    np.clip(live, 0, self.vocab - 1))
        return win, (self.slotize_pending(p) if pending_ids is not None
                     else np.empty(0, np.int32))

    # -- introspection ------------------------------------------------------
    def resident_chunks(self) -> np.ndarray:
        with self._lock:
            return np.flatnonzero(self.chunk_slot >= 0)

    def counters(self) -> Dict[str, float]:
        """Flat snapshot of the cumulative stats (benchmark/JSON form)."""
        s = self.stats
        return {"hits": s.hits, "misses": s.misses,
                "hit_rate": s.hit_rate, "evictions": s.evictions,
                "writebacks": s.writebacks,
                "swap_in_bytes": s.swap_in_bytes,
                "swap_out_bytes": s.swap_out_bytes,
                "warmup_bytes": s.warmup_bytes,
                "writeback_rows_dirty": s.writeback_rows_dirty,
                "writeback_rows_total": s.writeback_rows_total}
