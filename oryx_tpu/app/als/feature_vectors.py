"""Device-backed feature-vector store with a dynamic ID universe.

Reference: app/oryx-app-common/src/main/java/com/cloudera/oryx/app/als/
FeatureVectors.java:28-86 (get/set vector, recent-ID tracking,
retainRecentAndIDs, getVTV), FeatureVectorsPartition.java:36 (hash map +
RW lock per partition), PartitionedFeatureVectors.java:43-222 (the
serving-time sharded matrix).

TPU-native design (the "dynamic ID universe on a static-shape device"
hard part): IDs live in a host dict mapping to rows of a padded device
array.  Single-row "UP" mutations write a host mirror and enqueue the
row; the device copy is refreshed lazily at the next read — the dirty
rows scattered IN PLACE into the resident array by a jitted program that
donates it (no second copy of the store ever exists: at 20M x 250 the
store is 10 of the chip's 16 GB), a full re-upload when half the rows
changed — so serving reads always see a consistent device snapshot and
per-event device dispatch never happens.  Removed rows are zeroed and
recycled via a free list; capacity grows by doubling.

A store may be PARTITIONED (``partition_by``; the serving model's item
store under LSH): a row then lives in the region of its vector's hash
bucket, a region being whole ``step``-row steps of the array, so that
every step holds rows of one bucket and a scan can visit the steps of
some buckets only.  Free rows inside a region are inactive exactly as
removed rows are; a write whose vector hashes elsewhere moves the row.
There is still ONE copy of the factors.

The ordering rule between readers and syncs (``dispatching``): a sync
donates the resident arrays, which deletes the Python handles of the
version before it.  Syncs and readers are therefore ordered under one
lock, the store's DISPATCH lock: a reader holds it from the moment it
fetches ``(vecs, active, version)`` until it has ENQUEUED every program
that reads them — not until their results arrive: the device runs
programs in the order they were enqueued, so a scatter enqueued later
waits for them — and a sync happens only inside that lock, between two
readers' enqueues.  A handle fetched under the lock is never used after
the lock is released.
"""

from __future__ import annotations

import collections
import contextlib
import threading
from functools import partial
from typing import Callable, Iterable, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ...common.lang import AutoReadWriteLock

__all__ = ["FeatureVectorStore", "DeviceSnapshot", "resolve_dtype"]

# above this fraction of dirty rows, re-upload the whole array instead of
# scattering individual rows
_FULL_UPLOAD_FRACTION = 0.5

# the most rows one in-place scatter carries; more go in several.  Row
# counts are padded up to a power of two (floor 8) by repeating a row,
# so a store compiles at most this ladder of scatter programs
_SYNC_MAX_ROWS = 4096

# how many syncs back ``rows_changed_since`` can answer
_SYNC_LOG = 256

# beyond this many rows, capacity is rounded to a multiple of this chunk
# instead of the next power of two: a 20M-item model must not allocate a
# 32M-row device array, and the chunked top-N kernel requires the row
# count to be a multiple of its scan chunk (serving_model._CHUNK_ROWS)
_LARGE_ALIGN = 1 << 17


def planned_capacity(n_rows: int, initial_capacity: int = 1024,
                     buckets: int = 0, step: int = 0) -> int:
    """The padded row capacity a fresh store ends up with after a
    single ``bulk_load`` of ``n_rows`` vectors — the compiled leading
    dimension every serving kernel sees for a model of that size.  The
    deploy-time AOT warmup (deploy/warmup.py) uses this to lower the
    kernel ladder with the EXACT shapes a later model load produces;
    keep it in lock-step with ``__init__``/``_grow`` (and tested
    against a real bulk_load in tests/test_warmup.py).  For a store
    partitioned into ``buckets`` regions of ``step``-row steps it is
    what buckets of EQUAL size come to (a region is whole steps, so it
    holds half a step of slack in the mean): exact for factors that
    hash evenly, an estimate for a trained catalog's."""
    if buckets:
        steps = buckets * -(-n_rows // (buckets * step))
        cap = max(step, steps * step)
        return -(-cap // _LARGE_ALIGN) * _LARGE_ALIGN \
            if cap > _LARGE_ALIGN else cap
    cap = max(16, initial_capacity)
    if n_rows > cap:
        # one _grow(min_capacity=n_rows) from the fresh store
        cap = max(cap * 2, n_rows)
    if cap > _LARGE_ALIGN:
        cap = -(-cap // _LARGE_ALIGN) * _LARGE_ALIGN
    return cap


def resolve_dtype(name) -> np.dtype:
    """Factor storage dtype from a config string.  ``bfloat16`` halves
    both host and HBM footprint (20M x 250 drops from 20 GB to 10 GB —
    the reference's largest published model, docs/docs/performance.html
    memory table) and the MXU natively multiplies bf16 with float32
    accumulation, so dot-product scores keep full precision."""
    if name is None or isinstance(name, np.dtype):
        return np.dtype(np.float32) if name is None else name
    name = str(name)
    if name in ("bfloat16", "bf16"):
        import ml_dtypes
        return np.dtype(ml_dtypes.bfloat16)
    if name in ("float32", "f32"):
        return np.dtype(np.float32)
    raise ValueError(f"unsupported factor dtype: {name}")


class DeviceSnapshot(NamedTuple):
    """What ``FeatureVectorStore.dispatching`` hands a reader."""

    vecs: jax.Array
    active: jax.Array
    # bumped by every sync; the cache key of state derived from ``vecs``
    # (state derived from ``active`` alone hangs on the handle itself:
    # a sync that changes vectors only leaves it as it is)
    version: int
    # rows this acquisition's own sync wrote to the device (0: none
    # were pending), the bytes they came to, and the tags writers
    # attached to them (``set_vector(tag=...)``)
    synced_rows: int
    synced_bytes: int
    tags: tuple


@partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(resident, rows, values):
    """Write ``values`` at ``rows`` of a resident array, which is
    donated: XLA aliases the output to it and touches only the rows
    named (the compiled program holds no temporary; a duplicate row
    carries the same value twice)."""
    return resident.at[rows].set(values)


@jax.jit
def _gramian(vecs):
    """V^T V, float32 accumulation, contracting the row axis of the
    array as it lies: no transpose is materialised (an eager ``vecs.T``
    was a second copy of the store)."""
    return jax.lax.dot_general(vecs, vecs, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _sync_bucket(n: int) -> int:
    return max(8, 1 << (n - 1).bit_length())


class _Partition:
    """The layout of a partitioned store (``partition_by``): which
    bucket each ``step``-row step of the array belongs to, and where
    the free rows are.  Guarded by the store's write lock."""

    def __init__(self, bucket_of, n_buckets: int, step: int, cap: int):
        self.bucket_of = bucket_of      # (n, features) array -> (n,) ints
        self.n_buckets = n_buckets
        self.step = step
        # bucket of every step; -1: not given to a bucket yet
        self.step_bucket = np.full(cap // step, -1, dtype=np.int32)
        # steps no bucket has yet (popped from the end: lowest first)
        self.free_steps: list[int] = list(range(cap // step - 1, -1, -1))
        # free rows of each bucket's region (popped from the end)
        self.free: list[list[int]] = [[] for _ in range(n_buckets)]
        # bumped when a step is given to a bucket: the cache key of
        # what readers derive from ``step_bucket``
        self.version = 0
        # rows that changed region because a write changed their bucket
        self.row_moves = 0

    def bucket_of_row(self, row: int) -> int:
        return int(self.step_bucket[row // self.step])


class FeatureVectorStore:
    """Mutable {id -> float32[k]} map materialized as a device array."""

    def __init__(self, features: int, initial_capacity: int = 1024,
                 dtype="float32", device_sharding=None):
        """``device_sharding`` (a ``jax.sharding.NamedSharding`` whose
        first axis row-shards) places the device snapshot across a mesh
        instead of one device — serving mode for item matrices past one
        chip's HBM.  Capacity is always grown to a multiple of the
        device count so the leading dim splits evenly, and past
        ``_LARGE_ALIGN`` rows to a multiple of devices x that chunk, so
        that every SHARD splits into whole streaming chunks and blocks
        as a one-chip store does (20M rows over 4 devices: 5,013,504
        rows a shard would be 38.25 chunks; 5,111,808 are 39);
        single-row UP syncs use the same batched scatter as the
        single-device path (GSPMD partitions a replicated-update scatter
        onto the sharded operand with no collectives)."""
        self.features = features
        # Device snapshots lane-pad the feature dim to 128: a factor
        # tile whose minor dim is under the TPU's 128-lane width runs
        # the serving scan ~2x slower end to end (measured r05: the
        # 50-feature 20M-item phase-A kernel at 22.6 ms vs 11.6 ms with
        # the same data zero-padded to 128 lanes — the sub-width tile
        # poisons the MXU feed and every VPU op downstream).  Host
        # arrays stay at the true width; zero columns are transparent
        # to every dot-product consumer, and vtv() slices them off.
        self.device_features = features if features >= 128 else 128
        self.dtype = resolve_dtype(dtype)
        self._sharding = device_sharding
        self._cap_multiple = 1
        # rows a step of a partitioned store (partition_by); 1: none
        self._step_multiple = 1
        self._active_sharding = None
        if device_sharding is not None:
            n_dev = device_sharding.mesh.devices.size
            if n_dev & (n_dev - 1):
                raise ValueError(
                    f"sharded store needs a power-of-two device count, "
                    f"got {n_dev}")
            self._cap_multiple = n_dev
            from jax.sharding import NamedSharding, PartitionSpec
            self._active_sharding = NamedSharding(
                device_sharding.mesh,
                PartitionSpec(*device_sharding.spec[:1]))
        cap = self._aligned(max(16, initial_capacity))
        self._id_to_row: dict[str, int] = {}
        self._row_to_id: list[str | None] = [None] * cap
        self._free: list[int] = list(range(cap - 1, -1, -1))
        self._host = np.zeros((cap, features), dtype=self.dtype)
        self._active = np.zeros(cap, dtype=bool)
        self._dirty: set[int] = set()
        # both written under _lock.write() only (an AutoReadWriteLock,
        # which the guarded-by lint does not model); _device_lock, held
        # around some of those writes, ORDERS readers and syncs
        self._device: jax.Array | None = None  # guarded-by: none — see above
        self._device_active: jax.Array | None = None  # guarded-by: none — see above
        self._device_version = 0
        # a write made a row live or retired one since the last sync
        self._active_dirty = False
        # opaque marks of the writers whose rows wait in _dirty
        self._dirty_tags: set = set()
        # (version, rows) of the latest syncs, for rows_changed_since
        self._sync_log: collections.deque = collections.deque(
            maxlen=_SYNC_LOG)
        # counters: device syncs made and rows they carried
        self.device_syncs = 0
        self.rows_synced = 0
        # V^T V kept current by corrections: the Gramian of the rows as
        # they were at _vtv_base's moment, and the stored value each row
        # written since had then (first overwrite only)
        self._vtv_base: np.ndarray | None = None
        self._vtv_tracking = False
        self._vtv_old: dict[int, np.ndarray] = {}
        self.gramian_scans = 0
        self._recent: set[str] = set()
        self._lock = AutoReadWriteLock()
        # the dispatch lock (module docstring): syncs and readers'
        # enqueues, one at a time.  Taken BEFORE _lock, never inside it
        self._device_lock = threading.RLock()
        # row->id snapshot cache for the serving hot path; invalidated
        # by bumping _mutations under the write lock
        self._mutations = 0
        # (mutation count it was copied at, the copy): one tuple, so
        # that row_ids() can read both without the lock
        self._row_ids_cache: tuple[int, list[str | None]] | None = None
        # None: rows live wherever they were appended (the layout every
        # store has but an item store under LSH)
        self._part: _Partition | None = None

    # -- the partitioned layout ---------------------------------------------

    def partition_by(self, bucket_of: Callable[[np.ndarray], np.ndarray],
                     n_buckets: int, step: int) -> None:
        """Lay this (still empty, one-device) store out by bucket:
        ``bucket_of`` maps an (n, features) array of vectors in the
        store's dtype to their n bucket ids in [0, ``n_buckets``), and
        from now on every ``step``-row step of the array holds rows of
        ONE bucket (module docstring).  The capacity becomes a whole
        number of steps; a bucket takes a step when its first row
        arrives and another when its region is full, from the
        array's unassigned steps or, when there is none, after
        ``_grow`` (which costs what it costs any store: a new capacity
        and a whole re-upload)."""
        if step <= 0 or step & (step - 1):
            raise ValueError(f"a step is a power of two of rows, not {step}")
        with self._lock.write():
            if self._id_to_row or self._sharding is not None:
                raise ValueError("only an empty one-device store can "
                                 "be partitioned")
            self._step_multiple = step
            cap = self._aligned(len(self._row_to_id))
            self._row_to_id = [None] * cap
            self._free = []
            self._host = np.zeros((cap, self.features), dtype=self.dtype)
            self._active = np.zeros(cap, dtype=bool)
            self._part = _Partition(bucket_of, n_buckets, step, cap)
            self._mutations += 1

    @property
    def partitioned(self) -> bool:
        return self._part is not None

    def partition_layout(self) -> tuple[np.ndarray, int, int]:
        """(bucket of every step (a copy; -1 where no bucket has the
        step), rows a step, version of that table) of a partitioned
        store."""
        with self._lock.read():
            p = self._part
            return p.step_bucket.copy(), p.step, p.version

    @property
    def partition_version(self) -> int:
        return self._part.version

    @property
    def row_moves(self) -> int:
        """Rows that changed region because a write changed the bucket
        of their vector (0 for a store that is not partitioned)."""
        return self._part.row_moves if self._part is not None else 0

    def _bucket_row(self, bucket: int) -> int:
        """A free row of ``bucket``'s region (write lock held)."""
        free = self._part.free[bucket]
        if not free:
            self._give_step(bucket)
        return free.pop()

    def _give_step(self, bucket: int) -> None:
        """One more step for ``bucket``'s region (write lock held),
        from the steps no bucket has; the array grows first where there
        is none.  A region's rows are handed out in ascending order:
        the new step goes UNDER what is still free."""
        p = self._part
        if not p.free_steps:
            self._grow()
        s = p.free_steps.pop()
        p.step_bucket[s] = bucket
        p.version += 1
        p.free[bucket][:0] = range((s + 1) * p.step - 1, s * p.step - 1, -1)

    def _release(self, row: int) -> None:
        """Retire ``row`` (write lock held): zeroed, inactive, dirty,
        and free again, in its region where the store has regions."""
        self._row_to_id[row] = None
        self._host[row] = 0.0
        self._active[row] = False
        self._active_dirty = True
        self._dirty.add(row)
        if self._part is None:
            self._free.append(row)
        else:
            self._part.free[self._part.bucket_of_row(row)].append(row)

    # -- basic map ops ------------------------------------------------------

    def __len__(self) -> int:
        with self._lock.read():
            return len(self._id_to_row)

    def size(self) -> int:
        return len(self)

    def all_ids(self) -> list[str]:
        with self._lock.read():
            return list(self._id_to_row.keys())

    def __contains__(self, id_: str) -> bool:
        with self._lock.read():
            return id_ in self._id_to_row

    def get_vector(self, id_: str) -> np.ndarray | None:
        with self._lock.read():
            row = self._id_to_row.get(id_)
            return None if row is None \
                else self._host[row].astype(np.float32)

    def row_of(self, id_: str) -> int | None:
        with self._lock.read():
            return self._id_to_row.get(id_)

    def id_of(self, row: int) -> str | None:
        with self._lock.read():
            return self._row_to_id[row] if 0 <= row < len(self._row_to_id) else None

    def set_vector(self, id_: str, vector: np.ndarray, tag=None) -> None:
        """``tag``, if given, comes back in ``DeviceSnapshot.tags`` of
        the sync that carries this row to the device."""
        vector = np.asarray(vector, dtype=np.float32)
        part = self._part
        if part is not None:
            # hashed as it will be STORED, outside the lock (a device
            # call); the layout is only read under it
            stored = vector.astype(self.dtype)[None, :]
            bucket = int(part.bucket_of(stored)[0])
        with self._lock.write():
            row = self._id_to_row.get(id_)
            if part is not None and row is not None \
                    and part.bucket_of_row(row) != bucket:
                # the vector now hashes elsewhere: the row is freed and
                # the id takes a row in its new region
                self._overwriting(row)
                self._release(row)
                part.row_moves += 1
                row = None
            if row is None:
                if part is not None:
                    row = self._bucket_row(bucket)
                else:
                    if not self._free:
                        self._grow()
                    row = self._free.pop()
                self._id_to_row[id_] = row
                self._row_to_id[row] = id_
                self._mutations += 1
            self._overwriting(row)
            self._host[row] = vector
            if not self._active[row]:
                self._active[row] = True
                self._active_dirty = True
            self._dirty.add(row)
            if tag is not None:
                self._dirty_tags.add(tag)
            self._recent.add(id_)

    def _overwriting(self, row: int) -> None:
        """Before a single row's stored value changes (write lock held):
        keep what V^T V was computed from, once per row."""
        if self._vtv_tracking and row not in self._vtv_old:
            self._vtv_old[row] = self._host[row].astype(np.float32)

    def bulk_load(self, ids: list[str], matrix: np.ndarray) -> None:
        """Set many vectors at once — the fast path for MODEL publish
        consumption and benchmark model factories.  Equivalent to
        set_vector per row but one vectorized host write instead of n
        dict/array operations."""
        matrix = np.asarray(matrix)
        if matrix.dtype != self.dtype:
            matrix = matrix.astype(self.dtype)
        if matrix.shape != (len(ids), self.features):
            raise ValueError(
                f"matrix must be ({len(ids)}, {self.features}), "
                f"got {matrix.shape}")
        if self._part is not None:
            return self._bulk_load_partitioned(ids, matrix)
        with self._lock.write():
            new_ids = [i for i in ids if i not in self._id_to_row]
            if len(self._free) < len(new_ids):
                # size once, exactly: a 20M-row load must not hit
                # pow2-doubling (a 33.5M-row array at 250 features is
                # 13.4 GB of pure padding)
                self._grow(len(self._id_to_row) + len(new_ids))
            rows = np.empty(len(ids), dtype=np.int64)
            for j, id_ in enumerate(ids):
                row = self._id_to_row.get(id_)
                if row is None:
                    row = self._free.pop()
                    self._id_to_row[id_] = row
                    self._row_to_id[row] = id_
                    self._mutations += 1
                rows[j] = row
            self._host[rows] = matrix
            self._active[rows] = True
            self._active_dirty = True
            self._dirty.update(rows.tolist())
            self._recent.update(ids)
            self._vtv_forget()

    def _bulk_load_partitioned(self, ids: list[str],
                               matrix: np.ndarray) -> None:
        """``bulk_load`` into regions: the buckets in one pass over the
        matrix, the rows of every bucket taken at once, one permuted
        host write."""
        part = self._part
        if len(set(ids)) != len(ids):
            # an id twice in one load: row by row, the last one wins
            for id_, vector in zip(ids, matrix):
                self.set_vector(id_, vector)
            with self._lock.write():
                self._vtv_forget()
            return
        buckets = np.asarray(part.bucket_of(matrix), dtype=np.int64)
        with self._lock.write():
            rows = np.full(len(ids), -1, dtype=np.int64)
            for j, id_ in enumerate(ids):
                row = self._id_to_row.get(id_)
                if row is None:
                    continue
                if part.bucket_of_row(row) == buckets[j]:
                    rows[j] = row
                else:
                    del self._id_to_row[id_]
                    self._release(row)
                    part.row_moves += 1
            fresh = np.flatnonzero(rows < 0)
            counts = np.bincount(buckets[fresh], minlength=part.n_buckets)
            short = sum(-(-max(0, int(c) - len(part.free[b])) // part.step)
                        for b, c in enumerate(counts.tolist()))
            if short > len(part.free_steps):
                # size once, exactly (as bulk_load does): the steps the
                # buckets lack, no doubling
                self._grow(len(self._row_to_id)
                           + (short - len(part.free_steps)) * part.step)
            order = fresh[np.argsort(buckets[fresh], kind="stable")]
            at = 0
            for b, c in enumerate(counts.tolist()):
                free = part.free[b]
                while len(free) < c:
                    self._give_step(b)
                if c:
                    rows[order[at:at + c]] = free[:-c - 1:-1]
                    del free[-c:]
                    at += c
            for j in fresh.tolist():
                row = int(rows[j])
                self._id_to_row[ids[j]] = row
                self._row_to_id[row] = ids[j]
            self._mutations += 1
            self._host[rows] = matrix
            self._active[rows] = True
            self._active_dirty = True
            self._dirty.update(rows.tolist())
            self._recent.update(ids)
            self._vtv_forget()

    def remove(self, id_: str) -> None:
        with self._lock.write():
            row = self._id_to_row.pop(id_, None)
            if row is not None:
                self._mutations += 1
                self._overwriting(row)
                self._release(row)

    def recent_ids(self) -> set[str]:
        """IDs set since the last retain (reference: FeatureVectors.addAllRecentTo)."""
        with self._lock.read():
            return set(self._recent)

    def retain_recent_and_ids(self, ids: Iterable[str]) -> None:
        """Drop all IDs not in ``ids`` and not recently set; clear the
        recent set (reference: FeatureVectors.retainRecentAndIDs — the
        MODEL-swap grace logic)."""
        keep = set(ids)
        with self._lock.write():
            keep |= self._recent
            for id_ in [i for i in self._id_to_row if i not in keep]:
                self._release(self._id_to_row.pop(id_))
                self._mutations += 1
            # a model swap drops rows by the thousand: scan again
            self._vtv_forget()
            self._recent.clear()

    def reserve(self, n_rows: int) -> None:
        """Pre-size the store for ``n_rows`` expected vectors with ONE
        exact-fit grow — the capacity ``planned_capacity`` predicts and
        the deploy-time AOT warmup compiled for.  Called at MODEL time
        with the expected-ID universe, so the per-UP-message replay
        that follows never regrows (each regrow of a multi-GB store
        re-uploads the whole device snapshot, and every intermediate
        pow2 capacity would be a compiled-shape cache miss)."""
        with self._lock.write():
            if self._part is not None:
                # which buckets the rows will fall into is not known
                # yet: room for them plus the last, part-filled step
                # of every region
                n_rows += self._part.n_buckets * self._part.step
            if len(self._row_to_id) < n_rows:
                self._grow(n_rows)

    def _aligned(self, cap: int) -> int:
        """``cap`` rounded up to what the serving kernels can split: a
        multiple of the device count (exact-fit bulk_load growth can
        land on any size), and a large store a whole number of
        ``_LARGE_ALIGN``-row chunks on EVERY device."""
        m = self._cap_multiple
        if cap > _LARGE_ALIGN * m:
            m *= _LARGE_ALIGN
        elif cap > _LARGE_ALIGN:
            # between one chunk and one chunk a device: whole chunks,
            # as ever, and an even split
            cap = -(-cap // _LARGE_ALIGN) * _LARGE_ALIGN
        cap = -(-cap // m) * m
        # a partitioned store: whole steps (a power of two, as the
        # chunk is, so a large capacity stays whole chunks)
        return -(-cap // self._step_multiple) * self._step_multiple

    def _grow(self, min_capacity: int | None = None) -> None:
        old_cap = len(self._row_to_id)
        if old_cap >= 4 * _LARGE_ALIGN:
            # large stores grow by ~12.5% in chunk steps: doubling a
            # 20M-row exact-fit array when streaming updates exhaust its
            # head-room would allocate the very padding bulk_load avoids
            new_cap = old_cap + max(_LARGE_ALIGN, old_cap // 8)
        else:
            new_cap = old_cap * 2
        if min_capacity is not None and min_capacity > new_cap:
            new_cap = min_capacity
        new_cap = self._aligned(new_cap)
        host = np.zeros((new_cap, self.features), dtype=self.dtype)
        host[:old_cap] = self._host
        self._host = host
        active = np.zeros(new_cap, dtype=bool)
        active[:old_cap] = self._active
        self._active = active
        self._row_to_id.extend([None] * (new_cap - old_cap))
        self._mutations += 1
        part = self._part
        if part is None:
            self._free.extend(range(new_cap - 1, old_cap - 1, -1))
        else:
            # the new rows are whole steps no bucket has yet; no row
            # moves, every region keeps the steps it has
            old_steps = len(part.step_bucket)
            part.step_bucket = np.concatenate([
                part.step_bucket,
                np.full(new_cap // part.step - old_steps, -1, np.int32)])
            part.free_steps[:0] = range(new_cap // part.step - 1,
                                        old_steps - 1, -1)
            part.version += 1
        self._device = None  # force full re-upload at next sync
        self._device_active = None

    # -- device snapshot ----------------------------------------------------

    def device_arrays(self) -> tuple[jax.Array, jax.Array]:
        """(vectors, active_mask) on device, syncing pending host writes;
        see ``device_arrays_versioned`` for how long they stay valid."""
        vecs, active, _ = self.device_arrays_versioned()
        return vecs, active

    def device_arrays_versioned(self) -> tuple[jax.Array, jax.Array, int]:
        """Like device_arrays but also returns the snapshot's version,
        read atomically under the same lock — the safe cache key for
        derived device state (e.g. LSH buckets).

        The handles are valid until the NEXT sync, which donates them:
        a caller that can run while another thread writes to the store
        (every serving path) fetches them inside ``dispatching()``
        instead and enqueues its programs before leaving it.  This
        form is for callers that own the store (loads, tests, tools)."""
        with self._device_lock:
            snap = self._synced()
        return snap.vecs, snap.active, snap.version

    @contextlib.contextmanager
    def dispatching(self, track_vtv: bool = False
                    ) -> Iterator[DeviceSnapshot]:
        """The resident arrays, pending rows applied, with the dispatch
        lock held while the caller ENQUEUES the programs that read them
        (module docstring: the ordering rule).  Fetch results after the
        block, not in it: the lock is what every other reader and every
        sync waits on.  Reentrant on one thread."""
        with self._device_lock:
            yield self._synced(track_vtv)

    def pending_rows(self) -> int:
        """Rows written since the last sync that the next one will
        scatter (unlocked: a hint); 0 while nothing is resident yet,
        because the first upload is a load, not an update."""
        return len(self._dirty) if self._device is not None else 0

    def _synced(self, track_vtv: bool = False) -> DeviceSnapshot:
        """Apply what is pending (dispatch lock held) and describe the
        resident arrays."""
        with self._lock.write():
            cap = len(self._row_to_id)
            n_rows = n_bytes = 0
            tags: tuple = ()
            if self._device is None \
                    or len(self._dirty) >= cap * _FULL_UPLOAD_FRACTION:
                # drop the old copy first: both do not fit at 20M x 250
                self._device = self._device_active = None
                host = self._pad_cols(self._host)
                if self._sharding is not None:
                    self._device = jax.device_put(host, self._sharding)
                    self._device_active = jax.device_put(
                        self._active, self._active_sharding)
                else:
                    self._device = jnp.asarray(host)
                    self._device_active = jnp.asarray(self._active)
                self._device_version += 1
                self._sync_log.clear()  # no row list for a whole upload
                n_rows, n_bytes = cap, int(host.nbytes)
            elif self._dirty:
                rows = np.fromiter(self._dirty, dtype=np.int32,
                                   count=len(self._dirty))
                self._scatter(rows)
                self._device_version += 1
                self._sync_log.append((self._device_version, rows))
                n_rows = len(rows)
                n_bytes = n_rows * self.device_features \
                    * self.dtype.itemsize
            if n_rows:
                self.device_syncs += 1
                self.rows_synced += n_rows
                tags = tuple(self._dirty_tags)
                self._dirty_tags.clear()
            self._dirty.clear()
            self._active_dirty = False
            if track_vtv:
                # the Gramian about to be scanned is of the rows as they
                # are now: corrections count from here
                self._vtv_old = {}
                self._vtv_tracking = True
            return DeviceSnapshot(self._device, self._device_active,
                                  self._device_version, n_rows, n_bytes,
                                  tags)

    def _scatter(self, rows: np.ndarray) -> None:
        """The dirty ``rows`` of the host mirror into the resident
        arrays, in place (both locks held).  On a sharded snapshot GSPMD
        partitions the scatter onto the row-sharded operand with
        replicated updates — no collectives, no full re-upload."""
        for at in range(0, len(rows), _SYNC_MAX_ROWS):
            part = rows[at:at + _SYNC_MAX_ROWS]
            pad = _sync_bucket(len(part)) - len(part)
            if pad:
                part = np.concatenate([part, np.repeat(part[:1], pad)])
            self._device = _scatter_rows(
                self._device, part, self._pad_cols(self._host[part]))
            if self._active_dirty:
                # the mask only where a row came to life or was retired:
                # its handle, and what is derived from it alone, outlive
                # every sync that changes vectors only
                self._device_active = _scatter_rows(
                    self._device_active, part, self._active[part])

    def warm_sync(self, max_rows: int = _SYNC_MAX_ROWS) -> int:
        """Compile (and run, rewriting row 0 with itself) every scatter
        program a sync of up to ``max_rows`` rows can need, so that the
        first updates of a serving model compile nothing.  Returns how
        many programs ran."""
        n = 0
        with self._device_lock:
            self._synced()
            with self._lock.write():
                if self._device is None:
                    return 0
                bucket = 8
                while bucket <= min(_sync_bucket(max(1, max_rows)),
                                    _SYNC_MAX_ROWS):
                    rows = np.zeros(bucket, dtype=np.int32)
                    self._device = _scatter_rows(
                        self._device, rows,
                        self._pad_cols(self._host[rows]))
                    self._device_active = _scatter_rows(
                        self._device_active, rows, self._active[rows])
                    bucket *= 2
                    n += 1
        return n

    def rows_changed_since(self, version: int) -> np.ndarray | None:
        """The rows that syncs wrote after device version ``version``
        up to the current one, each once; None when that is not known
        (a whole upload in between, or further back than the log):
        whoever derives state from the matrix then rebuilds it."""
        with self._lock.read():
            if version == self._device_version:
                return np.zeros(0, dtype=np.int32)
            parts = [rows for v, rows in self._sync_log if v > version]
            if not self._sync_log or self._sync_log[0][0] > version + 1 \
                    or len(parts) != self._device_version - version:
                return None
        return np.unique(np.concatenate(parts))

    @property
    def device_version(self) -> int:
        """Monotonic counter bumped on every device-snapshot change; a
        safe cache key for derived device state (unlike id() of the
        array, which CPython can reuse after free)."""
        with self._lock.read():
            return self._device_version

    def row_ids(self) -> list[str | None]:
        """Snapshot of the row -> id table for batched result decoding.
        Cached against the mutation counter: the serving hot path calls
        this once per device dispatch, and copying a 20M-entry table per
        request batch would cost more than the scoring itself."""
        # No lock while no id was added or removed since the copy: this
        # is the LAST thing a drain does before its requests are
        # answered, and the lock prefers writers, so behind an update
        # consumer applying a micro-batch of UP records (a write lock a
        # record) a reader waited 4-12 ms here (PERF.md, PR 27).  A
        # stale count means a writer is ahead of us, which is what a
        # reader that took the lock a moment earlier would have seen.
        cache = self._row_ids_cache
        if cache is not None and cache[0] == self._mutations:
            return cache[1]
        with self._lock.read():
            cache = self._row_ids_cache
            if cache is None or cache[0] != self._mutations:
                cache = (self._mutations, list(self._row_to_id))
                self._row_ids_cache = cache
            return cache[1]

    def host_arrays(self) -> tuple[np.ndarray, np.ndarray, list[str | None]]:
        """Copy of (vectors, active, row->id) for host-side iteration."""
        with self._lock.read():
            return self._host.copy(), self._active.copy(), list(self._row_to_id)

    def _pad_cols(self, a: np.ndarray) -> np.ndarray:
        if self.device_features == self.features:
            return a
        out = np.zeros((a.shape[0], self.device_features), dtype=a.dtype)
        out[:, :self.features] = a
        return out

    def vtv(self) -> np.ndarray:
        """V^T V over live vectors (inactive rows are zero and
        contribute nothing; device lane-padding columns are zero and
        sliced off).  Reference: FeatureVectors.getVTV.

        The first call reads the resident array once, in place, in one
        device matmul.  From then on single-row writes are followed by
        corrections on the host — plus the outer products of the rows'
        new stored values, minus those of the values the scan saw — so
        a micro-batch of updates costs a (rows x k) matmul, not another
        read of the store; a bulk load or a model swap scans again."""
        with self._lock.write():
            base = self._vtv_base
            rows = np.fromiter(self._vtv_old, dtype=np.int64,
                               count=len(self._vtv_old))
            if base is not None and len(rows):
                old = np.stack([self._vtv_old[r] for r in rows.tolist()])
                new = self._host[rows].astype(np.float32)
                self._vtv_old = {}
        if base is None:
            with self.dispatching(track_vtv=True) as snap:
                out = _gramian(snap.vecs)
            k = self.features
            base = np.asarray(out)[:k, :k].astype(np.float64)
            with self._lock.write():
                self.gramian_scans += 1
                if self._vtv_tracking:  # no bulk load came in between
                    self._vtv_base = base
                    pending = bool(self._vtv_old)
                else:
                    pending = False
            return self.vtv() if pending else base.astype(np.float32)
        if len(rows):
            # products of stored values are exact in float32
            delta = (new.T @ new).astype(np.float64) \
                - (old.T @ old).astype(np.float64)
            with self._lock.write():
                if self._vtv_base is not None:
                    self._vtv_base = self._vtv_base + delta
                    base = self._vtv_base
                else:
                    base = base + delta
        return base.astype(np.float32)

    def _vtv_forget(self) -> None:
        """Many rows changed at once (write lock held): the next
        ``vtv()`` scans the store again."""
        self._vtv_base = None
        self._vtv_tracking = False
        self._vtv_old = {}

    def map_vectors(self, fn: Callable[[str, np.ndarray], None]) -> None:
        host, active, row_ids = self.host_arrays()
        for row, id_ in enumerate(row_ids):
            if id_ is not None and active[row]:
                fn(id_, host[row].astype(np.float32))
