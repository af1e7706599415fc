"""Sharded batch loader + double-buffered device prefetch.

TPU-native equivalent of the reference's ``DistributedSampler + DataLoader``
stack (`/root/reference/01_torch_distributor/01_basic_torch_distributor.py:285-286`,
`prepare_data_loader` in `/root/reference/05_ray/01_fashion_mnist_pytorch_ray.ipynb:cell-6`):

- :class:`DataLoader` shards the index space across *processes* (hosts), not
  across chips — each host materializes only its slice ("dataset handles, not
  dataset bytes, cross the process boundary", SURVEY.md §3.2), with
  ``set_epoch`` reshuffle semantics (`sampler.set_epoch(epoch)` parity).
- :class:`DevicePrefetcher` turns host batches into *global* jax Arrays laid
  out over the mesh's data axes and keeps ``depth`` batches in flight so
  host->HBM copies overlap compute (the role cuda streams/pin_memory play in
  the torch stack).

Batches are NHWC float32 (or uint8, converted on device); static shapes only —
the final ragged batch is either dropped (train) or padded with a validity
mask (eval) so XLA never recompiles.
"""

from __future__ import annotations

import collections
import multiprocessing
import os
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Iterator

import jax
import numpy as np

from tpuframe.core import runtime as rt
from tpuframe.track.telemetry import get_telemetry

#: XLA's CPU client zero-copies suitably-aligned host numpy buffers into
#: jax Arrays (measured on this jax: ``device_put`` of a 64-byte-aligned
#: f32 array aliases — mutating the numpy buffer afterwards mutates the
#: "device" value; small shard slices alias at a finer 16-byte grain).
#: Ring buffers are recycled after the device copy, so they must NEVER
#: be zero-copy donated.  Three layers keep that true: large buffers are
#: allocated off the 64-byte grain (here), tiny leaves get a private
#: copy before device_put (``DevicePrefetcher._SMALL_LEAF_BYTES``), and
#: ``BatchBufferPool.release`` re-verifies with ``np.shares_memory``
#: before any buffer re-enters the pool — the authoritative guard.
_XLA_ALIGN = 64


def _alloc_unaliasable(shape: tuple, dtype) -> np.ndarray:
    """A numpy array whose data pointer is deliberately NOT 64-byte
    aligned, so ``jax.device_put`` must copy instead of aliasing it."""
    dtype = np.dtype(dtype)
    nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
    # offset is a multiple of 8 (any dtype stays element-aligned) chosen
    # so the resulting pointer misses the 64-byte grain
    base = np.empty(nbytes + 2 * _XLA_ALIGN, np.uint8)
    addr = base.ctypes.data
    off = 8 if (addr + 8) % _XLA_ALIGN else 16
    return base[off : off + nbytes].view(dtype).reshape(shape)


def _aliases_host(device_arrays, host_bufs: "Sequence[np.ndarray]") -> bool:
    """True if any addressable shard of the device pytree shares memory
    with any of the host buffers (possible only on the CPU backend's
    zero-copy path; checked before a buffer is recycled)."""
    for leaf in jax.tree.leaves(device_arrays):
        if not isinstance(leaf, jax.Array):
            continue
        try:
            devices = leaf.devices()
        except Exception:
            continue
        if any(d.platform != "cpu" for d in devices):
            continue  # real H2D transfer: device memory never aliases host
        for shard in leaf.addressable_shards:
            view = np.asarray(shard.data)  # zero-copy view on CPU
            if any(np.shares_memory(view, b) for b in host_bufs):
                return True
    return False


class _BatchLease:
    """One pooled batch's buffers, outstanding until recycled."""

    __slots__ = ("images", "labels", "valid")

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 valid: np.ndarray | None):
        self.images = images
        self.labels = labels
        self.valid = valid

    def buffers(self) -> list:
        out = [self.images, self.labels]
        if self.valid is not None:
            out.append(self.valid)
        return out


class BatchBufferPool:
    """Small pool of preallocated, reusable batch buffers (the ring).

    Replaces per-batch ``np.stack`` allocations in :class:`DataLoader`
    assembly: workers write decoded samples directly into a leased
    buffer's rows; the lease returns to the pool once the consumer is
    done with it (in the standard pipeline: after the
    :class:`DevicePrefetcher`'s host->device copy of that batch
    completes).  Consumers that never release simply cause fresh
    allocations — exactly the old behavior, made visible through the
    ``data/ring_allocs`` counter (steady-state zero when recycling
    works).  The serve engine (``tpuframe.serve.engine``) is the second
    consumer: one pool per padded request bucket, leased per inference
    batch and released after the device copy — same zero-allocation
    steady state, same aliasing guards.

    Buffers are allocated off XLA's 64-byte zero-copy grain (see
    ``_alloc_unaliasable``) so a recycled buffer can never alias live
    device data, and ``release`` re-verifies that against the device
    arrays as defense in depth.
    """

    def __init__(self, size: int = 4):
        self.size = max(1, int(size))
        self._spec: tuple | None = None
        self._free: collections.deque[_BatchLease] = collections.deque()
        self._lock = threading.Lock()
        reg = get_telemetry().registry
        self._allocs = reg.counter("data/ring_allocs")
        self._recycled = reg.counter("data/ring_recycled")
        #: fresh allocations of THIS pool (the counter above is shared by
        #: every pool of the process)
        self.allocs = 0

    def acquire(self, batch: int, item_shape: tuple, dtype,
                with_valid: bool, label_shape: tuple = (),
                label_dtype=np.int32) -> _BatchLease:
        """A free pooled lease, or a freshly allocated one (counted).

        ``label_shape``/``label_dtype`` size the per-sample label row:
        ``()`` int32 for classification, ``(L,)`` for next-token LM
        targets — the ring serves both without a second pool."""
        spec = (int(batch), tuple(item_shape), np.dtype(dtype),
                bool(with_valid), tuple(label_shape), np.dtype(label_dtype))
        with self._lock:
            if spec != self._spec:  # shape/dtype change: old buffers useless
                self._spec = spec
                self._free.clear()
            if self._free:
                return self._free.popleft()
        self._allocs.inc()
        self.allocs += 1
        return _BatchLease(
            _alloc_unaliasable((batch,) + tuple(item_shape), dtype),
            _alloc_unaliasable((batch,) + tuple(label_shape), label_dtype),
            _alloc_unaliasable((batch,), np.bool_) if with_valid else None,
        )

    def release(self, lease: _BatchLease, device_arrays=None) -> bool:
        """Return ``lease`` to the pool.  ``device_arrays`` (the jax
        pytree built from it) gates recycling: an aliasing buffer — the
        CPU backend's zero-copy path, never expected given the
        misaligned allocation — is dropped, not reused."""
        if device_arrays is not None and _aliases_host(
            device_arrays, lease.buffers()
        ):
            return False
        with self._lock:
            lease_spec = (
                lease.labels.shape[0],
                lease.images.shape[1:],
                lease.images.dtype,
                lease.valid is not None,
                lease.labels.shape[1:],
                lease.labels.dtype,
            )
            if lease_spec == self._spec and len(self._free) < self.size:
                self._free.append(lease)
                self._recycled.inc()
                return True
        return False

#: Sample-fetch failures that read as a BAD RECORD rather than a bug:
#: decode errors (the strict native JPEG path and PIL both raise
#: ValueError/OSError on corrupt entropy data), shard I/O, codec
#: failures.  Bugs (TypeError, AttributeError, IndexError from a
#: mis-sized sampler) still raise immediately — the quarantine is for
#: poisoned *data*, not broken *code*.
_SKIPPABLE_SAMPLE_ERRORS = (ValueError, OSError, RuntimeError)


class _BadSample:
    """What a fetch returns instead of raising for a corrupt sample.

    A sentinel (not an exception) so it crosses the process-pool
    boundary as an ordinary pickled result: workers cannot emit the
    parent's telemetry, so the *parent* counts, logs and enforces the
    ``TPUFRAME_MAX_BAD_SAMPLES`` cap."""

    def __init__(self, index: int, error: str):
        self.index = index
        self.error = error


# Process-pool workers inherit the dataset via fork (copy-on-write — no
# per-item pickling of the dataset, only of the returned samples).  A
# module global is the one channel fork-inherited state can ride.
_WORKER_DATASET = None
_WORKER_EPOCH = None


def _pool_init(dataset) -> None:
    global _WORKER_DATASET, _WORKER_EPOCH
    _WORKER_DATASET = dataset
    _WORKER_EPOCH = None


def _pool_get(args):
    # epoch rides along with every request: the worker's dataset snapshot
    # never sees the parent's set_epoch calls, and epoch drives per-item
    # augmentation rngs (StreamingDataset.item_rng).  The shadow var — not
    # a dataset attribute probe — decides staleness, so set_epoch runs
    # once per epoch per worker regardless of how the dataset stores it.
    global _WORKER_EPOCH
    idx, epoch = args
    if epoch != _WORKER_EPOCH:
        if hasattr(_WORKER_DATASET, "set_epoch"):
            _WORKER_DATASET.set_epoch(epoch)
        _WORKER_EPOCH = epoch
    try:
        return _WORKER_DATASET[int(idx)]
    except _SKIPPABLE_SAMPLE_ERRORS as e:
        # bad-record quarantine: return the sentinel (picklable) so the
        # parent can skip-and-count instead of the whole epoch dying on
        # one corrupt JPEG
        return _BadSample(int(idx), f"{type(e).__name__}: {e}")


class DataLoader:
    """Iterates (images, labels[, valid_mask]) numpy batches of this process's shard.

    Args:
      dataset: map-style dataset (``__len__``/``__getitem__`` -> (img, label)).
      batch_size: **global** batch size; each process yields
        ``batch_size // process_count`` samples per step.
      shuffle: reshuffle per epoch from (seed, epoch) — equal permutations on
        every process, like DistributedSampler.
      drop_last: drop the trailing ragged batch (train default).  When False,
        the last batch is padded to full size and a boolean ``valid`` mask is
        yielded as third element (static shapes for jit-eval).
      num_workers: worker pool size for item fetch/transform (0 = inline).
        ``None`` (default) reads ``TPUFRAME_LOADER_WORKERS`` (else 0) —
        the env default is what lets the autotuner's winning config
        apply on a supervised restart without a code edit.
      worker_mode: ``"thread"`` (default — fine when decode releases the
        GIL and transforms are light) or ``"process"`` — a persistent
        pool that sidesteps the GIL entirely for numpy-heavy
        augmentation at ImageNet rates (SURVEY §7 "Input pipeline feeding
        HBM").  Process mode needs picklable *samples*.
      mp_context: process-pool start method.  ``"fork"`` (default, the
        torch-DataLoader convention) inherits the dataset copy-on-write —
        no pickling — but forking a process that already imported jax
        draws a deadlock warning; workers must therefore never touch jax
        (ours only touch the dataset).  ``"forkserver"``/``"spawn"``
        avoid that entirely but pickle the dataset once at pool creation
        (StreamingDataset pickles fine; locks/caches are re-created).
      transfer_dtype: dtype of the assembled batch buffers — what
        actually crosses host->HBM.  ``None`` (default) reads
        ``TPUFRAME_LOADER_TRANSFER_DTYPE``; unset, the buffers follow
        the first sample's dtype.  ``"uint8"`` is the 4x-less-PCIe path:
        pair with a geometric-only transform
        (:func:`tpuframe.data.transforms.uint8_image_transforms`) and
        on-device normalization (``Trainer(normalize=...)`` or the
        fused ``tpuframe.ops.normalize_images``).  Samples are cast on
        write with ``casting="same_kind"`` — a float sample under
        ``transfer_dtype="uint8"`` raises instead of silently
        truncating.
      ring_buffers: size of the preallocated batch-buffer pool (the
        assembly ring); ``None`` (default) reads
        ``TPUFRAME_LOADER_RING_BUFFERS`` (else 4).  Batches are views of pooled buffers, recycled
        after the :class:`DevicePrefetcher` finishes the device copy;
        steady-state assembly allocations are zero.  Consumers that
        hold many batches at once simply trigger fresh allocations
        (``data/ring_allocs`` counter) — never corruption.
    """

    def __init__(
        self,
        dataset: Any,
        batch_size: int,
        *,
        shuffle: bool = False,
        seed: int = 0,
        drop_last: bool = True,
        num_workers: int | None = None,
        worker_mode: str = "thread",
        mp_context: str = "fork",
        process_index: int | None = None,
        process_count: int | None = None,
        transfer_dtype: str | None = None,
        ring_buffers: int | None = None,
    ):
        if worker_mode not in ("thread", "process"):
            raise ValueError(
                f"worker_mode must be 'thread' or 'process', got {worker_mode!r}"
            )
        # env-defaulted knobs (tolerant reads; explicit arguments win) —
        # the seam through which a persisted autotune config reaches a
        # freshly constructed loader on a supervised restart
        from tpuframe.fault.health import _env_int

        if num_workers is None:
            num_workers = max(0, _env_int("TPUFRAME_LOADER_WORKERS", 0))
        if ring_buffers is None:
            ring_buffers = max(2, _env_int("TPUFRAME_LOADER_RING_BUFFERS", 4))
        if transfer_dtype is None:
            env_dtype = os.environ.get(
                "TPUFRAME_LOADER_TRANSFER_DTYPE", "").strip().lower()
            if env_dtype in ("uint8", "float32"):
                transfer_dtype = env_dtype
        multiprocessing.get_context(mp_context)  # fail at init, not mid-train
        self.mp_context = mp_context
        self.dataset = dataset
        self.global_batch_size = int(batch_size)
        self.shuffle = shuffle
        self.seed = seed
        self.drop_last = drop_last
        self.num_workers = num_workers
        self.worker_mode = worker_mode
        self.transfer_dtype = (
            np.dtype(transfer_dtype) if transfer_dtype is not None else None
        )
        self._pool = BatchBufferPool(ring_buffers)
        # FIFO of yielded-but-unreleased leases: release_oldest() recycles
        # in yield order (the DevicePrefetcher transfers batches in that
        # same order).  Bounded — a consumer that never releases must not
        # pin every buffer ever yielded — but drops are COUNTED, not
        # silent: each dropped lease swallows one future release, so the
        # FIFO pairing of releases to leases can never shift onto a
        # batch the consumer still holds.
        self._outstanding: collections.deque = collections.deque()
        self._outstanding_cap = max(8, 4 * ring_buffers)
        self._dropped_leases = 0
        self._lease_lock = threading.Lock()
        # bumped per __iter__: release_oldest never recycles a lease from
        # an abandoned earlier iteration (whose consumer may still hold
        # the views) — it forgets them instead
        self._iter_gen = 0
        self._proc_pool = None
        # (epoch, batches_yielded) as ONE tuple: the position is read from
        # the DevicePrefetcher's background thread while set_epoch /
        # load_state_dict may run on the main thread, and a single
        # attribute assignment is atomic under the GIL — two separate
        # attributes could be observed torn (new epoch, old position).
        self._pos = (0, 0)
        self._resume_offset = 0  # batches to skip on the next __iter__
        if num_workers and worker_mode == "process":
            # Fork NOW, from the constructing (main) thread — a lazy fork
            # from DevicePrefetcher's background thread while jax/XLA
            # threads hold locks is the classic child-deadlock setup.
            self._process_pool()
        self.process_index = (
            rt.process_index() if process_index is None else process_index
        )
        self.process_count = (
            rt.process_count() if process_count is None else process_count
        )
        if self.global_batch_size % self.process_count:
            raise ValueError(
                f"global batch size {batch_size} not divisible by "
                f"{self.process_count} processes"
            )
        self.local_batch_size = self.global_batch_size // self.process_count

    def set_epoch(self, epoch: int) -> None:
        """DistributedSampler.set_epoch parity — changes the shuffle order.

        Also rewinds the position counters: a ``state_dict`` taken after
        ``set_epoch(e)`` but before the epoch's first batch must read
        "epoch e, nothing consumed", not the previous epoch's end.
        (``load_state_dict`` re-applies its offset after calling this.)
        """
        self._pos = (int(epoch), 0)
        self._resume_offset = 0
        if hasattr(self.dataset, "set_epoch"):
            self.dataset.set_epoch(epoch)

    @property
    def _epoch(self) -> int:
        return self._pos[0]

    @property
    def _batches_yielded(self) -> int:
        """Within the current epoch (the resume point)."""
        return self._pos[1]

    def state_dict(self) -> dict:
        """Deterministic mid-epoch resume point (mosaicml-streaming's
        ``StreamingDataset.state_dict`` capability, surfaced at the loader
        where tpuframe's iteration order lives).

        Returns the position plus an iteration-order fingerprint — the
        permutation is a pure function of (seed, epoch, topology), so the
        fingerprint is what makes the position transferable.  Save it
        next to the model checkpoint; after a crash, ``load_state_dict``
        + iterate continues with the very next batch, no replayed or
        skipped samples.  One live iterator per loader is assumed
        (concurrent iterators would share this counter).  NOTE: when the
        loader is consumed through :class:`DevicePrefetcher`, take the
        snapshot from the *prefetcher's* ``state_dict()`` — the loader's
        own counter runs up to ``depth`` batches ahead of what training
        actually consumed.
        """
        # NOTE: no process_index — the position is rank-uniform (every
        # process consumes the same batch count in lockstep), so rank 0's
        # snapshot must restore cleanly on every other process (the
        # checkpoint meta is written once, globally)
        epoch, batches = self._pos  # one read: epoch/position stay paired
        return {
            "epoch": epoch,
            "batches_yielded": batches,
            "global_batch_size": self.global_batch_size,
            "process_count": self.process_count,
            "dataset_len": len(self.dataset),
            "seed": self.seed,
            "shuffle": self.shuffle,
            "drop_last": self.drop_last,
        }

    def load_state_dict(self, state: dict) -> None:
        """Resume from :meth:`state_dict`: the next ``__iter__`` skips the
        already-consumed batches by index arithmetic (no fetch/decode of
        skipped samples) and continues the same (seed, epoch) order.

        Raises ``ValueError`` when the snapshot's iteration-order
        fingerprint doesn't match this loader — a position saved under a
        different batch size, topology, seed, or dataset indexes a
        different permutation, and resuming there would silently replay
        and skip samples.
        """
        mine = self.state_dict()
        mismatched = {
            k: (state.get(k), mine[k])
            for k in ("global_batch_size", "process_count",
                      "dataset_len", "seed", "shuffle", "drop_last")
            if k in state and state[k] != mine[k]
        }
        if mismatched:
            raise ValueError(
                "loader state_dict fingerprint mismatch (saved != current): "
                + ", ".join(f"{k}: {a!r} != {b!r}"
                            for k, (a, b) in mismatched.items())
            )
        offset = int(state["batches_yielded"])
        if not 0 <= offset <= len(self):
            # negative offsets would wrap python slices and silently
            # replay end-of-epoch batches
            raise ValueError(
                f"batches_yielded {offset} outside [0, {len(self)}]"
            )
        self.set_epoch(int(state["epoch"]))
        self._resume_offset = offset
        self._pos = (int(state["epoch"]), offset)

    def _fetch_one(self, idx: int):
        """One sample, with decode/IO failures downgraded to a
        :class:`_BadSample` sentinel (thread/inline path; the process
        pool does the same inside ``_pool_get``)."""
        try:
            return self.dataset[idx]
        except _SKIPPABLE_SAMPLE_ERRORS as e:
            return _BadSample(idx, f"{type(e).__name__}: {e}")

    def release_oldest(self, device_arrays=None) -> bool:
        """Recycle the oldest outstanding batch's ring buffers (FIFO).

        Call once per consumed batch, after nothing reads its numpy
        views anymore — the :class:`DevicePrefetcher` calls this right
        after the host->device copy of that batch completes (batches are
        transferred in yield order, so FIFO release matches).
        ``device_arrays`` (the jax pytree built from the batch) lets the
        pool verify the buffers don't alias live device memory before
        reuse.  Returns True when a buffer actually re-entered the pool.
        """
        with self._lease_lock:
            if self._dropped_leases:
                # the lease this release pairs with fell off the bounded
                # FIFO: swallow the release so later ones stay aligned
                # with their own leases
                self._dropped_leases -= 1
                return False
            try:
                gen, lease = self._outstanding.popleft()
            except IndexError:
                return False
        if gen != self._iter_gen:
            # stale lease from an abandoned iteration: its views may
            # still be held by the old consumer — and this release was
            # for that iteration's batch anyway.  Forget both; walking
            # on into current-generation leases here could recycle a
            # buffer whose own H2D hasn't happened yet.
            return False
        return self._pool.release(lease, device_arrays)

    def _per_process_count(self) -> int:
        n = len(self.dataset)
        if not self.drop_last and n % self.process_count:
            return n // self.process_count + 1
        return n // self.process_count

    def _indices(self, epoch: int) -> tuple[np.ndarray, np.ndarray]:
        """This process's (indices, genuine) for ``epoch`` — genuine=False
        marks wrap-pad duplicates added only to equalize per-process
        counts.  Takes the epoch explicitly so ``__iter__``'s captured
        epoch seeds the permutation AND tags every position write — one
        consistent epoch even if set_epoch races on another thread."""
        n = len(self.dataset)
        order = (
            np.random.default_rng(self.seed * 1_000_003 + epoch).permutation(n)
            if self.shuffle
            else np.arange(n)
        )
        genuine = np.ones(n, bool)
        # Equal per-process share, DistributedSampler-style wrap-around pad —
        # but padded duplicates are flagged so eval never double-counts them.
        per_proc = self._per_process_count()
        total = per_proc * self.process_count
        if total > n:
            # np.resize repeats cyclically, so the pad stays correct even when
            # it exceeds the dataset size (tiny dataset, many processes).
            order = np.resize(order, total)
            genuine = np.zeros(total, bool)
            genuine[:n] = True
        else:
            order, genuine = order[:total], genuine[:total]
        sl = slice(self.process_index, None, self.process_count)
        return order[sl], genuine[sl]

    def __len__(self) -> int:
        per_proc = self._per_process_count()
        if self.drop_last:
            return per_proc // self.local_batch_size
        return -(-per_proc // self.local_batch_size)

    def _process_pool(self):
        """Persistent fork pool, created on first use, reused across epochs
        (recreating per epoch would pay fork + page-fault warmup each time)."""
        if self._proc_pool is None:
            ctx = multiprocessing.get_context(self.mp_context)
            self._proc_pool = ctx.Pool(
                self.num_workers, initializer=_pool_init, initargs=(self.dataset,)
            )
        return self._proc_pool

    def close(self) -> None:
        """Release the persistent process pool (no-op otherwise)."""
        if self._proc_pool is not None:
            self._proc_pool.terminate()
            self._proc_pool.join()
            self._proc_pool = None

    def __del__(self):  # best-effort: pools must not outlive the loader
        try:
            self.close()
        except Exception:
            pass

    def __iter__(self) -> Iterator[tuple]:
        # generation bump at ITERATOR CREATION (not first next()): any
        # outstanding lease of a previous iteration is stale from this
        # moment, so a late release from its abandoned consumer can never
        # recycle buffers into this iteration
        self._iter_gen += 1
        return self._iter_batches(self._iter_gen)

    def _iter_batches(self, gen: int) -> Iterator[tuple]:
        # the generator captures ITS epoch once and pairs it with every
        # position write — a concurrent set_epoch on another thread can
        # replace _pos wholesale but never produce a mixed pair
        epoch = self._epoch
        indices, genuine = self._indices(epoch)
        nb_full = len(indices) // self.local_batch_size
        tail = len(indices) % self.local_batch_size

        pool = None
        if self.num_workers and self.worker_mode == "process":
            # chunked map: one IPC round per worker-chunk, not per item
            ppool = self._process_pool()
            chunk = max(1, self.local_batch_size // (self.num_workers * 2))
            fetch = lambda idxs: ppool.map(  # noqa: E731
                _pool_get, [(int(i), epoch) for i in idxs], chunksize=chunk
            )
        elif self.num_workers:
            pool = ThreadPoolExecutor(self.num_workers)
            fetch = lambda idxs: list(  # noqa: E731
                pool.map(lambda i: self._fetch_one(int(i)), idxs)
            )
        else:
            # plain Python ints: torch-style datasets (the reference's
            # map-style Dataset contract) often reject numpy indices
            fetch = lambda idxs: [self._fetch_one(int(i)) for i in idxs]  # noqa: E731
        # mid-epoch resume: skip already-consumed batches arithmetically
        # (the permutation is (seed, epoch)-deterministic, so no fetch of
        # skipped samples is needed); a fresh epoch starts at 0
        start = min(self._resume_offset, len(self))
        self._resume_offset = 0
        self._pos = (epoch, start)
        tele = get_telemetry()

        # bad-sample quarantine: corrupt records are skipped-and-counted
        # (one `data/bad_sample` event each) up to a per-epoch cap —
        # one poisoned shard degrades the epoch instead of killing it,
        # while a systematically broken dataset still raises fast
        from tpuframe.fault.health import _env_int

        max_bad = _env_int("TPUFRAME_MAX_BAD_SAMPLES", 8)
        bad_count = 0

        def screen(items: list, gen_rows, batch_idx: int) -> tuple:
            """Drop :class:`_BadSample` sentinels (and their genuine
            flags), enforcing the cap; ``assemble``'s tail-pad refills
            the shortened batch by cycling the surviving good samples.
            On the eval path (``drop_last=False``) the pad rows carry a
            ``valid=False`` mask; on the train path they are UNMASKED
            repeats — bounded by the cap (a handful of duplicated
            samples per epoch), because growing a weight column
            mid-epoch would change the pinned train batch signature."""
            nonlocal bad_count
            bad = [it for it in items if isinstance(it, _BadSample)]
            if not bad:
                return items, gen_rows
            for b in bad:
                bad_count += 1
                tele.registry.counter("data/bad_samples").inc()
                tele.event(
                    "data/bad_sample",
                    index=b.index, error=b.error[:300], batch=batch_idx,
                )
            if bad_count > max_bad:
                raise RuntimeError(
                    f"{bad_count} bad sample(s) this epoch exceed "
                    f"TPUFRAME_MAX_BAD_SAMPLES={max_bad}; the dataset is "
                    f"poisoned beyond skip-and-count (last: sample "
                    f"{bad[-1].index}: {bad[-1].error})"
                )
            good = [
                (it, bool(g))
                for it, g in zip(items, gen_rows)
                if not isinstance(it, _BadSample)
            ]
            if not good:
                raise RuntimeError(
                    f"every sample in batch {batch_idx} was bad "
                    f"(last: sample {bad[-1].index}: {bad[-1].error}); "
                    "nothing left to assemble"
                )
            return [it for it, _ in good], np.asarray(
                [g for _, g in good], bool
            )

        def assemble(items, gen_rows) -> tuple:
            """Write fetched samples into a leased ring buffer — the
            zero-allocation replacement for per-batch ``np.stack``."""
            n = len(items)
            first = np.asarray(items[0][0])
            first_lb = np.asarray(items[0][1])
            dtype = self.transfer_dtype or first.dtype
            lease = self._pool.acquire(
                self.local_batch_size, first.shape, dtype,
                with_valid=not self.drop_last,
                label_shape=first_lb.shape, label_dtype=first_lb.dtype,
            )
            for i, (im, lb) in enumerate(items):
                # same_kind: a float sample under transfer_dtype="uint8"
                # raises instead of silently truncating to garbage
                np.copyto(lease.images[i], im, casting="same_kind")
                lease.labels[i] = lb
            for i in range(n, self.local_batch_size):  # ragged-tail pad
                # cycle over the good samples: under drop_last the pad is
                # UNMASKED (adding a weight column mid-epoch would change
                # the train batch signature the compile spine pinned), so
                # spreading beats weighting one sample k+1 times
                src = items[i % n]
                np.copyto(lease.images[i], src[0], casting="same_kind")
                lease.labels[i] = src[1]
            if lease.valid is None:
                out = (lease.images, lease.labels)
            else:
                lease.valid[:n] = gen_rows
                lease.valid[n:] = False
                out = (lease.images, lease.labels, lease.valid)
            with self._lease_lock:
                self._outstanding.append((gen, lease))
                if len(self._outstanding) > self._outstanding_cap:
                    self._outstanding.popleft()
                    self._dropped_leases += 1
            return out

        def assembled(sl: slice, b: int) -> tuple:
            # the span takes its train step from the prefetcher's
            # data/prefetch_fetch span around this pull, where there is one
            with tele.span("data/assemble", cpu=True, batch=b) as sp:
                allocs0 = self._pool.allocs
                out = assemble(*screen(fetch(indices[sl]), genuine[sl], b))
                # did the ring have to allocate (steady state: never)?
                sp.attrs["fresh_alloc"] = self._pool.allocs != allocs0
            return out

        try:
            for b in range(start, nb_full):
                out = assembled(slice(b * self.local_batch_size,
                                      (b + 1) * self.local_batch_size), b)
                # count BEFORE the yield: a generator suspends AT the
                # yield, so a post-yield update would lag one batch behind
                # what the caller has already consumed
                self._pos = (epoch, b + 1)
                yield out
            if tail and not self.drop_last and start <= nb_full:
                out = assembled(slice(nb_full * self.local_batch_size, None),
                                nb_full)
                self._pos = (epoch, nb_full + 1)
                yield out
        finally:
            if pool:
                pool.shutdown(wait=False)


class DevicePrefetcher:
    """Wrap a host-batch iterable into global device Arrays, ``depth`` in flight.

    Each host batch (this process's shard) becomes one global jax.Array sharded
    over the mesh's (data, fsdp) axes via
    ``jax.make_array_from_process_local_data`` — the multi-host-safe way to
    assemble a global batch.  A background thread keeps the pipeline full so
    H2D copies overlap the train step (double/triple-buffering per ``depth``;
    depth=2 default, depth=3 hides longer transfer tails).

    Ring-buffer handoff: when the upstream produces pooled ring-buffer
    batches (:class:`DataLoader`), the worker recycles each batch's
    buffers the moment its device copy *completes* (``recycler`` —
    auto-detected from the wrapped iterable's ``release_oldest``), so
    steady-state host allocations are zero.  The handoff is
    donation-safe by construction: pooled buffers are allocated off
    XLA's zero-copy alignment grain and re-verified against the device
    arrays before reuse, so a recycled buffer can never alias live
    device data.
    """

    _DONE = object()

    def __init__(self, it: Any, depth: int = 2, sharding=None,
                 track_loader: "DataLoader | None" = None,
                 recycler: Any = None, first_step: int | None = None):
        self.it = it
        # the train step the first batch feeds (the Trainer says); the
        # worker counts on from there and tags its spans with it
        self.first_step = first_step
        if sharding is None:
            sharding = rt.current_runtime().data_sharding()
        self.sharding = sharding
        self.depth = max(1, depth)
        if recycler is None and hasattr(it, "release_oldest"):
            recycler = it
        self.recycler = recycler
        # Mid-epoch-resume position of the batch most recently handed to
        # the CONSUMER.  The wrapped loader's own counter runs up to
        # ``depth`` batches ahead (the background thread prefetches), so
        # each queue item carries the loader snapshot taken at pull time
        # and the position only advances when the consumer receives it.
        self.track_loader = track_loader
        self._position = (
            track_loader.state_dict() if track_loader is not None else None
        )

    def state_dict(self) -> dict:
        """Resume point of the last batch the consumer actually received
        (see :meth:`DataLoader.state_dict`; requires ``track_loader=``)."""
        if self.track_loader is None:
            raise ValueError(
                "DevicePrefetcher was built without track_loader=; no "
                "resume position to report"
            )
        return dict(self._position)

    #: XLA's CPU client zero-copies SMALL aligned host buffers at a finer
    #: (16-byte) grain than large ones, so a tiny pooled leaf — labels,
    #: valid masks — can alias its device shards even from a misaligned
    #: base (a shard boundary inevitably lands on an aligned address).
    #: Leaves at or under this size get a private copy before device_put:
    #: the copy is what the device references, so the pooled buffer stays
    #: recyclable.  Bytes-trivial; image buffers are far above it.
    _SMALL_LEAF_BYTES = 4096

    def _put(self, batch):
        """Any pytree of host arrays (tuple / dict / nested) -> global Arrays."""

        def to_global(x):
            x = np.asarray(x)
            if x.nbytes <= self._SMALL_LEAF_BYTES:
                x = np.array(x)  # private copy: see _SMALL_LEAF_BYTES
            return jax.make_array_from_process_local_data(
                self.sharding_for(x), x
            )

        return jax.tree.map(to_global, batch)

    def sharding_for(self, x: np.ndarray):
        # batch-dim sharding only; trailing dims replicated
        spec = list(self.sharding.spec) + [None] * (x.ndim - len(self.sharding.spec))
        return jax.sharding.NamedSharding(
            self.sharding.mesh, jax.sharding.PartitionSpec(*spec)
        )

    def __iter__(self):
        q: queue.Queue = queue.Queue(maxsize=self.depth)
        err: list[BaseException] = []
        stop = threading.Event()

        def put(item) -> bool:
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        def worker():
            # data/prefetch_fetch stays emit=False (histogram + live span
            # stack only); data/h2d DOES emit — one JSONL event per batch
            # with its wall-clock interval is exactly what proves the
            # transfer of batch k+1 overlapped the step of batch k.
            tele = get_telemetry()
            try:
                it = iter(self.it)
                n = 0
                while True:
                    step = None if self.first_step is None else self.first_step + n
                    with tele.span("data/prefetch_fetch", emit=False, step=step):
                        try:
                            batch = next(it)
                        except StopIteration:
                            break
                    # snapshot right after the pull: this is the position
                    # of exactly the batch being enqueued (pulling may
                    # advance the loader by several batches, e.g. the
                    # trainer's grad-accum grouping)
                    snap = (
                        self.track_loader.state_dict()
                        if self.track_loader is not None
                        else None
                    )
                    with tele.span("data/h2d", cpu=True, batch=n, step=step):
                        device_batch = self._put(batch)
                        # wait for the copy itself (NOT any consumer
                        # compute): after this the host buffers are free
                        # to recycle, and span/data/h2d measures the real
                        # transfer, not the dispatch
                        jax.block_until_ready(device_batch)
                    if self.recycler is not None:
                        self.recycler.release_oldest(device_batch)
                    n += 1
                    if not put((device_batch, snap)):
                        return  # consumer went away
            except BaseException as e:  # propagate to consumer
                err.append(e)
            finally:
                put(self._DONE)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is self._DONE:
                    if err:
                        raise err[0]
                    return
                batch, snap = item
                if snap is not None:
                    self._position = snap
                yield batch
        finally:
            # Early consumer exit (break / GeneratorExit): release the worker
            # so it doesn't pin `depth` device batches forever.
            stop.set()
            while not q.empty():
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
