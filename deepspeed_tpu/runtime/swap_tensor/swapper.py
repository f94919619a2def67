"""Tensor swappers — rebuild of deepspeed/runtime/swap_tensor/
(partitioned_param_swapper.py:36, optimizer_utils.py:118,
pipelined_optimizer_swapper.py): NVMe residency for optimizer state and
parameters, powered by the native async-IO library (csrc/aio.cpp).

Layout: one file per (tensor, field) under ``<nvme_path>/zero_swap_<pid>/``;
double-buffered reads (``prefetch`` starts the async read of the next
tensor while the caller consumes the current one — the reference's
pipelined swapper overlap, pipelined_optimizer_swapper.py:60).

Pipelined schedules (PR 5, the reference's pipeline_read/pipeline_write
knobs made real): the param and optimizer swappers each own a SECOND aio
handle dedicated to write-behind — ``aio_handle_wait`` drains a whole
handle, so reads and writes must never share one — plus a bounded pool
of host staging buffers. A write-behind submission copies the leaf into
a pool buffer and returns immediately; the buffer then doubles as a
byte-exact cache of the file, so the next swap-in of a recently written
leaf is a host memcpy instead of a disk read. The drain fence
(``drain_writes``) runs before any pending leaf is re-read FROM DISK —
cache-served leaves need no fence because the staged bytes are the
authoritative copy the file was written from.

Swap files are preallocated (``ftruncate`` + ``posix_fallocate``) and
kept open without ``O_TRUNC`` across steps, so steady-state writes reuse
extents instead of reallocating them, and swap-in issues an
``fadvise(WILLNEED)`` readahead pass before reading — the first-epoch
read path runs at steady-state bandwidth instead of the 5x-slower
cold-file rate.

All swap-path telemetry is sync-free (host wall timers + byte counters
into the process registry): ``swap/bytes_read``, ``swap/bytes_written``,
``swap/cache_hit_bytes`` counters, the ``swap/staging_bytes`` occupancy
gauge, and the per-step I/O-blocked seconds surfaced via
``take_stall_s()`` (the engine folds them into the ``swap/stall_s``
histogram).
"""

import os
import shutil
import time
import weakref

import numpy as np

from deepspeed_tpu.utils.logging import logger


def _make_aio_handle(aio_config):
    """One construction point for the aio handle's tuning knobs — every
    swapper shares the same defaults, and the ``aio.o_direct`` knob
    reaches all four handle sites (park, read window, prefetch,
    write-behind) plus the snapshotter through here."""
    from deepspeed_tpu.ops.native.aio import AsyncIOHandle
    cfg = aio_config
    return AsyncIOHandle(
        block_size=getattr(cfg, "block_size", 1 << 20),
        queue_depth=getattr(cfg, "queue_depth", 8),
        single_submit=getattr(cfg, "single_submit", False),
        overlap_events=getattr(cfg, "overlap_events", True),
        thread_count=getattr(cfg, "thread_count", 2),
        o_direct=getattr(cfg, "o_direct", False))


def _aligned_empty(nbytes):
    from deepspeed_tpu.ops.native.aio import aligned_empty
    return aligned_empty(nbytes)


def _fd_is_direct(fd):
    from deepspeed_tpu.ops.native.aio import fd_is_direct
    return fd_is_direct(fd)


def _fsync_dir(path):
    dfd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


def sweep_stale_pid_dirs(nvme_path, prefix):
    """SIGKILL leaves pid-scoped scratch dirs behind — the weakref
    finalizers that normally rmtree them never run (ISSUE 20 fix).
    Reclaim any ``<prefix>_<pid>`` sibling whose pid is dead before
    creating ours; a pid we cannot signal (EPERM: alive, someone
    else's) is left alone."""
    try:
        names = os.listdir(nvme_path)
    except OSError:
        return []
    swept = []
    for name in names:
        if not name.startswith(prefix + "_"):
            continue
        tail = name.rsplit("_", 1)[-1]
        if not tail.isdigit() or int(tail) == os.getpid():
            continue
        try:
            os.kill(int(tail), 0)
        except ProcessLookupError:
            shutil.rmtree(os.path.join(nvme_path, name),
                          ignore_errors=True)
            swept.append(name)
        except OSError:
            continue
    if swept:
        logger.info("reclaimed %d stale swap scratch dir(s) under %s: %s",
                    len(swept), nvme_path, ", ".join(sorted(swept)))
    return swept


def _registry():
    from deepspeed_tpu.telemetry import default_registry
    return default_registry()


def _recorder():
    from deepspeed_tpu.telemetry import default_recorder
    return default_recorder()


def _close_fds_and_rm(path, fds, remove):
    """weakref.finalize target — must not reference the swapper. ``fds``
    is the LIVE dict (cleared by release(), so a later GC finalize never
    double-closes recycled fd numbers)."""
    for fd in list(fds.values()):
        try:
            os.close(fd)
        except OSError:
            pass
    fds.clear()
    if remove:
        shutil.rmtree(path, ignore_errors=True)


class TensorSwapper:
    """Owns the swap directory + aio handle; swaps named fp32 buffers."""

    def __init__(self, nvme_path, aio_config=None, sub_dir="zero_swap"):
        sweep_stale_pid_dirs(nvme_path, sub_dir)
        self.dir = os.path.join(nvme_path, f"{sub_dir}_{os.getpid()}")
        os.makedirs(self.dir, exist_ok=True)
        self.handle = _make_aio_handle(aio_config)
        self._pending_read = None  # (name, buffer, fd)
        # swap files are pid-scoped scratch — reclaim the NVMe space when
        # the swapper is garbage-collected or the process exits (a weakref
        # finalizer, unlike atexit.register(self.release), does not pin
        # the instance and its staging buffers for the process lifetime)
        self._finalizer = weakref.finalize(
            self, shutil.rmtree, self.dir, ignore_errors=True)

    def _path(self, name):
        return os.path.join(self.dir, f"{name}.swp")

    def _drain_pending(self):
        """Wait for the in-flight prefetch (if any) and close its fd."""
        if self._pending_read is None:
            return None, None
        name, buf, fd = self._pending_read
        self._pending_read = None
        try:
            self.handle.wait()
        finally:
            self.handle.close(fd)
        return name, buf

    def swap_out(self, name, array):
        assert array.dtype == np.float32 and array.flags["C_CONTIGUOUS"]
        # drain first: the handle's wait/error accounting is per-batch, so a
        # sync op must not share the handle with an in-flight prefetch (it
        # would absorb the prefetch's completion and error status)
        self._drain_pending()
        self.handle.sync_pwrite(array, self._path(name))

    def swap_in(self, name, out_array):
        if self._pending_read and self._pending_read[0] == name:
            _, buf = self._drain_pending()
            if buf is not out_array:
                np.copyto(out_array, buf)
            return out_array
        self._drain_pending()
        self.handle.sync_pread(out_array, self._path(name))
        return out_array

    def prefetch(self, name, out_array):
        """Start the async read of `name`; a following swap_in(name) waits
        and consumes it (double buffering)."""
        self._drain_pending()
        fd = self.handle.open(self._path(name), False)
        self.handle.async_pread(out_array, fd)
        self._pending_read = (name, out_array, fd)

    def release(self):
        try:
            self._drain_pending()
        except Exception:
            pass
        shutil.rmtree(self.dir, ignore_errors=True)


class _StagingArena:
    """Staging buffers for the swap path served from one contiguous arena
    (reference: stage 3 backs its fp16 partitions with the
    ContiguousMemoryAllocator and defragments on demand, stage3.py:1073).
    Live buffers are never moved — an async read may be in flight into
    them — so the arena only defragments when nothing is live; requests it
    cannot place contiguously fall back to a plain numpy allocation."""

    def __init__(self, slots=4, aligned=False):
        self.arena = None
        self._live = 0
        self._max_numel = 0
        # sized for ``slots`` leaves of the largest size seen — the
        # double-buffer minimum is 4 (2 Adam fields x 2 leaves in flight);
        # pipelined write-behind asks for more
        self._slots = max(4, int(slots))
        # page-aligned sub-allocations (ISSUE 20): slices handed to an
        # O_DIRECT aio handle start on page boundaries, so the aligned
        # body of every transfer submits zero-copy
        self._aligned = bool(aligned)

    def _align_elems(self):
        if not self._aligned:
            return 1
        from deepspeed_tpu.ops.native.aio import ALIGNMENT
        return ALIGNMENT // np.dtype(np.float32).itemsize

    def take(self, shape):
        """Returns (tid_or_None, float32 array of `shape`)."""
        from deepspeed_tpu.runtime.zero.contiguous_memory_allocator import (
            ContiguousMemoryAllocator)
        numel = int(np.prod(shape))
        # grow to the LARGEST leaf seen whenever idle, so heterogeneous
        # leaf sizes converge on an arena that fits everything after one
        # full fetch/store cycle (first-leaf sizing would permanently
        # exile every bigger leaf to the numpy fallback)
        self._max_numel = max(self._max_numel, numel)
        ae = self._align_elems()
        slot_numel = -(-self._max_numel // ae) * ae
        if self.arena is None or (
                self._live == 0
                and self.arena.size < self._slots * slot_numel):
            self.arena = ContiguousMemoryAllocator(
                self._slots * slot_numel, np.float32, align_elems=ae)
        alloc = -(-numel // ae) * ae
        can_place = self.arena._largest_free() >= alloc or self._live == 0
        if not can_place or alloc > self.arena.total_free:
            if self._aligned:
                from deepspeed_tpu.ops.native.aio import aligned_empty
                flat = aligned_empty(numel * 4).view(np.float32)
                return None, flat.reshape(shape)
            return None, np.empty(shape, np.float32)
        tid, view = self.arena.allocate_tensor(numel)
        self._live += 1
        return tid, view.reshape(shape)

    def give(self, tid):
        if tid is not None:
            self.arena.release_tensor(tid)
            self._live -= 1


class PartitionedParamSwapper:
    """NVMe-resident model parameters — the ZeRO-Infinity parameter tier
    (reference swap_tensor/partitioned_param_swapper.py:36). Compute-dtype
    param leaves rest in one file each; around every step they stream

        disk --aio read--> bounded staging (buffer_count) --device_put--> HBM

    with the disk read of leaf group k+1 overlapping the h2d put of group
    k (sliding read window over ``buffer_count`` staging slots), and after
    the update HBM → staging → disk. Host RSS for parameters is therefore
    bounded by ``buffer_count`` read slots + ``buffer_count`` write-behind
    buffers of the largest leaf regardless of model size — the reference's
    pinned-buffer-count bound.

    ``pipeline_write`` turns the post-step park into write-behind: leaves
    are copied into pool buffers and the aio writes run on a dedicated
    handle while the caller proceeds (the swap-out of step N overlaps
    whatever follows — the optimizer tail, telemetry, and the next step's
    swap-in). ``drain_writes()`` is the durability fence; it runs
    automatically before any pending leaf would be re-read from disk.
    The pool buffers double as a byte cache of the just-written files, so
    the next swap-in serves recently written leaves from host memory.
    """

    def __init__(self, nvme_path, aio_config=None, sub_dir=None,
                 durable=False, pipeline_read=False, pipeline_write=False,
                 buffer_count=2, registry=None, fsync=False):
        """``sub_dir``/``durable``: by default the swap files are
        pid-scoped SCRATCH (reclaimed on GC/exit). A durable tier (the
        ZeRO-Infinity at-rest files, runtime/zero/infinity.py) passes a
        stable sub_dir and durable=True: files survive the process and
        carry a meta.json sidecar so a fresh process can restore."""
        if sub_dir is None:
            sweep_stale_pid_dirs(nvme_path, "param_swap")
        self.dir = os.path.join(
            nvme_path, sub_dir or f"param_swap_{os.getpid()}")
        os.makedirs(self.dir, exist_ok=True)
        self.handle = _make_aio_handle(aio_config)
        self._aio_config = aio_config
        self.meta = {}            # leaf idx -> (shape, numpy dtype)
        self.pipeline_read = bool(pipeline_read)
        self.pipeline_write = bool(pipeline_write)
        self.buffer_count = max(2, int(buffer_count))
        self._staging = [None] * (self.buffer_count if pipeline_read else 2)
        self._durable = durable
        # -- write-behind state (pipeline_write) ---------------------------
        self._whandle = None      # dedicated aio handle, lazily built
        self._wpool = []          # staging buffers (np.uint8)
        self._wbusy = set()       # pool indices with an in-flight write
        self._cache = {}          # leaf idx -> (pool idx, nbytes)
        self._pending = set()     # leaf idx with a not-yet-drained write
        self._wfds = {}           # leaf idx -> preallocated write fd
        self._fsizes = {}         # leaf idx -> preallocated byte size
        # fsync-fenced durability (ISSUE 7 satellite): without it the
        # swap files ride the guest page cache and the drain fence only
        # orders THIS process's reads after its writes; with it the
        # fence is a real durability barrier — elastic snapshots that
        # copy parked files require this mode on the param tier
        self.fsync = bool(fsync)
        self._stall_s = 0.0
        self._registry = registry
        self._finalizer = weakref.finalize(
            self, _close_fds_and_rm, self.dir, self._wfds,
            remove=not durable)

    def _path(self, i):
        return os.path.join(self.dir, f"param_{i}.swp")

    def _meta_path(self):
        return os.path.join(self.dir, "meta.json")

    def save_meta(self):
        import json
        with open(self._meta_path(), "w") as f:
            json.dump({str(i): [list(s), str(np.dtype(d))]
                       for i, (s, d) in self.meta.items()}, f)

    def load_meta(self):
        """Restore leaf metadata written by a previous process's
        write_all (durable tiers only)."""
        import json
        with open(self._meta_path()) as f:
            raw = json.load(f)
        self.meta = {int(i): (tuple(s), np.dtype(d))
                     for i, (s, d) in raw.items()}
        return self.meta

    # -- telemetry (sync-free: host counters/timers only) ------------------
    def _reg(self):
        if self._registry is None:
            self._registry = _registry()
        return self._registry

    def take_stall_s(self):
        """I/O-blocked host seconds accumulated since the last call —
        time the caller's thread actually waited on disk (sync ops +
        drain fences), NOT time I/O spent overlapped with other work."""
        s, self._stall_s = self._stall_s, 0.0
        return s

    def _timed_wait(self, handle):
        t0 = time.perf_counter()
        try:
            handle.wait()
        finally:
            self._stall_s += time.perf_counter() - t0

    def _staging_bytes(self):
        return sum(b.nbytes for b in self._wpool) + sum(
            b.nbytes for b in self._staging if b is not None)

    # -- file lifecycle: preallocated, no O_TRUNC churn --------------------
    def _write_fd(self, i, nbytes):
        """Cached write fd for leaf ``i``'s file, preallocated to its
        I/O size: steady-state writes reuse extents (no per-step
        truncate/alloc). Buffered mode preallocates byte-exact; under
        O_DIRECT the physical size rounds up to the page (aligned
        extents — transfer lengths must be aligned, so readers request
        the rounded length and slice the exact bytes via ``meta``)."""
        fd = self._wfds.get(i)
        if fd is None:
            fd = self.handle.open_fd(self._path(i),
                                     os.O_WRONLY | os.O_CREAT)
            self._wfds[i] = fd
        alloc = self.handle.io_nbytes(nbytes)
        if self._fsizes.get(i) != alloc:
            os.ftruncate(fd, alloc)
            try:
                os.posix_fallocate(fd, 0, alloc)
            except OSError:
                pass  # fs without fallocate: sparse until first write
            if self.fsync and _fd_is_direct(fd):
                # the one metadata fsync this file needs: the direct
                # writes themselves bypass the cache, but the size/
                # extent change from this preallocation does not
                os.fsync(fd)
            self._fsizes[i] = alloc
        return fd

    def _readahead(self, indices):
        """fadvise(WILLNEED) the files about to be read — kernel
        readahead fills the page cache while earlier leaves process, so
        the first epoch reads at steady-state bandwidth (the earlier
        first_read_mbps=298-vs-1640 fix). Under active O_DIRECT there
        is no page cache to warm — the pass would be a pure syscall tax
        per file per window, so it is gated off entirely."""
        if self.handle.direct_active:
            return
        for i in indices:
            try:
                fd = os.open(self._path(i), os.O_RDONLY)
            except OSError:
                continue
            try:
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_WILLNEED)
            except OSError:
                pass
            finally:
                os.close(fd)

    @staticmethod
    def _as_bytes(arr):
        return np.ascontiguousarray(arr).view(np.uint8).reshape(-1)

    def write_all(self, leaves):
        """Initial population / re-park after checkpoint load: every leaf
        (device or host) → its preallocated file. Sync writes; called off
        the step path. Ends with a readahead pass so the first swap-in is
        not cold-file-bound. ``leaves`` may be any iterable — a generator
        keeps host residency at one leaf while parking a >RAM model
        (the nvme_xl path)."""
        self.drain_writes()
        self._cache.clear()
        n = 0
        for i, leaf in enumerate(leaves):
            arr = np.ascontiguousarray(np.asarray(leaf))  # sync-ok: d2h park
            self.meta[i] = (arr.shape, arr.dtype)
            b = self._as_bytes(arr)
            t0 = time.perf_counter()
            self.handle.sync_pwrite(b, self._write_fd(i, b.nbytes))
            self._stall_s += time.perf_counter() - t0
            self._reg().counter("swap/bytes_written").inc(b.nbytes)
            n = i + 1
        if self._durable:
            self.save_meta()
        self._readahead(range(n))

    # -- write-behind ------------------------------------------------------
    def _take_wbuf(self, nbytes):
        """A pool buffer free for a new write: not in flight, preferring
        one that backs no cache entry; evicts the oldest cache entry when
        the pool is full; drains the write handle when every buffer is
        busy. Pool is bounded at ``buffer_count`` buffers of the largest
        leaf size seen."""
        alloc = self.handle.io_nbytes(nbytes)
        backing = {idx for idx, _ in self._cache.values()}
        for attempt in range(2):
            free = [k for k in range(len(self._wpool))
                    if k not in self._wbusy and k not in backing]
            if not free and len(self._wpool) < self.buffer_count:
                self._wpool.append(_aligned_empty(alloc))
                return len(self._wpool) - 1
            if not free:
                # evict the oldest cached leaf whose buffer is idle
                for leaf, (idx, _) in list(self._cache.items()):
                    if idx not in self._wbusy:
                        del self._cache[leaf]
                        free = [idx]
                        break
            if free:
                idx = free[0]
                if self._wpool[idx].nbytes < alloc:
                    self._wpool[idx] = _aligned_empty(alloc)
                return idx
            # every buffer carries an in-flight write: fence and retry
            self.drain_writes()
            backing = {idx for idx, _ in self._cache.values()}
        raise RuntimeError("write-behind pool exhausted after drain")

    def _write_handle(self):
        if self._whandle is None:
            self._whandle = _make_aio_handle(self._aio_config)
        return self._whandle

    def write_behind(self, i, host_arr):
        """Queue the async write of leaf ``i`` (bytes are copied into a
        pool buffer — the caller may reuse ``host_arr`` immediately) and
        return without waiting. The pool copy stays registered as a byte
        cache of the file, so a following swap-in of this leaf is a host
        memcpy. ``drain_writes`` (automatic before any disk re-read of a
        pending leaf) is the durability fence."""
        arr = np.ascontiguousarray(np.asarray(host_arr))  # sync-ok: d2h park
        if i in self._pending:
            # a second write of the same leaf must not race the first on
            # the same fd (completion order is not defined)
            self.drain_writes()
        self.meta[i] = (arr.shape, arr.dtype)
        b = arr.view(np.uint8).reshape(-1)
        idx = self._take_wbuf(b.nbytes)
        buf = self._wpool[idx][:b.nbytes]
        np.copyto(buf, b)
        # submit the handle's physical length: under O_DIRECT that is
        # the aligned slice of the (page-aligned) pool buffer — a
        # zero-copy submission; buffered mode submits the exact bytes
        wlen = self.handle.io_nbytes(b.nbytes)
        if wlen > b.nbytes:
            self._wpool[idx][b.nbytes:wlen] = 0
        self._write_handle().async_pwrite(self._wpool[idx][:wlen],
                                          self._write_fd(i, b.nbytes))
        self._wbusy.add(idx)
        self._cache[i] = (idx, b.nbytes)
        self._pending.add(i)
        reg = self._reg()
        reg.counter("swap/bytes_written").inc(b.nbytes)
        reg.gauge("swap/staging_bytes").set_max(self._staging_bytes())

    def drain_writes(self):
        """Fence: wait for every in-flight write-behind. Cheap no-op when
        nothing is pending. With ``fsync`` on, the fence additionally
        makes the just-written files durable: buffered fds get a data
        fsync each; O_DIRECT fds need none (completed direct writes are
        on the device) — only the DIRENT durability remains, one
        directory fsync per drain instead of a per-file data flush."""
        if not self._pending and not self._wbusy:
            return
        n = len(self._pending)
        t0 = time.perf_counter()
        self._timed_wait(self._write_handle())
        if self.fsync:
            t1 = time.perf_counter()
            need_dirent = False
            for i in self._pending:
                fd = self._wfds.get(i)
                if fd is None:
                    continue
                if _fd_is_direct(fd):
                    need_dirent = True
                else:
                    os.fsync(fd)
            if need_dirent:
                _fsync_dir(self.dir)
            self._stall_s += time.perf_counter() - t1
        self._wbusy.clear()
        self._pending.clear()
        _recorder().record("swap_drain", leaves=n, fsync=self.fsync,
                           o_direct=self.handle.direct_active,
                           wait_s=time.perf_counter() - t0)

    @property
    def has_pending_writes(self):
        return bool(self._pending)

    def staged_leaf(self, i):
        """Snapshot-path access to a parked leaf (ISSUE 7): returns
        ``(value, source)`` where ``value`` is a host ndarray view of
        the write-behind staging cache (``source="cache"`` — valid
        only until the next park reuses the pool, so callers must
        consume/copy it before returning to training) or the swap-file
        path (``source="file"``). Callers must ``drain_writes()``
        first while ``has_pending_writes`` — a pending file is not
        whole yet. This is the supported API for reading parked bytes;
        the pool/cache internals it wraps are free to change."""
        shape, dtype = self.meta[i]
        c = self._cache.get(i)
        if c is not None:
            idx, nbytes = c
            return self._host_view(self._wpool[idx][:nbytes], i), "cache"
        return self._path(i), "file"

    # -- the swap schedule -------------------------------------------------
    def _stage(self, slot, nbytes):
        """Staging slot sized to the handle's physical I/O length —
        page-aligned mmap buffers, so O_DIRECT reads of the aligned
        slice land zero-copy (``_host_view`` slices the exact leaf
        bytes back out)."""
        need = self.handle.io_nbytes(nbytes)
        buf = self._staging[slot]
        if buf is None or buf.nbytes < need:
            self._staging[slot] = buf = _aligned_empty(need)
        return buf[:need]

    def _leaf_nbytes(self, i):
        shape, dtype = self.meta[i]
        return int(np.prod(shape or (1,))) * dtype.itemsize

    def _host_view(self, raw, i):
        shape, dtype = self.meta[i]
        return raw[:self._leaf_nbytes(i)].view(dtype).reshape(shape)

    def swap_in_device(self, shardings, order=None):
        """disk → device params; returns the list of device leaves.

        ``order`` (a permutation of leaf indices) is the per-layer swap
        schedule: leaves stream in the order compute will consume them.
        Recently write-behind-parked leaves are served from the pool
        cache (host memcpy, no disk read, no fence needed — the staged
        bytes are what the file was written from); the rest read through
        a sliding window of ``len(self._staging)`` staging slots so the
        disk read of group k+1 overlaps the host/h2d processing of
        group k."""
        import jax
        n = len(self.meta)
        outs = [None] * n
        if n == 0:
            return outs
        order = list(order) if order is not None else list(range(n))
        assert sorted(order) == list(range(n)), order
        # CPU device_put aliases host memory — a reused staging buffer
        # would corrupt the "device" params. Decide from the TARGET
        # devices (an engine may run a CPU mesh under a TPU default)
        aliases_host = shardings[0].mesh.devices.flat[0].platform == "cpu"
        reg = self._reg()

        disk = [i for i in order if i not in self._cache]
        cached = [i for i in order if i in self._cache]
        self._readahead(disk)

        # cache-served leaves process FIRST, while the write-behind of the
        # previous park is still in flight — the staged bytes are the
        # authoritative copy, so no fence is needed for them
        for i in cached:
            idx, nbytes = self._cache[i]
            view = self._host_view(self._wpool[idx][:nbytes], i)
            # non-aliasing backends: device_put copies and the end-of-
            # call fence protects the pool view until the h2d lands, so
            # only the aliasing CPU backend needs the private copy
            host = np.array(view, copy=True) if aliases_host else view
            outs[i] = jax.device_put(host, shardings[i])
            reg.counter("swap/cache_hit_bytes").inc(nbytes)

        if self._pending.intersection(disk):
            # durability fence: a pending write's file must be whole
            # before it is re-read from disk
            self.drain_writes()

        slots = len(self._staging)
        group = max(1, slots // 2)
        groups = [disk[k:k + group] for k in range(0, len(disk), group)]
        fds = {}

        def submit(gi):
            for j, i in enumerate(groups[gi]):
                slot = (gi * group + j) % slots
                buf = self._stage(slot, self._leaf_nbytes(i))
                fds[i] = self.handle.open(self._path(i), False)
                self.handle.async_pread(buf, fds[i])

        if groups:
            submit(0)

        for gi, g in enumerate(groups):
            self._timed_wait(self.handle)
            for i in g:
                self.handle.close(fds.pop(i))
            if gi + 1 < len(groups):
                if not aliases_host and gi >= 1:
                    # group gi+1 reuses group gi-1's slots: their h2d
                    # puts must have consumed the staging bytes
                    for i in groups[gi - 1]:
                        outs[i].block_until_ready()  # sync-ok: slot reuse
                submit(gi + 1)  # reads overlap the puts below
            for j, i in enumerate(g):
                slot = (gi * group + j) % slots
                arr = self._host_view(self._staging[slot], i)
                host = np.array(arr, copy=True) if aliases_host else arr
                outs[i] = jax.device_put(host, shardings[i])
                reg.counter("swap/bytes_read").inc(self._leaf_nbytes(i))
        reg.gauge("swap/staging_bytes").set_max(self._staging_bytes())
        if not aliases_host:
            for o in outs:
                o.block_until_ready()  # sync-ok: staging reuse safety
        _recorder().record(
            "swap_in", leaves=n,
            bytes_read=sum(self._leaf_nbytes(i) for i in disk),
            cache_hit_bytes=sum(self._cache[i][1] for i in cached
                                if i in self._cache))
        return outs

    def swap_in_stream(self, order=None):
        """Generator form of the read schedule for layer-streamed
        consumers (ISSUE 20's >RAM-scale path): yields ``(i, host_view)``
        in ``order`` with the same sliding staging window as
        ``swap_in_device`` but NO device materialization — host residency
        stays bounded by the staging slots no matter the model size. The
        yielded view aliases a staging slot and is valid only until the
        window advances past it (consume or copy before the next
        ``len(self._staging) // 2`` items)."""
        n = len(self.meta)
        order = list(order) if order is not None else list(range(n))
        if not order:
            return
        if self._pending.intersection(order):
            self.drain_writes()
        self._readahead([i for i in order if i not in self._cache])
        reg = self._reg()
        slots = len(self._staging)
        group = max(1, slots // 2)
        groups = [order[k:k + group] for k in range(0, len(order), group)]
        fds = {}

        def submit(gi):
            for j, i in enumerate(groups[gi]):
                slot = (gi * group + j) % slots
                buf = self._stage(slot, self._leaf_nbytes(i))
                fds[i] = self.handle.open(self._path(i), False)
                self.handle.async_pread(buf, fds[i])

        submit(0)
        for gi, g in enumerate(groups):
            self._timed_wait(self.handle)
            for i in g:
                self.handle.close(fds.pop(i))
            if gi + 1 < len(groups):
                submit(gi + 1)   # next group's reads overlap the yields
            for j, i in enumerate(g):
                slot = (gi * group + j) % slots
                reg.counter("swap/bytes_read").inc(self._leaf_nbytes(i))
                yield i, self._host_view(self._staging[slot], i)

    def swap_out_device(self, leaves, write_behind=None):
        """device params → disk; frees nothing itself (callers delete the
        device arrays after). d2h transfers for all leaves start up front
        so later copies overlap earlier writes; with ``write_behind`` the
        disk writes run asynchronously on the dedicated handle and this
        returns as soon as the d2h copies land in the pool."""
        wb = self.pipeline_write if write_behind is None else write_behind
        for leaf in leaves:
            if hasattr(leaf, "copy_to_host_async"):
                try:
                    leaf.copy_to_host_async()
                except Exception:
                    pass
        for i, leaf in enumerate(leaves):
            if wb:
                self.write_behind(i, leaf)
                continue
            if i in self._pending:
                # same-fd race guard, mirroring write_behind: a sync
                # write must not overlap an undrained async one
                self.drain_writes()
            arr = np.ascontiguousarray(np.asarray(leaf))  # sync-ok: d2h park
            self.meta[i] = (arr.shape, arr.dtype)
            b = self._as_bytes(arr)
            t0 = time.perf_counter()
            fd = self._write_fd(i, b.nbytes)
            self.handle.sync_pwrite(b, fd)
            if self.fsync and not _fd_is_direct(fd):
                os.fsync(fd)   # direct writes are on-device already
            self._stall_s += time.perf_counter() - t0
            self._cache.pop(i, None)  # staged bytes (if any) are stale
            self._reg().counter("swap/bytes_written").inc(b.nbytes)
        if self._durable:
            self.save_meta()
        _recorder().record(
            "swap_out", leaves=len(leaves), write_behind=bool(wb),
            bytes=sum(self._leaf_nbytes(i) for i in range(len(leaves))
                      if i in self.meta))

    def release(self):
        try:
            self.drain_writes()
        except Exception:
            pass
        for fd in list(self._wfds.values()):
            try:
                os.close(fd)
            except OSError:
                pass
        self._wfds.clear()   # the GC finalizer sees the emptied dict
        self._cache.clear()
        shutil.rmtree(self.dir, ignore_errors=True)


class OptimizerStateSwapper:
    """NVMe-resident Adam moments (the ZeRO-Infinity optimizer tier —
    reference optimizer_utils.py:118). Reads are double-buffered on a
    DEDICATED aio handle (the reference's PipelinedOptimizerSwapper
    overlap, pipelined_optimizer_swapper.py:60): ``prefetch(next_leaf)``
    starts the async read of the next leaf's moments while the caller
    computes on the current one. With ``pipeline_write`` the stores are
    write-behind on a third handle (the updated moments copy into a
    bounded pool and the writes overlap the next leaves' SIMD steps);
    otherwise writes stay sync on the main handle. Staging buffers come
    from a contiguous arena (_StagingArena) instead of per-call numpy
    churn."""

    FIELDS = ("exp_avg", "exp_avg_sq")

    def __init__(self, nvme_path, aio_config=None, pipeline_write=False,
                 buffer_count=2, registry=None):
        self.swapper = TensorSwapper(nvme_path, aio_config, "optimizer_swap")
        self.shapes = {}
        self._aio_config = aio_config
        self._pf_handle = _make_aio_handle(aio_config)
        self._pf = None  # (leaf_id, [bufs], [fds], [tids])
        self.pipeline_write = bool(pipeline_write)
        self.buffer_count = max(2, int(buffer_count))
        # write-behind pool sized for buffer_count leaves x 2 fields over
        # the shared arena; the arena grows to slots x largest-leaf
        self._arena = _StagingArena(
            slots=4 + (2 * self.buffer_count if pipeline_write else 0),
            aligned=getattr(aio_config, "o_direct", False))
        self._consumed = {}  # leaf_id -> [tids] handed out by fetch()
        self._wb_handle = None
        # in-flight write sources: (leaf_id, [tids], [arrays]) — the
        # array refs keep numpy-fallback staging alive until the drain
        # (the aio thread reads from those buffers)
        self._wb_live = []
        self._wb_pending = set()
        self._wb_fds = {}    # (leaf_id, field) -> preallocated write fd
        self._wb_sizes = {}
        self._registry = registry
        self._stall_s = 0.0
        self._fd_finalizer = weakref.finalize(
            self, _close_fds_and_rm, self.swapper.dir, self._wb_fds,
            remove=False)

    def _reg(self):
        if self._registry is None:
            self._registry = _registry()
        return self._registry

    def take_stall_s(self):
        s, self._stall_s = self._stall_s, 0.0
        return s

    def init_state(self, leaf_id, shape):
        self.shapes[leaf_id] = tuple(shape)
        zeros = np.zeros(shape, np.float32)
        for field in self.FIELDS:
            self.swapper.swap_out(f"{leaf_id}.{field}", zeros)

    def _drain_prefetch(self):
        if self._pf is None:
            return None
        leaf_id, bufs, fds, tids = self._pf
        self._pf = None
        t0 = time.perf_counter()
        try:
            self._pf_handle.wait()
        finally:
            self._stall_s += time.perf_counter() - t0
            for fd in fds:
                self._pf_handle.close(fd)
        return leaf_id, bufs, tids

    def _discard_prefetch(self):
        drained = self._drain_prefetch()
        if drained is not None:
            for tid in drained[2]:
                self._arena.give(tid)

    def _release_consumed(self, leaf_id):
        for tid in self._consumed.pop(leaf_id, ()):
            self._arena.give(tid)

    def drain_writes(self):
        """Fence for the write-behind stores: wait, then release the
        arena slots that backed the in-flight writes."""
        if not self._wb_live:
            return
        t0 = time.perf_counter()
        try:
            self._wb_handle.wait()
        finally:
            self._stall_s += time.perf_counter() - t0
        for _, tids, _arrs in self._wb_live:
            for tid in tids:
                self._arena.give(tid)
        self._wb_live = []
        self._wb_pending.clear()

    def prefetch(self, leaf_id):
        """Start the async read of ``leaf_id``'s moments; the matching
        fetch() consumes them without blocking on the disk."""
        if self._pf is not None and self._pf[0] == leaf_id:
            return
        if leaf_id in self._wb_pending:
            # the moments about to be read are still being written
            self.drain_writes()
        self._discard_prefetch()
        shape = self.shapes[leaf_id]
        bufs, fds, tids = [], [], []
        for field in self.FIELDS:
            tid, buf = self._arena.take(shape)
            fd = self._pf_handle.open(
                self.swapper._path(f"{leaf_id}.{field}"), False)
            self._pf_handle.async_pread(buf, fd)
            bufs.append(buf)
            fds.append(fd)
            tids.append(tid)
        self._pf = (leaf_id, bufs, fds, tids)

    def fetch(self, leaf_id):
        # a re-fetch without an intervening store (e.g. state_dict() walks
        # every leaf read-only) must not orphan the previous staging slots
        self._release_consumed(leaf_id)
        if leaf_id in self._wb_pending:
            self.drain_writes()
        if self._pf is not None and self._pf[0] == leaf_id:
            _, bufs, tids = self._drain_prefetch()
            self._consumed[leaf_id] = tids
            return bufs
        self._discard_prefetch()
        shape = self.shapes[leaf_id]
        out, tids = [], []
        t0 = time.perf_counter()
        for field in self.FIELDS:
            tid, buf = self._arena.take(shape)
            self.swapper.swap_in(f"{leaf_id}.{field}", buf)
            out.append(buf)
            tids.append(tid)
        self._stall_s += time.perf_counter() - t0
        self._consumed[leaf_id] = tids
        return out

    def store(self, leaf_id, exp_avg, exp_avg_sq):
        if self.pipeline_write:
            return self._store_behind(leaf_id, exp_avg, exp_avg_sq)
        t0 = time.perf_counter()
        self.swapper.swap_out(f"{leaf_id}.exp_avg", exp_avg)
        self.swapper.swap_out(f"{leaf_id}.exp_avg_sq", exp_avg_sq)
        self._stall_s += time.perf_counter() - t0
        self._reg().counter("swap/bytes_written").inc(
            exp_avg.nbytes + exp_avg_sq.nbytes)
        # the fetched staging views are dead once the new moments hit disk
        self._release_consumed(leaf_id)

    def _store_behind(self, leaf_id, exp_avg, exp_avg_sq):
        """Write-behind store: the updated moments usually ARE the arena
        views handed out by fetch() (the SIMD step updates them in
        place) — hand exactly those slots to the write handle and defer
        their release to the drain, so no extra copy happens; foreign
        arrays are copied into fresh arena slots first."""
        if leaf_id in self._wb_pending:
            self.drain_writes()  # same-fd write race guard
        elif len(self._wb_live) >= self.buffer_count:
            # bound the live staged moments at ~buffer_count leaves (the
            # documented pool bound): without this reap, a whole step's
            # stores stay live until the next step's first prefetch —
            # host RSS = total moment bytes, not the pool
            self.drain_writes()
        mine = self._consumed.pop(leaf_id, None)
        arrs = [np.ascontiguousarray(exp_avg, np.float32),
                np.ascontiguousarray(exp_avg_sq, np.float32)]
        if mine is not None and arrs[0] is exp_avg and arrs[1] is exp_avg_sq:
            tids = mine
        else:
            # foreign buffers (or a copy was forced): stage them
            if mine is not None:
                for tid in mine:
                    self._arena.give(tid)
            tids = []
            staged = []
            for a in arrs:
                tid, buf = self._arena.take(a.shape)
                np.copyto(buf, a)
                tids.append(tid)
                staged.append(buf)
            arrs = staged
        wh = self._wb_handle
        if wh is None:
            wh = self._wb_handle = _make_aio_handle(self._aio_config)
        for field, a in zip(self.FIELDS, arrs):
            wh.async_pwrite(a, self._wb_fd(leaf_id, field, a.nbytes))
        self._wb_live.append((leaf_id, tids, arrs))
        self._wb_pending.add(leaf_id)
        self._reg().counter("swap/bytes_written").inc(
            sum(a.nbytes for a in arrs))

    def _wb_fd(self, leaf_id, field, nbytes):
        """Cached no-O_TRUNC write fd per moment file, preallocated so
        steady-state stores reuse extents (the TensorSwapper sync path
        reopens with O_TRUNC each step — fine off the hot path)."""
        key = (leaf_id, field)
        handle = self.swapper.handle
        fd = self._wb_fds.get(key)
        if fd is None:
            fd = handle.open_fd(self.swapper._path(f"{leaf_id}.{field}"),
                                os.O_WRONLY | os.O_CREAT)
            self._wb_fds[key] = fd
        alloc = handle.io_nbytes(nbytes)
        if self._wb_sizes.get(key) != alloc:
            os.ftruncate(fd, alloc)
            try:
                os.posix_fallocate(fd, 0, alloc)
            except OSError:
                pass
            self._wb_sizes[key] = alloc
        return fd

    def release(self):
        try:
            self._discard_prefetch()
        except Exception:
            pass
        try:
            self.drain_writes()
        except Exception:
            pass
        for leaf in list(self._consumed):
            self._release_consumed(leaf)
        for fd in list(self._wb_fds.values()):
            try:
                os.close(fd)
            except OSError:
                pass
        self._wb_fds.clear()
        self.swapper.release()
