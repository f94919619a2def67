"""Async-IO throughput harness — the role of the reference's aio perf suite
(csrc/aio/py_test/: ds_aio_basic.py sweep of block size / queue depth /
submit mode against libaio).

Measures MB/s for write + read of a tensor-sized file through each backend
(io_uring ring vs pread/pwrite thread pool) across queue depths and block
sizes. Run directly for the sweep table, or import `quick_throughput` for
the pinned single point (median of several passes, and the O_DIRECT leg).

Usage: python tests/perf/aio_bench.py [--mb 512] [--dir /tmp]
"""

import argparse
import json
import os
import tempfile
import time

import numpy as np


def _run_case(handle, arr, path, write_first=True):
    """One write+read pass; returns (write_mbps, read_mbps). The file's
    pages are dropped from the page cache between write and read (fsync
    makes them clean, fadvise evicts) so read_mbps measures the device,
    not memcpy out of cache."""
    nbytes = arr.nbytes
    fd = handle.open(path, True)
    t0 = time.perf_counter()
    handle.async_pwrite(arr, fd)
    handle.wait()
    os.fsync(fd)
    wt = time.perf_counter() - t0
    os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    handle.close(fd)

    out = np.empty_like(arr)
    fd = handle.open(path, False)
    t0 = time.perf_counter()
    handle.async_pread(out, fd)
    handle.wait()
    rt = time.perf_counter() - t0
    handle.close(fd)
    assert np.array_equal(arr, out), "aio roundtrip corrupted data"
    return nbytes / wt / 2**20, nbytes / rt / 2**20


def quick_throughput(mb=256, directory=None, queue_depth=32,
                     block_size=1 << 20, trials=3):
    """Pinned-methodology MB/s point.

    Round-3 postmortem: a single write+read pass is measuring LUCK on a
    virtualized disk — the guest-side fadvise(DONTNEED) drops the guest
    page cache but cannot touch the virtio host's cache, so one-shot read
    numbers swing 20x (43.9 vs 950 MB/s across r3 runs) with host-cache
    state. Two pinned numbers instead:

    - ``read_mbps`` / ``write_mbps``: MEDIAN of ``trials`` passes — the
      steady-state tier. This is the number the swap tier actually sees:
      ZeRO-Infinity re-reads the same optimizer-state files every step,
      so steady-state (host-cache-assisted) behavior is the
      representative regime, not an anomaly.
    - ``first_read_mbps``: the cold first pass, reported separately (the
      restart/first-touch case).
    - ``o_direct``: the same point through the O_DIRECT alignment layer
      (ISSUE 20) — no page cache in the path at all, so first ≈ steady
      by construction and the numbers are device truth on both legs.

    All knob values ride along so the number is reproducible. Returns
    None if the native lib is unavailable.
    """
    try:
        from deepspeed_tpu.ops.native.aio import (
            AsyncIOHandle, aligned_empty, o_direct_fallback_latched)
        handle = AsyncIOHandle(block_size=block_size, queue_depth=queue_depth,
                               thread_count=4)
    except Exception:
        return None
    arr = np.random.randint(0, 255, size=mb << 20, dtype=np.uint8)
    path = tempfile.mktemp(dir=directory, suffix=".aio")
    try:
        ws, rs = [], []
        for _ in range(trials):
            w, r = _run_case(handle, arr, path)
            ws.append(w)
            rs.append(r)
        dws, drs = [], []
        dhandle = AsyncIOHandle(block_size=block_size,
                                queue_depth=queue_depth,
                                thread_count=4, o_direct=True)
        darr = aligned_empty(arr.nbytes)    # page-aligned: zero-copy leg
        darr[:] = arr
        for _ in range(trials):
            w, r = _run_case(dhandle, darr, path)
            dws.append(w)
            drs.append(r)
        return {"backend": handle.backend,
                "write_mbps": round(float(np.median(ws)), 1),
                "read_mbps": round(float(np.median(rs)), 1),
                "first_read_mbps": round(rs[0], 1),
                "o_direct": {
                    "write_mbps": round(float(np.median(dws)), 1),
                    "read_mbps": round(float(np.median(drs)), 1),
                    "first_read_mbps": round(drs[0], 1),
                    "fallback_latched": o_direct_fallback_latched(),
                },
                "mb": mb, "trials": trials,
                "queue_depth": queue_depth,
                "block_kb": block_size >> 10,
                "cache_note": "guest page cache dropped (fsync+fadvise) "
                              "each pass; virtio host cache uncontrollable "
                              "from the guest — median == steady-state "
                              "(the swap tier's every-step re-read regime); "
                              "the o_direct point bypasses the guest cache "
                              "entirely (honest first-touch == steady)"}
    finally:
        if os.path.exists(path):
            os.unlink(path)


def sweep(mb, directory):
    from deepspeed_tpu.ops.native.aio import AsyncIOHandle
    arr = np.random.randint(0, 255, size=mb << 20, dtype=np.uint8)
    rows = []
    for backend in ("io_uring", "threads"):
        for queue_depth in (4, 16, 64):
            for block_kb in (256, 1024, 4096):
                try:
                    handle = AsyncIOHandle(block_size=block_kb << 10,
                                           queue_depth=queue_depth,
                                           thread_count=4, backend=backend)
                except OSError:
                    continue  # io_uring unsupported here
                path = tempfile.mktemp(dir=directory, suffix=".aio")
                try:
                    w, r = _run_case(handle, arr, path)
                finally:
                    if os.path.exists(path):
                        os.unlink(path)
                rows.append({"backend": backend, "queue_depth": queue_depth,
                             "block_kb": block_kb, "write_mbps": round(w, 1),
                             "read_mbps": round(r, 1)})
                print(json.dumps(rows[-1]))
    best = max(rows, key=lambda x: x["read_mbps"])
    print(json.dumps({"best": best, "mb": mb}))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=512)
    ap.add_argument("--dir", default=None)
    args = ap.parse_args()
    sweep(args.mb, args.dir)


if __name__ == "__main__":
    main()
