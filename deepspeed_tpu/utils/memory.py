"""Memory reporting — rebuild of ``see_memory_usage`` (deepspeed/runtime/utils.py).

Reports host RSS plus per-device HBM stats where the backend exposes
``memory_stats()`` (TPU runtime does; CPU backend returns nothing).
"""

import resource

from deepspeed_tpu.utils.logging import logger


def _device_memory_stats():
    try:
        import jax
        stats = []
        for d in jax.local_devices():
            s = getattr(d, "memory_stats", None)
            s = s() if callable(s) else None
            if s:
                stats.append((str(d), s.get("bytes_in_use", 0), s.get("bytes_limit", 0)))
        return stats
    except Exception:
        return []


def host_max_rss_mb():
    """Host peak RSS in MB (ru_maxrss is KB on Linux)."""
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def memory_metrics():
    """One flat dict of the memory observables, for the telemetry
    scalar stream: host RSS and per-device HBM in use where the backend
    exposes it."""
    out = {"host_max_rss_mb": host_max_rss_mb()}
    for i, (_, in_use, limit) in enumerate(_device_memory_stats()):
        out[f"device{i}_bytes_in_use"] = in_use
        out[f"device{i}_bytes_limit"] = limit
    return out


def see_memory_usage(message, force=False):
    if not force:
        return
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    lines = [message, f"Host MaxRSS {rss_mb:.1f} MB"]
    for name, in_use, limit in _device_memory_stats():
        lines.append(f"{name}: HBM in use {in_use / 2**30:.2f} GB / {limit / 2**30:.2f} GB")
    logger.info(" | ".join(lines))
