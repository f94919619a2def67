"""Reductions that several per-layer metric readers share.

A reader takes the run's ``harness.Record`` and returns a number, or None
when there is nothing to read (no trace, no such histogram, a CPU
rehearsal): the harness then leaves the metric out of the line.
"""

from benchmark import harness, stats, trace_reduce


def traced(record):
    return record.trace is not None and record.slice is not None \
        and bool(record.planes())


def device_idle_share(record):
    """100 x (1 - union of device-op intervals / slice), chips averaged."""
    if not traced(record):
        return None
    busy, window = harness.busy_and_window(record)
    return 100.0 * (1.0 - busy / window)


def peak_hbm_gb(record):
    peak = record.memory_peak_bytes
    return peak / 1e9 if peak else None


def attention_flops_per_step(record):
    """Causal attention flops (forward + backward) one chip's step needs,
    by the family's count."""
    per_chip = record.extra["global_batch"] // record.cell["chips"]
    return record.family.train_attention_flops_per_step(
        record.config, per_chip, record.extra["seq_len"], record.rehearse)


def compiles_in_window(record):
    return record.compiles_in_window


def registry_median_ms(record, histogram):
    values = record.registry.get(histogram) or []
    return None if not values else 1e3 * stats.median(values)


def registry_mean_pct(record, histogram):
    values = record.registry.get(histogram) or []
    return None if not values else 100.0 * sum(values) / len(values)


def slice_op_share(record, pred):
    """100 x self time of the slice's device ops satisfying ``pred`` over
    the slice's busy time, worst chip first (the largest share)."""
    if not traced(record):
        return None
    t0, t1 = record.slice
    shares = []
    for plane in record.planes():
        evs = trace_reduce.clip(trace_reduce.ops(record.trace, plane), t0, t1)
        busy = trace_reduce.union_ns(evs)
        if busy:
            shares.append(100.0 * trace_reduce.time_where(evs, pred) / busy)
    return max(shares) if shares else None


def slice_op_seconds(record, pred):
    """Self seconds of the slice's device ops satisfying ``pred``, averaged
    over the chips."""
    if not traced(record):
        return None
    t0, t1 = record.slice
    secs = [trace_reduce.time_where(
        trace_reduce.clip(trace_reduce.ops(record.trace, p), t0, t1), pred)
        / 1e9 for p in record.planes()]
    return sum(secs) / len(secs)
