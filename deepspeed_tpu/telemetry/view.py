"""Flight-recorder dump viewer.

Usage::

    python -m deepspeed_tpu.telemetry.view <dump.jsonl>

Renders a watchdog dump (anomaly.py) — or any JSONL stream of recorder
events — as:

- the trigger header (rule, dump id, detail);
- a per-step phase-attribution table: one row per training step,
  columns for each recorded span tag (host phase seconds), the step's
  tokens / swap stall, and the boundary loss readbacks;
- per-request serving timelines: admit -> prefill (TTFT) -> ticks ->
  finish, with waits and reasons;
- a checkpoint / restore / preempt timeline (ISSUE 7): snapshot
  begin/commit pairs with the commit-fence wait, corruption fallbacks,
  the preemption signal + final snapshot, elastic resumes — the
  elastic-serving lifecycle (ISSUE 11): drain -> snapshot -> restore
  -> requeue, aborts, replica kills and pool scale events — and the
  fault-tolerant training lifecycle (ISSUE 15): give the supervisor's
  dump and the workers' dumps together and the same table stitches
  die -> detect (rank_exit/rank_hang) -> teardown -> shrunk restart ->
  resume, stamped with each epoch's restart_epoch;
- a swap-tier I/O summary per step (bytes in/out, drain waits);
- request-scoped distributed traces (ISSUE 12): given N dump files
  TOGETHER (``view.py dumpA.jsonl dumpB.jsonl``), events are merged,
  deduplicated and stitched by ``trace_id`` into one cross-replica
  timeline per request — "born on replica 0, killed mid-verify,
  restored on replica 2, finished";
- cluster fences (ISSUE 12): the per-rank step-time skew table the
  cross-rank aggregation recorded at each fence;
- the trailing raw events with ``--events N``.

Pure stdlib + host-side JSON — the viewer never imports jax, so it runs
anywhere the dump landed (a dev laptop, a CI artifact store);
tests/test_metric_names.py pins the import chain jax-free.
"""

import argparse
import json
import os
import sys
from collections import OrderedDict, defaultdict


def load_dump(path):
    """Returns (header_or_None, events). Unparseable lines are skipped
    with a count so a truncated dump still renders."""
    header = None
    events = []
    skipped = 0
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                skipped += 1
                continue
            if not isinstance(obj, dict):
                skipped += 1
                continue
            if obj.get("kind") == "dump_header" and header is None:
                header = obj
            else:
                events.append(obj)
    return header, events, skipped


def load_dumps(paths):
    """Merge N dumps into one event stream: events deduplicate on
    ``(seq, ts, kind)`` (two dumps of the SAME recorder ring overlap —
    e.g. a mid-run anomaly dump plus an end-of-run one) and sort by
    wall clock then sequence, which also interleaves dumps from
    DIFFERENT processes/replicas onto one timeline. Returns
    ``(headers, events, skipped)`` with one (path, header) per file
    that had one."""
    headers, events, skipped = [], [], 0
    seen = set()
    for path in paths:
        header, evs, sk = load_dump(path)
        skipped += sk
        if header is not None:
            headers.append((path, header))
        for ev in evs:
            key = (ev.get("seq"), ev.get("ts"), ev.get("kind"))
            if ev.get("seq") is not None:
                if key in seen:
                    continue
                seen.add(key)
            events.append(ev)
    events.sort(key=lambda e: (e.get("ts") or 0.0, e.get("seq") or 0))
    return headers, events, skipped


def _fmt(v, width):
    if v is None or v == "":
        s = "-"
    elif isinstance(v, float):
        s = f"{v:.4g}"
    else:
        s = str(v)
    if len(s) > width:
        s = s[:width - 1] + "…"
    return s.rjust(width)


def _table(headers, rows, out):
    widths = [max(len(str(h)), 10) for h in headers]
    out.append("  " + " ".join(_fmt(h, w) for h, w in
                               zip(headers, widths)))
    for row in rows:
        out.append("  " + " ".join(_fmt(v, w) for v, w in
                                   zip(row, widths)))


def render_header(header, out):
    if header is None:
        out.append("no dump header (raw event stream)")
        return
    det = header.get("detail") or {}
    out.append(f"flight dump #{header.get('dump_id')} — rule "
               f"{header.get('rule')!r} (source "
               f"{header.get('source')}, {header.get('n_events')} "
               f"events)")
    if det:
        out.append("  trigger: " + ", ".join(
            f"{k}={det[k]!r}" if isinstance(det[k], str)
            else f"{k}={_fmt(det[k], 12).strip()}" for k in det))


def render_steps(events, out):
    """Per-step phase attribution: span tags as columns (seconds summed
    per step), plus tokens, swap stall and the boundary loss."""
    steps = OrderedDict()          # step -> {col: value}
    tags = []
    for ev in events:
        step = ev.get("step")
        if step is None:
            continue
        row = steps.setdefault(step, defaultdict(float))
        kind = ev.get("kind")
        if kind == "span":
            tag = ev.get("tag", "?")
            if tag not in tags:
                tags.append(tag)
            row[("span", tag)] += ev.get("dur_s") or 0.0
        elif kind == "step":
            row["tokens"] = ev.get("tokens")
            if ev.get("swap_stall_s") is not None:
                row["swap_stall_s"] = ev["swap_stall_s"]
            if ev.get("comm_intra_bytes") is not None \
                    or ev.get("comm_inter_bytes") is not None:
                # hierarchical comm cost model (ISSUE 10): bytes this
                # step put on the wire, fast + slow links
                row["comm_mb"] = ((ev.get("comm_intra_bytes") or 0)
                                  + (ev.get("comm_inter_bytes") or 0)) \
                    / 2**20
        elif kind == "onebit_freeze":
            row["comm_phase"] = "freeze"
        elif kind == "loss":
            row["loss"] = ev.get("loss")
        elif kind == "window":
            row["window_step_s"] = ev.get("step_s")
        elif kind == "anomaly":
            row["anomaly"] = ev.get("rule")
    if not steps:
        return
    out.append("")
    out.append("per-step phase attribution (host seconds per span tag):")
    extra = [c for c in ("comm_mb", "comm_phase")
             if any(c in row for row in steps.values())]
    headers = (["step"] + [t.replace("train/", "") for t in tags]
               + ["window_step_s", "tokens", "swap_stall_s"] + extra
               + ["loss", "anomaly"])
    rows = []
    for step, row in steps.items():
        rows.append([step] + [row.get(("span", t), "") for t in tags]
                    + [row.get("window_step_s", ""),
                       row.get("tokens", ""),
                       row.get("swap_stall_s", "")]
                    + [row.get(c, "") for c in extra]
                    + [row.get("loss", ""),
                       row.get("anomaly", "")])
    _table(headers, rows, out)


def render_requests(events, out):
    """Per-request serving timelines from admit/prefill/finish events,
    with the global tick stream summarized."""
    reqs = OrderedDict()           # rid -> fields
    ticks = 0
    tick_steps = 0
    spec_rounds = 0
    exhausted = 0
    t0 = None
    for ev in events:
        kind = ev.get("kind")
        if kind in ("admit", "prefill", "finish", "tick", "spec_round",
                    "pool_exhausted") and t0 is None:
            t0 = ev.get("ts")
        if kind == "admit":
            r = reqs.setdefault(ev.get("rid"), {})
            r["t_admit"] = ev.get("ts")
            r["slot"] = ev.get("slot")
            r["pages"] = ev.get("pages")
            r["wait_s"] = ev.get("wait_s")
        elif kind == "prefill":
            r = reqs.setdefault(ev.get("rid"), {})
            r["prompt_tokens"] = ev.get("prompt_tokens")
            r["ttft_s"] = ev.get("ttft_s")
        elif kind == "finish":
            r = reqs.setdefault(ev.get("rid"), {})
            r["t_finish"] = ev.get("ts")
            r["reason"] = ev.get("reason")
            r["generated"] = ev.get("generated")
        elif kind == "tick":
            ticks += 1
            tick_steps += ev.get("steps") or 0
        elif kind == "spec_round":
            # one speculative verify dispatch = one decode step that
            # commits up to rows tokens
            ticks += 1
            tick_steps += 1
            spec_rounds += 1
        elif kind == "pool_exhausted":
            exhausted += 1
    if not reqs and not ticks:
        return
    out.append("")
    out.append(f"serving: {len(reqs)} requests in window, {ticks} ticks"
               f" ({tick_steps} decode steps)"
               + (f", {spec_rounds} speculative verify rounds"
                  if spec_rounds else "")
               + (f", {exhausted} pool-exhausted admissions"
                  if exhausted else ""))
    if not reqs:
        return
    out.append("per-request timelines (t relative to first serving "
               "event):")
    headers = ["rid", "t_admit", "slot", "pages", "wait_s",
               "prompt_toks", "ttft_s", "t_finish", "reason", "toks"]
    rows = []
    for rid, r in reqs.items():
        rel = (lambda t: (t - t0) if (t is not None and t0 is not None)
               else None)
        rows.append([rid, rel(r.get("t_admit")), r.get("slot"),
                     r.get("pages"), r.get("wait_s"),
                     r.get("prompt_tokens"), r.get("ttft_s"),
                     rel(r.get("t_finish")), r.get("reason"),
                     r.get("generated")])
    _table(headers, rows, out)


def render_ckpt(events, out):
    """Checkpoint / restore / preemption timeline (ISSUE 7): one row
    per elastic lifecycle event — async snapshot begins and commits
    (with the commit-fence wait), aborts, resume-time validation
    failures, the preemption signal and its final snapshot, and the
    resume itself."""
    kinds = ("ckpt_begin", "ckpt_commit", "ckpt_abort", "ckpt_corrupt",
             "preempt_signal", "preempt", "resume",
             # elastic serving lifecycle (ISSUE 11): the
             # drain -> snapshot -> restore -> requeue chain plus the
             # replica-pool scale/kill incidents ride the same timeline
             "serving_drain", "serving_snapshot", "serving_restore",
             "serving_requeue", "serving_abort", "replica_scale",
             "replica_kill",
             # fault-tolerant training lifecycle (ISSUE 15): the
             # die -> detect -> shrink -> resume chain — supervisor
             # events (spawn/rank_exit/world_down/restart/crash_loop)
             # merged with the workers' own rank_hang/restart_epoch
             # breadcrumbs onto one timeline
             "supervisor_spawn", "rank_exit", "rank_hang", "world_down",
             "restart", "crash_loop", "restart_epoch")
    rows = []
    t0 = None
    for ev in events:
        kind = ev.get("kind")
        if kind not in kinds:
            continue
        if t0 is None:
            t0 = ev.get("ts")
        detail = ""
        if kind == "serving_drain":
            detail = (f"{ev.get('drained', 0)} drained, "
                      f"{ev.get('left', 0)} left"
                      + (", snapshotted" if ev.get("snapshotted")
                         else ", NO snapshot"))
        elif kind == "serving_snapshot":
            detail = (f"{ev.get('requests', '?')} req "
                      f"({ev.get('slots', '?')} slots + "
                      f"{ev.get('queued', '?')} queued), "
                      f"{ev.get('pages', '?')} pages")
        elif kind == "serving_restore":
            detail = (f"{ev.get('restored', 0)} direct + "
                      f"{ev.get('requeued', 0)} requeued, "
                      f"{ev.get('pages', 0)} pages, "
                      f"{ev.get('restore_s', 0):.4g}s")
            if ev.get("dropped_prefix_pages"):
                detail += (f", {ev['dropped_prefix_pages']} prefix "
                           f"pages dropped")
        elif kind == "serving_requeue":
            detail = f"rid {ev.get('rid')!r}"
            if ev.get("outcome"):
                detail += (f" {ev['outcome']} "
                           f"(attempt {ev.get('attempts', '?')})")
            if ev.get("committed") is not None:
                detail += f", {ev['committed']} committed tokens kept"
        elif kind == "serving_abort":
            detail = (f"rid {ev.get('rid')!r} from "
                      f"{ev.get('where', '?')}, "
                      f"{ev.get('generated', 0)} tokens generated")
        elif kind == "replica_scale":
            detail = (f"{ev.get('direction')} -> "
                      f"{ev.get('replicas', '?')} replicas "
                      f"(replica {ev.get('replica')}, "
                      f"{ev.get('reason', '')})")
        elif kind == "replica_kill":
            detail = (f"replica {ev.get('replica')}: "
                      f"{str(ev.get('reason', ''))[:40]}")
        elif kind == "ckpt_begin":
            detail = f"{ev.get('files', '?')} files, " \
                     f"{ev.get('from_swapfiles', 0)} from swap tier"
        elif kind == "ckpt_commit":
            detail = f"wait {ev.get('wait_s', 0):.4g}s" \
                     + (", fsync" if ev.get("fsync") else "")
        elif kind in ("ckpt_abort", "ckpt_corrupt"):
            detail = str(ev.get("reason", ""))[:40]
        elif kind == "preempt_signal":
            detail = f"sig {ev.get('signal')}, grace " \
                     f"{ev.get('grace_s', '?')}s"
        elif kind == "preempt":
            detail = "final snapshot committed" if ev.get("snapshotted") \
                else "NO final snapshot"
        elif kind == "resume":
            detail = f"dp {ev.get('from_dp')}→{ev.get('to_dp')}, " \
                     f"micro {ev.get('micro')} gas {ev.get('grad_accum')}"
            if ev.get("fell_back"):
                detail += f", {ev['fell_back']} corrupt skipped"
        elif kind == "supervisor_spawn":
            detail = (f"world {ev.get('world')}, epoch "
                      f"{ev.get('restart_epoch')}, coordinator "
                      f":{ev.get('port')}")
        elif kind == "rank_exit":
            detail = (f"rank {ev.get('rank')} down: "
                      f"{ev.get('reason', '?')} (epoch "
                      f"{ev.get('restart_epoch')})")
        elif kind == "rank_hang":
            detail = (f"rank {ev.get('rank')} blocked "
                      f"{ev.get('blocked_s', 0):.4g}s in "
                      f"{ev.get('region', '?')} (deadline "
                      f"{ev.get('deadline_s', '?')}s)")
        elif kind == "world_down":
            detail = (f"{ev.get('survivors_torn_down', 0)} survivors "
                      f"torn down, {ev.get('lost', '?')} rank(s) lost")
        elif kind == "restart":
            detail = (f"world {ev.get('world_from')}→"
                      f"{ev.get('world_to')}, epoch "
                      f"{ev.get('restart_epoch')}, backoff "
                      f"{ev.get('backoff_s', 0):.4g}s "
                      f"({ev.get('reason', '')})")
        elif kind == "crash_loop":
            detail = (f"{ev.get('restarts')} restart(s) spent (max "
                      f"{ev.get('max_restarts')}), last "
                      f"{ev.get('last_reason', '?')}")
        elif kind == "restart_epoch":
            detail = (f"worker up in epoch {ev.get('epoch')}, world "
                      f"{ev.get('world')}")
        rows.append([
            None if t0 is None or ev.get("ts") is None
            else ev["ts"] - t0,
            kind, ev.get("step"), ev.get("tag", ev.get("dir", "")),
            (ev.get("bytes") or 0) / 2**20 if "bytes" in ev else "",
            detail])
    if not rows:
        return
    out.append("")
    out.append("checkpoint / restore / preempt timeline (t relative to "
               "first ckpt event):")
    # the serving-elastic kinds (and their details) outgrow the
    # default 10-char column — size both to their longest row (detail
    # capped so one verbose reason can't blow up the table)
    ev_w = max(len("event"), *(len(str(r[1])) for r in rows))
    det_w = min(max(10, *(len(str(r[5])) for r in rows)), 60)
    _table(["t", "event".ljust(ev_w), "step", "tag", "mb",
            "detail".ljust(det_w)], rows, out)


def render_swap(events, out):
    """Swap-tier I/O per step: bytes written/read, cache hits, drains."""
    per_step = OrderedDict()
    seen = False
    for ev in events:
        kind = ev.get("kind")
        if kind not in ("swap_out", "swap_in", "swap_drain"):
            continue
        seen = True
        row = per_step.setdefault(ev.get("step"), defaultdict(float))
        if kind == "swap_out":
            row["write_mb"] += (ev.get("bytes") or 0) / 2**20
            row["out_leaves"] += ev.get("leaves") or 0
        elif kind == "swap_in":
            row["read_mb"] += (ev.get("bytes_read") or 0) / 2**20
            row["cache_mb"] += (ev.get("cache_hit_bytes") or 0) / 2**20
            row["in_leaves"] += ev.get("leaves") or 0
        elif kind == "swap_drain":
            row["drain_s"] += ev.get("wait_s") or 0.0
    if not seen:
        return
    out.append("")
    out.append("swap-tier I/O per step:")
    headers = ["step", "write_mb", "read_mb", "cache_mb", "out_leaves",
               "in_leaves", "drain_s"]
    rows = [[step] + [row.get(h, "") for h in headers[1:]]
            for step, row in per_step.items()]
    _table(headers, rows, out)


# lifecycle kinds that carry a single ``trace`` field, and the batch
# kinds whose ``traces`` list names every request they touched.
# ISSUE 14: the disaggregated lifecycle rides the same stitching —
# router_route (admission decision) -> admit/prefill on the
# prefill-role replica -> handoff_out -> handoff_in on the decode-role
# replica -> ticks -> finish; router_block marks admissions deferred
# on decode-pool pressure.
TRACE_POINT_KINDS = ("admit", "prefill", "finish", "serving_abort",
                     "serving_requeue", "pool_exhausted",
                     "router_route", "router_block", "handoff_out",
                     "handoff_in")
TRACE_SET_KINDS = ("serving_snapshot", "serving_restore")


def trace_timelines(events):
    """trace_id -> ordered event list (the stitching primitive the
    tests drive directly): lifecycle events attach by their ``trace``
    field, snapshot/restore events by membership in their ``traces``
    list. Events without a trace are ignored — a request admitted
    before tracing existed simply has no timeline."""
    traces = OrderedDict()
    for ev in events:
        kind = ev.get("kind")
        if kind in TRACE_POINT_KINDS and ev.get("trace") is not None:
            traces.setdefault(ev["trace"], []).append(ev)
        elif kind in TRACE_SET_KINDS:
            for tid in ev.get("traces") or ():
                if tid is not None:
                    traces.setdefault(tid, []).append(ev)
    return traces


def _trace_outcome(evs):
    for ev in reversed(evs):
        if ev.get("kind") == "finish":
            return f"finished ({ev.get('reason')})"
        if ev.get("kind") == "serving_abort":
            return "aborted"
        if ev.get("kind") == "serving_requeue" \
                and ev.get("outcome") == "dropped":
            # the pool's retry budget ran out — a TERMINAL loss, the
            # trace an operator is most likely hunting for
            return f"lost (dropped after {ev.get('attempts', '?')} " \
                   f"attempts)"
    return "open"


def render_traces(events, out):
    """Request-scoped distributed traces (ISSUE 12): a summary row per
    trace_id, then a stitched per-event timeline for every trace that
    crossed a replica boundary or was requeued — the "born on replica
    0, restored on replica 2, finished" story."""
    traces = trace_timelines(events)
    if not traces:
        return
    ts_all = [ev["ts"] for evs in traces.values() for ev in evs
              if ev.get("ts") is not None]
    t0 = min(ts_all) if ts_all else None
    rel = (lambda t: (t - t0) if (t is not None and t0 is not None)
           else None)
    out.append("")
    out.append(f"request traces ({len(traces)} trace_id(s) stitched "
               f"across the given dumps):")
    headers = ["trace", "rid", "replicas", "events", "requeues",
               "outcome", "t_first", "t_last"]
    rows = []
    for tid, evs in traces.items():
        rid = next((ev.get("rid") for ev in evs
                    if ev.get("rid") is not None), None)
        reps = sorted({ev["replica"] for ev in evs
                       if ev.get("replica") is not None})
        rows.append([
            tid, rid, ",".join(str(r) for r in reps) or "-", len(evs),
            sum(ev.get("kind") == "serving_requeue" for ev in evs),
            _trace_outcome(evs),
            rel(evs[0].get("ts")), rel(evs[-1].get("ts"))])
    _table(headers, rows, out)
    for tid, evs in traces.items():
        reps = {ev["replica"] for ev in evs
                if ev.get("replica") is not None}
        crossed = len(reps) > 1 or any(
            ev.get("kind") == "serving_requeue" for ev in evs)
        if not crossed:
            continue
        out.append(f"  trace {tid} (rid "
                   f"{next((ev.get('rid') for ev in evs if ev.get('rid') is not None), '?')!r}):")
        for ev in evs:
            kind = ev.get("kind")
            rep = ev.get("replica")
            where = f"replica {rep}" if rep is not None else "-"
            bits = []
            for k in ("slot", "prompt_tokens", "ttft_s", "reason",
                      "generated", "outcome", "attempts", "committed",
                      "remaining", "restored", "requeued", "tag",
                      "engine", "pos"):
                if ev.get(k) is not None:
                    v = ev[k]
                    bits.append(f"{k}={v:.4g}" if isinstance(v, float)
                                else f"{k}={v}")
            t = rel(ev.get("ts"))
            out.append(f"    +{t:9.3f}s  {kind:<17} [{where}] "
                       + ", ".join(bits)
                       if t is not None else
                       f"    {'':>10}   {kind:<17} [{where}] "
                       + ", ".join(bits))


def render_disagg(events, out):
    """Disaggregated-serving summary (ISSUE 14): routing decisions by
    reason, handoff volume, transport requeues, and admissions the
    router deferred on decode-pool pressure — per-trace detail rides
    the stitched timelines above (prefill→handoff→decode crosses a
    replica boundary, so every handed-off trace prints there)."""
    routed = defaultdict(int)
    handoffs = requeues = blocked = 0
    for ev in events:
        kind = ev.get("kind")
        if kind == "router_route":
            routed[ev.get("reason") or "?"] += 1
        elif kind == "handoff_in":
            handoffs += 1
        elif kind == "router_block":
            blocked += 1
        elif kind == "serving_requeue" \
                and ev.get("outcome") == "scheduled":
            requeues += 1
    if not routed and not handoffs:
        return
    out.append("")
    by_reason = ", ".join(f"{n} by {r}" for r, n in sorted(routed.items()))
    out.append(f"disaggregated serving: {sum(routed.values())} prompts "
               f"routed ({by_reason}), {handoffs} prefill→decode "
               f"handoffs, {requeues} requeues, {blocked} admissions "
               f"deferred on decode-pool pressure")


def render_cluster(events, out):
    """Cluster fences (ISSUE 12): the per-rank step-time skew table
    the cross-rank aggregation recorded on rank 0 at each fence."""
    fences = [ev for ev in events if ev.get("kind") == "cluster_fence"]
    if not fences:
        return
    world = max(len(ev.get("step_time_per_rank") or ()) for ev in fences)
    out.append("")
    out.append(f"cluster fences (world {world}; per-rank step time, s):")
    headers = ["step", "world"] + [f"rank{r}_step_s" for r in range(world)]         + ["loss_rank0"]
    rows = []
    for ev in fences:
        st = list(ev.get("step_time_per_rank") or ())
        st += [None] * (world - len(st))
        losses = ev.get("loss_per_rank") or [None]
        rows.append([ev.get("step"), ev.get("world")] + st + [losses[0]])
    _table(headers, rows, out)


def render(paths, tail_events=0):
    """The full report as a list of lines (the CLI joins and prints).
    ``paths`` may be one dump path (str or PathLike, the pre-ISSUE-12
    signature) or a list of them — multiple dumps merge onto one
    timeline (cross-replica trace stitching)."""
    if isinstance(paths, (str, os.PathLike)):
        paths = [paths]
    headers, events, skipped = load_dumps(paths)
    out = []
    if not headers:
        out.append("no dump header (raw event stream)")
    for path, header in headers:
        if len(headers) > 1:
            out.append(f"[{path}]")
        render_header(header, out)
    if skipped:
        out.append(f"({skipped} unparseable line(s) skipped)")
    if not events:
        out.append("no events")
        return out
    render_steps(events, out)
    render_requests(events, out)
    render_disagg(events, out)
    render_traces(events, out)
    render_cluster(events, out)
    render_ckpt(events, out)
    render_swap(events, out)
    plans = [ev for ev in events
             if ev.get("kind") in ("overlap_bucket_plan",
                                   "comm_hierarchy_plan",
                                   "comm_hierarchy_fallback")]
    if plans:
        out.append("")
        out.append("comm bucket plans (trace-time):")
        for ev in plans:
            out.append("  " + json.dumps(
                {k: v for k, v in ev.items() if k not in ("ts", "seq")}))
    if tail_events:
        out.append("")
        out.append(f"last {min(tail_events, len(events))} raw events:")
        for ev in events[-tail_events:]:
            out.append("  " + json.dumps(ev))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.telemetry.view",
        description=__doc__.splitlines()[0])
    ap.add_argument("dump", nargs="+",
                    help="flight-recorder dump(s) (JSONL) — give several "
                         "to merge them onto one timeline (cross-replica "
                         "trace stitching)")
    ap.add_argument("--events", type=int, default=0, metavar="N",
                    help="also print the last N raw events")
    ap.add_argument("--format", choices=("text", "perfetto"),
                    default="text", dest="fmt",
                    help="text report (default) or a Chrome "
                         "trace-event JSON for ui.perfetto.dev / "
                         "chrome://tracing (telemetry/perfetto.py)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the report here instead of stdout")
    args = ap.parse_args(argv)
    try:
        if args.fmt == "perfetto":
            # lazy: perfetto imports load_dump from THIS module
            from deepspeed_tpu.telemetry import perfetto
            text = perfetto.dumps(perfetto.export(args.dump))
        else:
            text = "\n".join(render(args.dump,
                                    tail_events=args.events))
    except OSError as e:
        print(f"cannot read {' '.join(args.dump)}: {e}", file=sys.stderr)
        return 2
    if args.out:
        with open(args.out, "w") as fh:
            fh.write(text + "\n")
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
