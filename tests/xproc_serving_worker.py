"""Shared cross-process serving worker (ISSUE 17).

One ranked OS process of the ``transport: "process"`` fabric: rank 0
runs the router + prefill engine (:class:`PrefillNode`), every other
rank one decode engine (:class:`DecodeNode`). The SAME module backs

- the 2-real-process acceptance tests (tests/test_serving_transport.py,
  launched through the PR-10 ``spawn_workers`` harness),
- the supervisor SIGKILL fault acceptance (launched as the
  ``Supervisor`` worker command with ``roles={0: "prefill", ...}``).

Stdout protocol (machine-parsed by both callers), one line each::

    RES <rid> <json done-doc>    per finished request   (rank 0 only)
    MET <json>                   final stats + metric summaries

Filesystem under ``out_dir`` (argv[1]):

- ``ledger.json``   rank 0: every submitted request's wire doc,
  written ATOMICALLY before serving starts (replica_pool.save_ledger)
  — the PR-11 pool-ledger discipline applied across processes. A
  respawned epoch reloads it and re-serves ONLY the unfinished rids.
- ``results.jsonl`` rank 0: append-only finished streams (fsynced per
  line, so a SIGKILL between lines loses at most the request it was
  mid-appending — which the ledger then replays).
- ``flight_rank*.jsonl``  per-rank/per-epoch recorder dumps
  (``Watchdog.force_dump`` at clean exit; a SIGKILLed rank writes
  nothing — the router rank's "finish" authority closes its traces).

Env contract: the spawn_workers / Supervisor variables
(``DSTPU_COORDINATOR_*``, ``DSTPU_PROCESS_ID`` ...) plus the
supervisor's ``DSTPU_RESTART_EPOCH`` / ``DSTPU_HEARTBEAT_DIR`` /
``DSTPU_SERVING_ROLE``. argv: ``out_dir [n_reqs] [max_new]
[kill_after] [slots] [num_blocks] [addressing] [tick_cap]`` —
``kill_after >= 0`` arms a RANK-1 decode self-SIGKILL after that many
deliveries, EPOCH 0 ONLY (the fault under test; pinned to rank 1 so a
D>=2 world loses exactly one decode rank). ``slots``/``num_blocks``
size the engine geometry per leg (ISSUE 18: with the default 2-slot pool
a TTFT tail is pure queue wait — a caller says which geometry it
drives); ``addressing`` picks the wire mode
(targeted|broadcast); ``tick_cap > 0`` overrides
``serving.router.decode_tick_cap`` (1 keeps
streams resident long enough to saturate every rank's slots).
"""

import json
import os
import signal
import sys

import jax

jax.config.update("jax_platforms", "cpu")

from deepspeed_tpu.utils.distributed import init_distributed  # noqa: E402

REQ_SEED = 1
VOCAB = 256
PROMPT_LENS = (5, 9, 14, 21)


def build_model():
    """The tiny deterministic GPT-2 the serving tests share (the
    ``gpt2_dis`` fixture geometry) — every rank builds identical
    params from PRNGKey(0)."""
    import numpy as np
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    cfg = GPT2Config(vocab_size=VOCAB, n_positions=128, n_embd=64,
                     n_layer=2, n_head=4, dtype=jnp.float32,
                     param_dtype=jnp.float32, scan_layers=True)
    params = jax.jit(GPT2LMHeadModel(cfg).init)(
        jax.random.PRNGKey(0), np.zeros((1, 8), np.int32))["params"]
    return cfg, params


def serving_config(slots=2, num_blocks=0, addressing="targeted",
                   tick_cap=0):
    sv = {"slots": int(slots), "page_size": 8,
          "max_pages_per_slot": 8,
          "disaggregation": {"transport": "process",
                             "addressing": str(addressing)}}
    if int(num_blocks) > 0:
        sv["num_blocks"] = int(num_blocks)
    if int(tick_cap) > 0:
        sv["router"] = {"decode_tick_cap": int(tick_cap)}
    return {"serving": sv}


def build_requests(n_reqs, max_new):
    import numpy as np
    import deepspeed_tpu.serving as serving
    rs = np.random.RandomState(REQ_SEED)
    lens = rs.choice(PROMPT_LENS, n_reqs)
    return [serving.Request(
        i, rs.randint(0, VOCAB, size=(int(L),)).astype(np.int32),
        max_new_tokens=max_new) for i, L in enumerate(lens)]


def _append_result(path, doc):
    # crash-safe append: one fsynced line per finished stream
    with open(path, "a") as fh:
        fh.write(json.dumps(doc) + "\n")
        fh.flush()
        os.fsync(fh.fileno())


def _load_results(path):
    out = {}
    if os.path.exists(path):
        with open(path) as fh:
            for line in fh:
                line = line.strip()
                if line:
                    doc = json.loads(line)
                    out[doc["rid"]] = doc
    return out


def main(argv):
    out_dir = argv[1]
    n_reqs = int(argv[2]) if len(argv) > 2 else 8
    max_new = int(argv[3]) if len(argv) > 3 else 6
    kill_after = int(argv[4]) if len(argv) > 4 else -1
    slots = int(argv[5]) if len(argv) > 5 else 2
    num_blocks = int(argv[6]) if len(argv) > 6 else 0
    addressing = argv[7] if len(argv) > 7 else "targeted"
    tick_cap = int(argv[8]) if len(argv) > 8 else 0
    os.makedirs(out_dir, exist_ok=True)

    init_distributed()
    rank = int(jax.process_index())
    world = int(jax.process_count())
    epoch = int(os.environ.get("DSTPU_RESTART_EPOCH", "0"))

    from deepspeed_tpu.runtime.elastic.hang import HangWatchdog
    from deepspeed_tpu.telemetry.anomaly import Watchdog
    from deepspeed_tpu.telemetry.recorder import FlightRecorder
    from deepspeed_tpu.telemetry.registry import MetricsRegistry
    import deepspeed_tpu.serving as serving
    from deepspeed_tpu.serving import elastic, replica_pool
    from deepspeed_tpu.serving.engine import ensure_trace_id

    rec = FlightRecorder()
    reg = MetricsRegistry()
    hw = None
    hb_dir = os.environ.get("DSTPU_HEARTBEAT_DIR")
    if hb_dir:
        # beat-only watchdog: a generous deadline and no dispatch marks
        # — the supervisor needs the liveness file, not hang detection
        hw = HangWatchdog(600.0, rank=rank, world=world, recorder=rec,
                          heartbeat_dir=hb_dir,
                          heartbeat_interval_s=0.1, restart_epoch=epoch)

    cfg, params = build_model()
    node = serving.build_transport_node(
        "gpt2", cfg, params,
        config=serving_config(slots, num_blocks, addressing, tick_cap),
        registry=reg, recorder=rec)

    def _hist(name):
        return reg.histogram(name).summary()

    def _slot_util(stats):
        cap = stats.get("slot_cap_ticks", 0)
        return (stats.get("slot_busy_ticks", 0) / cap) if cap else 0.0

    if rank == 0:
        ledger_path = os.path.join(out_dir, "ledger.json")
        results_path = os.path.join(out_dir, "results.jsonl")
        finished = _load_results(results_path)
        docs = replica_pool.load_ledger(ledger_path)
        if docs is None:
            reqs = build_requests(n_reqs, max_new)
            for r in reqs:
                ensure_trace_id(r)   # the ledgered trace identity is
                #                      the one every epoch's events use
            replica_pool.save_ledger(
                ledger_path, {r.rid: elastic._req_doc(r) for r in reqs})
        else:
            # respawned epoch: replay ONLY the unfinished rids from
            # their ledger docs (greedy replay is token-lossless), and
            # re-record the already-finished streams so THIS epoch's
            # dump closes every trace the incident interrupted
            reqs = [elastic.resume_request(doc)
                    for rid, doc in sorted(docs.items(),
                                           key=lambda kv: int(kv[0]))
                    if str(rid) not in {str(k) for k in finished}]
            for doc in finished.values():
                rec.record("finish", rid=doc["rid"],
                           trace=doc.get("trace_id"),
                           reason=doc.get("finish_reason"),
                           generated=doc.get("generated"))
        node.on_done = lambda doc: _append_result(results_path, doc)
        done = dict(node.serve(reqs))
        for rid, doc in finished.items():
            done.setdefault(int(rid) if str(rid).isdigit() else rid,
                            doc)
        for rid in sorted(done, key=int):
            print("RES", rid, json.dumps(done[rid]), flush=True)
        met = {"rank": rank, "epoch": epoch, "role": "prefill",
               "stats": node.stats,
               "counters": reg.snapshot()["counters"],
               "ttft_s": reg.histogram("serving/ttft_s").summary(),
               "ttft_queue_wait_s": _hist("serving/ttft_queue_wait_s"),
               "ttft_prefill_s": _hist("serving/ttft_prefill_s"),
               "transport_encode_s": _hist("serving/transport_encode_s"),
               "transport_collective_s": _hist(
                   "serving/transport_collective_s"),
               "slot_util": _slot_util(node.stats),
               "slots": slots,
               "page_nbytes": node.engines[0].cache.page_nbytes,
               "leak_fence": _fence(node.engines)}
    else:
        if kill_after >= 0 and epoch == 0 and rank == 1:
            def _boom(n):
                if n.stats["delivered"] >= kill_after:
                    # mid-stream by construction: the request just
                    # adopted has generated nothing on this rank yet
                    os.kill(os.getpid(), signal.SIGKILL)
            node.on_absorb = _boom
        node.run()
        met = {"rank": rank, "epoch": epoch, "role": "decode",
               "stats": node.stats,
               "counters": reg.snapshot()["counters"],
               "transport_s": _hist("serving/transport_s"),
               "transport_collective_s": _hist(
                   "serving/transport_collective_s"),
               "transport_decode_s": _hist("serving/transport_decode_s"),
               "slot_util": _slot_util(node.stats),
               "slots": slots,
               "decode_tokens": node.engine.stats["decode_tokens"],
               "absorbed_pages": node.absorbed_pages,
               "done": node.done_count,
               "leak_fence": _fence([node.engine])}

    wd = Watchdog(out_dir, recorder=rec, registry=reg,
                  source=f"rank{rank}e{epoch}")
    wd.force_dump("worker_exit")
    print("MET", json.dumps(met), flush=True)
    if hw is not None:
        hw.stop()


def _fence(engines):
    """num_blocks - 1 free pages after a sweep on every pool = no leak
    survived the run (the PR-14 invariant, now held across processes)."""
    out = []
    for cb in engines:
        cb.cache.sweep_prefix_cache()
        out.append({"replica": cb.replica_id,
                    "free": int(cb.cache.free_pages),
                    "want": int(cb.cache.num_blocks - 1)})
    return out


if __name__ == "__main__":
    main(sys.argv)
