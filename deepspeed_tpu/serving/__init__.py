"""Continuous-batching serving engine with a paged KV cache.

Entry point for every model family in ``FAMILIES``::

    import deepspeed_tpu.serving as serving

    engine = serving.build_engine(
        family="gpt2", model_config=gpt2_cfg, params=params,
        config={"serving": {"slots": 8, "page_size": 128,
                            "kv_cache_bits": 8}})
    results = engine.serve([serving.Request(0, prompt_ids,
                                            max_new_tokens=64)])

``config`` is the standard DeepSpeed-style dict/json whose ``serving``
block (docs/CONFIG.md) sizes the engine; keyword overrides win over the
block. See docs/serving.md for the scheduler model and tuning notes.
"""

from deepspeed_tpu.serving.paged_cache import (   # noqa: F401
    PagedCacheSpec, PagedKVCache, TRASH_BLOCK)
from deepspeed_tpu.serving.engine import (        # noqa: F401
    ContinuousBatcher, Request)
from deepspeed_tpu.serving.adapters import (      # noqa: F401
    GPT2ServingAdapter, LlamaServingAdapter, PagedServingAdapter)
from deepspeed_tpu.serving.elastic import (       # noqa: F401
    ElasticServingController, capture_state, load_latest_serving,
    load_serving_snapshot, restore_serving, snapshot_serving)
from deepspeed_tpu.serving.replica_pool import ReplicaPool  # noqa: F401
from deepspeed_tpu.serving.router import (        # noqa: F401
    DisaggRouter, HandoffPacket, deliver_handoff, extract_handoff)


# The families that serve: name -> the paged-serving skeleton bound to the
# family's layer math (docs/serving.md "Adding a family"). Every builder
# below reads this table and nothing else about a family.
FAMILIES = {"gpt2": GPT2ServingAdapter, "llama": LlamaServingAdapter}

_POOL_GEOMETRY = ("n_layers", "kv_heads", "head_dim", "dtype")


def _family(family: str):
    if family not in FAMILIES:
        raise ValueError(f"unknown serving family {family!r} "
                         f"(expected one of {sorted(FAMILIES)})")
    return FAMILIES[family]


def _param_dict(config):
    """Parse a config (dict or json path) ONCE into a param dict; a
    dict passes through cheaply, so callers can pre-parse and thread
    the result to avoid re-reading a file."""
    from deepspeed_tpu.config.config import DeepSpeedConfig
    if config is None:
        return {}
    return DeepSpeedConfig.load_param_dict(config)


def _serving_section(config):
    from deepspeed_tpu.config.config import ServingConfig
    return ServingConfig(_param_dict(config))


def cache_spec_from_config(model_config, family: str, config=None,
                           **overrides) -> PagedCacheSpec:
    """Resolve a PagedCacheSpec from a model config + the ``serving``
    config block (+ keyword overrides: slots, page_size,
    max_pages_per_slot, num_blocks, kv_cache_bits)."""
    sc = _serving_section(config)
    known = ("slots", "page_size", "max_pages_per_slot", "num_blocks",
             "kv_cache_bits")
    unknown = set(overrides) - set(known) - {"quantize_bits"}
    if unknown:
        raise TypeError(f"unknown serving override(s) {sorted(unknown)}; "
                        f"valid: {list(known) + ['quantize_bits']}")
    fields = {k: overrides.get(k, getattr(sc, k)) for k in known}
    geom = _family(family).math().serving_geometry(model_config)
    return PagedCacheSpec(**{k: geom[k] for k in _POOL_GEOMETRY}, **fields)


def _adapter_from_config(family: str, model_config, params, pd,
                         **overrides) -> PagedServingAdapter:
    """The family's adapter over the cache spec ``pd`` + overrides size.
    serving.quantize_bits = 8 quantizes full-precision param trees to
    the int8 serving storage at build time; trees that already carry
    int8 codes ("kernel_q") serve as-is either way."""
    spec = cache_spec_from_config(model_config, family, pd, **overrides)
    qb = overrides.get("quantize_bits", _serving_section(pd).quantize_bits)
    return _family(family)(model_config, params, spec, quantize_bits=qb)


def build_engine(family: str, model_config, params, config=None,
                 registry=None, recorder=None, watchdog=None,
                 drafter_model_config=None, drafter_params=None,
                 **overrides) -> ContinuousBatcher:
    """Build a ContinuousBatcher for ``family``:

    - ``"gpt2"``: ``params`` is either the training ``GPT2LMHeadModel``
      tree or the converted (optionally int8-quantized) inference tree;
    - ``"llama"``: ``params`` is the PACKED serving tree
      (models.llama_inference.convert_llama_serving_params /
      quantize_llama_serving_params / random_int8_serving_params).

    A ``monitor.watchdog`` block in ``config`` attaches an anomaly
    watchdog (telemetry/anomaly.py: TTFT blowup + page-pool exhaustion
    rules, one-shot flight-recorder dumps); pass ``watchdog=`` to
    supply one directly.

    A ``serving.prefix_cache`` sub-block turns on copy-on-write prefix
    page sharing; a ``serving.speculative`` sub-block turns on
    speculative decoding (``drafter: "model"`` additionally needs
    ``drafter_model_config`` + ``drafter_params`` — same family, its
    own smaller geometry).
    """
    from deepspeed_tpu.config import constants as C
    # parse once; pd is a plain dict, so the helpers below re-load it
    # for free instead of re-reading a json file per call
    pd = _param_dict(config)
    if config is not None:
        if C.SERVING in pd and not _serving_section(pd).enabled:
            raise ValueError(
                "the config's serving block sets enabled: false — "
                "drop the block (or flip the flag) to build a serving "
                "engine from it")
    sc = _serving_section(pd)
    if sc.speculative.enabled and sc.speculative.drafter == "model" \
            and (drafter_model_config is None or drafter_params is None):
        raise ValueError(
            "serving.speculative.drafter='model' needs "
            "drafter_model_config= and drafter_params= (a smaller "
            "checkpoint of the SAME family)")
    adapter = _adapter_from_config(family, model_config, params, pd,
                                   **overrides)
    mc = None
    if C.MONITOR in pd:
        from deepspeed_tpu.config.config import MonitorConfig
        mc = MonitorConfig(pd)   # parsed ONCE for watchdog + endpoint
    if watchdog is None and mc is not None:
        from deepspeed_tpu.telemetry.anomaly import Watchdog
        from deepspeed_tpu.telemetry.recorder import default_recorder
        # reconfigure the process recorder only when THIS config
        # actually carries a monitor block — a serving-only config must
        # not clobber a training engine's explicit recorder settings
        default_recorder().configure(
            enabled=mc.flight_recorder.enabled,
            capacity=mc.flight_recorder.capacity)
        if mc.watchdog.enabled and registry is None:
            # the watchdog's trip counters must land in the SAME
            # registry the batcher records into, or metrics_snapshot /
            # an exporter over the engine registry never sees them
            from deepspeed_tpu.telemetry.registry import MetricsRegistry
            registry = MetricsRegistry()
        watchdog = Watchdog.from_config(mc.watchdog, recorder=recorder,
                                        registry=registry,
                                        source="serving")
    drafter = None
    spec_tokens = sc.speculative.tokens
    if sc.speculative.enabled:
        from deepspeed_tpu.serving.drafter import (NGramDrafter,
                                                   ModelDrafter)
        if sc.speculative.drafter == "model":
            dadapter = _adapter_from_config(
                family, drafter_model_config, drafter_params, pd,
                **{**overrides, "num_blocks": 0})
            drafter = ModelDrafter(dadapter)
        else:
            drafter = NGramDrafter(adapter.spec.slots,
                                   ngram_max=sc.speculative.ngram_max,
                                   ngram_min=sc.speculative.ngram_min)
    # registry: pass telemetry.default_registry() to merge the serving
    # metrics into the process-wide stream; default is per-engine
    cb = ContinuousBatcher(adapter, registry=registry,
                           recorder=recorder, watchdog=watchdog,
                           prefix_cache=sc.prefix_cache.enabled,
                           prefix_cow=sc.prefix_cache.cow,
                           drafter=drafter, spec_tokens=spec_tokens)
    # ISSUE 11: a serving.elastic block attaches the drain-or-snapshot
    # preemption controller (SIGTERM → finish what fits the grace
    # budget, snapshot the rest through the two-rename commit path)
    if sc.elastic.enabled:
        from deepspeed_tpu.serving.elastic import ElasticServingController
        cb.attach_elastic(ElasticServingController.from_config(
            cb, sc.elastic))
    # ISSUE 12: live /metrics + /healthz over THIS engine's registry
    # (monitor.serve_port; a bind failure warns instead of killing the
    # server — e.g. a training engine in the same process won the port)
    if mc is not None and mc.serve_port:
        from deepspeed_tpu.telemetry.serve import start_metrics_server
        cb.metrics_server = start_metrics_server(
            mc.serve_port, host=mc.serve_host, registry=cb.metrics,
            watchdog=cb.watchdog,
            fence_age_fn=lambda: cb._t_last_step_ts)
    return cb


def build_router(family: str, model_config, params, config=None,
                 registry=None, recorder=None, **overrides):
    """Build a :class:`~deepspeed_tpu.serving.router.DisaggRouter`
    from the ``serving.disaggregation`` + ``serving.router`` config
    blocks (ISSUE 14): one shared adapter (the compiled prefill/tick
    programs), ``prefill_replicas`` prefill-role engines (prefix index
    ON by default — the locality-routing signal), ``decode_replicas``
    decode-role engines (prefix index on when ``dedupe_pages`` — the
    handoff re-share signal), each with its OWN paged pool.

    ``decode_replicas: 0`` or ``disaggregation.enabled: false`` falls
    back to colocated engines (``role="both"``) behind the same router
    API — no handoff, pre-disagg behavior per engine."""
    from deepspeed_tpu.serving.router import DisaggRouter

    pd = _param_dict(config)
    sc = _serving_section(pd)
    dg, rt = sc.disaggregation, sc.router
    # loud, not silent: a block that would be dropped on the floor
    # must raise — build_router still wires no drafters onto its role
    # engines (per-role drafter placement stays the follow-up; the
    # serving.elastic lift landed with ISSUE 17: per-engine snapshot
    # dirs below)
    if sc.speculative.enabled:
        raise ValueError(
            "serving.build_router does not compose with the "
            "serving.speculative block yet — drop it from the config, "
            "or construct the role engines and DisaggRouter directly")
    if dg.transport == "process":
        raise ValueError(
            "serving.disaggregation.transport \"process\" places "
            "roles on RANKS, not on in-process engines — each process "
            "builds its own role node with "
            "serving.build_transport_node(...) (build_router builds "
            "the in-process fabric only)")
    adapter = _adapter_from_config(family, model_config, params, pd,
                                   **overrides)
    disagg = dg.enabled and dg.decode_replicas > 0

    def mk(role, prefix_on):
        return ContinuousBatcher(
            adapter, registry=registry, recorder=recorder,
            prefix_cache=prefix_on, prefix_cow=sc.prefix_cache.cow,
            role=role)

    if disagg:
        prefills = [mk("prefill",
                       sc.prefix_cache.enabled or rt.prefix_routing)
                    for _ in range(dg.prefill_replicas)]
        decodes = [mk("decode", dg.dedupe_pages)
                   for _ in range(dg.decode_replicas)]
    else:
        prefills = [mk("both", sc.prefix_cache.enabled)
                    for _ in range(max(dg.prefill_replicas, 1))]
        decodes = []
    router = DisaggRouter(
        prefills, decodes, registry=registry, recorder=recorder,
        prefix_routing=rt.prefix_routing,
        dedupe_pages=dg.dedupe_pages,
        queue_weight=rt.queue_weight, ttft_weight=rt.ttft_weight,
        ttft_window=rt.ttft_window,
        max_handoff_retries=rt.max_handoff_retries,
        decode_tick_cap=rt.decode_tick_cap,
        max_inflight_pages=rt.max_inflight_pages or None,
        decode_schedule=rt.decode_schedule)
    if sc.elastic.enabled:
        # ISSUE 17 satellite: the serving.elastic lift. Each role
        # engine snapshots into its OWN subdir of snapshot_path (keyed
        # by the replica_id the router just assigned) — N engines
        # writing one dir would race the commit-rename protocol. The
        # installed signal handlers chain through preemption.py's
        # lock-free chain, so one delivered SIGTERM drains every
        # engine; DisaggRouter.close() retires them via release() (the
        # pool discipline — restore() would drop later handlers).
        import os as _os
        e = sc.elastic
        for cb in router.prefill_engines + router.decode_engines:
            cb.attach_elastic(ElasticServingController(
                cb, _os.path.join(e.snapshot_path, cb.replica_id),
                grace_secs=e.grace_secs,
                interval_ticks=e.interval_ticks, keep=e.keep,
                fsync=e.fsync, signals=e.signals,
                max_retries=e.max_retries, backoff_s=e.backoff_s))
    return router


def build_transport_node(family: str, model_config, params, config=None,
                         registry=None, recorder=None, endpoint=None,
                         on_tick=None, on_absorb=None, on_done=None,
                         **overrides):
    """This process's role node for the cross-process handoff fabric
    (ISSUE 17, ``serving.disaggregation.transport: "process"``): roles
    are assigned BY RANK — rank 0 builds the prefill engine(s) plus
    the router (:class:`~deepspeed_tpu.serving.transport.PrefillNode`),
    every other rank builds one decode engine
    (:class:`~deepspeed_tpu.serving.transport.DecodeNode`). One device
    per process, sequential collectives — the documented
    gloo-flake-stable recipe (tests/test_multiprocess_dist.py).

    Every rank must run the SAME config (the decode pool geometry the
    router's backpressure default assumes is the one this rank would
    build). ``endpoint`` defaults to the live
    :class:`~deepspeed_tpu.serving.transport.ProcessEndpoint`; tests
    pass :class:`~deepspeed_tpu.serving.transport.LoopbackFabric`
    endpoints to run both roles in one process."""
    from deepspeed_tpu.serving.transport import (DecodeNode,
                                                 PrefillNode,
                                                 ProcessEndpoint)
    from deepspeed_tpu.config import constants as C
    pd = _param_dict(config)
    sc = _serving_section(pd)
    dg, rt = sc.disaggregation, sc.router
    adapter = _adapter_from_config(family, model_config, params, pd,
                                   **overrides)
    mc = None
    if C.MONITOR in pd:
        from deepspeed_tpu.config.config import MonitorConfig
        mc = MonitorConfig(pd)   # SLO plane + live endpoint gates
    if endpoint is None:
        # ISSUE 18: addressing "targeted" (default) moves dst-addressed
        # frames point-to-point, "broadcast" keeps the PR-17 legacy leg
        endpoint = ProcessEndpoint(
            addressing=dg.addressing,
            payload_timeout_s=dg.payload_timeout_s)
    assert endpoint.world >= 2, (
        f"the process transport needs >= 2 ranks (prefill + decode), "
        f"got world={endpoint.world}")
    if endpoint.rank == 0:
        prefills = []
        for i in range(max(dg.prefill_replicas, 1)):
            cb = ContinuousBatcher(
                adapter, registry=registry, recorder=recorder,
                prefix_cache=sc.prefix_cache.enabled or rt.prefix_routing,
                prefix_cow=sc.prefix_cache.cow, role="prefill")
            cb.replica_id = f"prefill{i}"
            prefills.append(cb)
        # default backpressure bound mirrors DisaggRouter's: 2x the
        # decode pools' allocatable total (same spec on every rank)
        alloc = prefills[0].cache.num_blocks - 1
        bound = rt.max_inflight_pages \
            or 2 * alloc * (endpoint.world - 1)
        node = PrefillNode(
            prefills, endpoint, registry=registry, recorder=recorder,
            max_inflight_pages=bound,
            max_inflight_pages_per_rank=(
                rt.max_inflight_pages_per_rank or None),
            max_handoff_retries=rt.max_handoff_retries,
            on_tick=on_tick, on_done=on_done)
        if mc is not None:
            # ISSUE 19: the rank-0 SLO plane — windowed per-role
            # quantiles + burn rate over the exchanged metrics vector,
            # exported as slo/* gauges each tick
            from deepspeed_tpu.telemetry.slo import SloPlane
            node.slo = SloPlane.from_config(mc.slo)
            if mc.serve_port:
                # live /metrics + /healthz on the router rank; /healthz
                # carries the targeted-transport fabric liveness
                # (per-peer connected / last-payload age) so a
                # half-dead socket mesh is visible BEFORE a
                # payload_timeout_s trips (ISSUE 19 satellite)
                from deepspeed_tpu.telemetry.serve import \
                    start_metrics_server
                node.metrics_server = start_metrics_server(
                    mc.serve_port, host=mc.serve_host,
                    registry=node.metrics,
                    extra_health_fn=getattr(endpoint, "fabric_health",
                                            None))
        return node
    cb = ContinuousBatcher(adapter, registry=registry, recorder=recorder,
                           prefix_cache=dg.dedupe_pages,
                           prefix_cow=sc.prefix_cache.cow, role="decode")
    cb.replica_id = f"decode{endpoint.rank}"
    return DecodeNode(cb, endpoint, registry=registry,
                      recorder=recorder,
                      decode_ticks=rt.decode_tick_cap,
                      on_tick=on_tick, on_absorb=on_absorb)
