"""Ask the TPU compiler, without a chip, whether the WHOLE programs build
(``tests/test_tpu_compile.py`` says how, and asks the same of the kernels at
the cells' shapes): at GPT-2 large (774M) widths ``generate``'s static-cache
decode loop, the serving engine's prefill and decode tick, the ZeRO-3 train
step on one chip and on four, and the OLMoE cell's step — from
``jax.eval_shape`` shapes. The later cells' whole steps are minutes each and
slow-marked, in ``tests/test_tpu_compile.py`` beside their kernels'."""

import dataclasses
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import chip_smoke  # (the model, batch and serving block it runs)
from deepspeed_tpu.telemetry.registry import default_registry
from tests import hlo_text
from tests.described_chip import (  # noqa: F401 (the fixture: autouse)
    BF16, F32, HBM_BYTES, I8, I32, SDS, _compile_for_the_chip, _paged_pool,
    compile_on_chip, flash_calls, head_major_operands, kernel_names, on_chip,
    topo)


# ------------------------------------------------ whole programs, 774M

def _serving_trees(quantize):
    """(cfg, converted inference-param shapes) of GPT-2 large — bf16 or the
    int8 serving storage — without allocating a weight."""
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.models.gpt2_inference import (
        convert_gpt2_params, quantize_gpt2_inference_params)
    cfg = dataclasses.replace(chip_smoke.model_config(rehearse=False),
                              remat=False, loss_chunk=0)
    params = jax.eval_shape(
        lambda r: GPT2LMHeadModel(cfg).init(
            r, jnp.zeros((1, 8), I32))["params"], jax.random.PRNGKey(0))

    def convert(p):
        ip = convert_gpt2_params(p, cfg)
        return quantize_gpt2_inference_params(ip) if quantize else ip
    return cfg, jax.eval_shape(convert, params)


@pytest.mark.parametrize("quantize", [False, True], ids=["bf16", "int8"])
def test_generate_decode_loop_compiles(quantize):
    """``gpt2_inference.generate``'s compiled decode scan over the static
    cache: ln_qkv / decode_attention_{fp,int8} / out_ffn stacked kernels."""
    from deepspeed_tpu.models.gpt2_inference import _fast_decode_scan_fn
    cfg, ip = _serving_trees(quantize)
    L, Lyr, H, D = cfg.n_positions, cfg.n_layer, cfg.n_head, cfg.head_dim
    if quantize:
        caches = (SDS((Lyr, 1, H, L, D), I8), SDS((Lyr, 1, H, L), F32)) * 2
    else:
        caches = (SDS((Lyr, 1, H, L, D), BF16),) * 2
    steps = chip_smoke.NEW_TOKENS - 1
    fast = _fast_decode_scan_fn(cfg, L, weights_q8=quantize,
                                cache_q8=quantize)
    key = jax.eval_shape(lambda: jax.random.split(jax.random.PRNGKey(0),
                                                  steps))
    p = {k: ip[k] for k in ("wte", "wpe", "ln_f")}
    args = on_chip((p, ip["h"]["blk"], caches, SDS((1,), I32)))
    tail = on_chip((SDS((), I32), key, SDS((), F32)))
    lowered = fast.lower(*args, steps, *tail)
    lowered.compile()
    attn = "_decode_attn_stacked_kernel"
    want = {"_ln_qkv_stacked_kernel", attn, "_out_ffn_stacked_kernel"}
    if quantize:
        want.add("_kv_quant_kernel")
    assert kernel_names(lowered.as_text()) == want


@pytest.mark.parametrize("bits", [0, 8], ids=["bf16-cache", "int8-cache"])
def test_serving_engine_programs_compile(bits):
    """The programs ``eng.serve`` dispatches in chip_smoke's serve phase:
    the decode tick over all slots and one page-bucketed prefill."""
    from deepspeed_tpu.serving import GPT2ServingAdapter
    cfg, ip = _serving_trees(quantize=False)
    spec, pool = _paged_pool(bits)
    adapter = GPT2ServingAdapter(cfg, ip, spec)
    B, MAXP, Pg = spec.slots, spec.max_pages_per_slot, spec.page_size
    vec = lambda dt: SDS((B,), dt)  # noqa: E731
    text, _ = compile_on_chip(
        adapter._tick_fn(1), adapter._p, adapter._blk, pool, vec(I32),
        vec(I32), SDS((B, MAXP), I32), vec(jnp.uint32), vec(I32), vec(F32))
    assert kernel_names(text) == {
        "_ln_qkv_stacked_kernel", "_decode_attn_paged_kernel",
        "_out_ffn_stacked_kernel"} | ({"_kv_quant_kernel"} if bits else set())
    pages = 8                        # a 128-token bucket
    text, _ = compile_on_chip(
        adapter._prefill_fn(pages), adapter._p, adapter._blk, pool,
        SDS((1, pages * Pg), I32), SDS((), I32), SDS((pages,), I32))
    assert kernel_names(text) == {"_fwd_kernel"}


def lower_train_step(n_devices):
    """chip_smoke's train step — GPT-2 large, ZeRO-3, batch 8 — lowered for
    ``n_devices`` described chips: the engine is built on a mesh of described
    devices and handed state SHAPES under its own shardings, since nothing
    can be placed on a chip that is not attached."""
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.runtime import precision as prec
    from deepspeed_tpu.runtime.engine import TrainState

    cfg = chip_smoke.model_config(rehearse=False)
    mesh = Mesh(np.asarray(topo().devices[:n_devices]).reshape(
        (1, n_devices, 1, 1, 1)), mesh_lib.AXIS_ORDER)
    engine, _, _, _ = dstpu.initialize(
        config=chip_smoke.train_config(0, rehearse=False),
        model=GPT2LMHeadModel(cfg), mesh=mesh)
    ids = SDS((chip_smoke.BATCH, cfg.n_positions), I32)
    params = jax.eval_shape(
        lambda r, x: engine.module.init(r, x)["params"],
        jax.random.PRNGKey(0), ids)
    state = TrainState(
        params=params, opt_state=jax.eval_shape(engine.optimizer.init, params),
        scaler=jax.eval_shape(
            lambda: prec.init_scaler_state(engine.precision)),
        global_step=SDS((), I32), skipped_steps=SDS((), I32))
    engine.state_shardings = engine._build_state_shardings(state)
    engine._build_jit_fns()
    state = jax.tree_util.tree_map(
        lambda s, sh: SDS(s.shape, s.dtype, sharding=sh), state,
        engine.state_shardings)
    rng = jax.random.PRNGKey(0)
    return engine._jit_train_batch.lower(
        state, {"input_ids": on_chip(ids, mesh_lib.batch_sharding(mesh))},
        on_chip(SDS(rng.shape, rng.dtype), NamedSharding(mesh, P())))


@pytest.fixture(scope="module")
def one_chip_step():
    lowered = lower_train_step(1)
    return lowered.as_text(), lowered.compile()


def test_train_step_compiles_for_one_chip_with_flash_and_fits(one_chip_step):
    text, compiled = one_chip_step
    assert "tpu_custom_call" in text
    assert kernel_names(text) == {"_fwd_kernel", "_bwd_fused_kernel"}
    ma = compiled.memory_analysis()
    # the compiler refuses a program over the chip's memory; state alone
    # (fp32 params + bf16/fp32 Adam moments of 774M) is just under half of it
    assert 7.0e9 < ma.argument_size_in_bytes < HBM_BYTES / 2
    assert not re.search(r"all-gather|all-reduce|reduce-scatter",
                         compiled.as_text())


def assert_scope_names(hlo):
    """The names ``benchmark/scope_reduce.py`` joins device events to
    (``telemetry.spans.annotate`` lists them): every Pallas call sits under
    its kernel's scope, and each phase scope reaches some ``op_name``."""
    kernels = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert kernels and all(
        re.search(r'op_name="[^"]*/flash_(fwd|bwd)/', ln) for ln in kernels)
    for scope in ("ds_optimizer", "ds_loss_head", "ds_embed",
                  "transpose(jvp(", "rematted_computation"):
        assert re.search(r'op_name="[^"]*' + re.escape(scope), hlo), scope


def test_train_step_scope_names_reach_the_compiled_text(one_chip_step):
    assert_scope_names(one_chip_step[1].as_text())


def head_matmuls(hlo, vocab):
    """The compiled text's matmuls (``dot`` / ``convolution``) traced under
    ``ds_loss_head`` that have the vocabulary among the dimensions of
    their result or operands, as (instruction line, op_name)."""
    dims = {name: shape.split(",") for name, shape in re.findall(
        r"(%[\w.\-]+) = \(?\w+\[([\d,]*)\]", hlo)}
    lines = hlo.splitlines()
    out = []
    for ln in (hlo_text.instructions(lines, "dot")
               + hlo_text.instructions(lines, "convolution")):
        op_name = re.search(r'op_name="([^"]*ds_loss_head[^"]*)"', ln)
        names = re.findall(r"%[\w.\-]+", ln.split("metadata=")[0])
        if op_name and any(str(vocab) in dims.get(n, ()) for n in names):
            out.append((ln, op_name.group(1)))
    return out


def test_the_loss_head_derives_its_logits_once_a_step():
    """PR 51: ``chunked_lm_loss`` forms dlogits, dhidden and dW in the
    forward chunk. The differentiated step of a small GPT-2 under remat
    holds the three matmuls the mathematics has (logits, dlogits @ wte,
    dlogitsᵀ @ h), all in the forward scan; nothing of the head's scope is
    computed again in the backward pass; a step that only scores holds
    the logits' matmul alone."""
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel, gpt2_tiny
    vocab = 640                     # no other dimension of the model
    model = GPT2LMHeadModel(gpt2_tiny(
        vocab_size=vocab, loss_chunk=32, remat=True, dtype=BF16))
    ids = SDS((2, 64), I32)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros(ids.shape, I32)))

    def score(p, i):
        return model.apply(p, i, labels=i)

    _, scored = compile_on_chip(score, params, ids)
    assert len(head_matmuls(scored.as_text(), vocab)) == 1
    _, stepped = compile_on_chip(jax.value_and_grad(score), params, ids)
    hlo = stepped.as_text()
    matmuls = head_matmuls(hlo, vocab)
    assert len(matmuls) == 3, matmuls
    assert all("transpose(jvp(" not in op_name and "/while/body/" in op_name
               for _, op_name in matmuls), matmuls
    # the layers' remat is there; the head's is not
    assert re.search(r'op_name="[^"]*rematted_computation', hlo)
    assert not re.search(
        r'op_name="[^"]*(ds_loss_head[^"]*rematted_computation|'
        r'rematted_computation[^"]*ds_loss_head)', hlo)


def assert_attention_in_the_models_layout(hlo):
    """ISSUE 30 in a compiled step of GPT-2 large: the flash calls take
    [B, S, H*D] operands (nothing head-major), and inside the two layer
    scans XLA builds NO ``copy`` under ``blk/attn/`` (20 a layer on one
    chip and 11 on four before: q, k, v, o, do, dq, dk, dv into head-major
    and back) and no ``split`` — E = 10 lane blocks, so the fused
    projection is read in place."""
    calls = flash_calls(hlo)
    assert len(calls) == 2 and not head_major_operands(calls), calls
    layer_scans = [lines for lines in hlo_text.loop_bodies(hlo).values()
                   if any("/blk/" in ln for ln in lines)]
    assert len(layer_scans) == 2        # forward and backward
    for lines in layer_scans:
        attn = [ln for ln in lines
                if re.search(r'op_name="[^"]*/blk/attn/', ln)]
        assert attn
        assert not hlo_text.instructions(attn, "copy")
        assert not [ln for ln in attn if re.search(
            r'op_name="[^"]*/blk/attn/[^"]*split', ln)]


def test_one_chip_step_keeps_attention_in_the_models_layout(one_chip_step):
    assert_attention_in_the_models_layout(one_chip_step[1].as_text())


def test_one_chip_train_step_emits_no_gather_edge(one_chip_step, monkeypatch):
    """With a data axis of one the ZeRO-3 gather edge does not exist: the
    step lowers to the same text, so the same instructions per opcode, as
    with the edge's source (``mesh_lib.pinned_gather_edge``) cut off."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    monkeypatch.setattr(mesh_lib, "pinned_gather_edge", lambda: None)
    assert lower_train_step(1).as_text() == one_chip_step[0]


def test_train_step_compiles_for_four_chips_sharded(one_chip_step):
    """ZeRO-3 over data=4: the flash kernel survives partitioning (inside a
    shard_map — GSPMD refuses a bare Mosaic call), every chip holds a quarter
    of the state, and the step gathers parameters — the WEIGHTS, through the
    gather edge: no activation is re-laid (no all-to-all), no sharded small
    leaf is gathered as update-slice + all-reduce inside a layer scan, and
    a chip's temporaries stay under the one-chip step's."""
    lowered = lower_train_step(4)
    assert kernel_names(lowered.as_text()) == {"_fwd_kernel",
                                               "_bwd_fused_kernel"}
    compiled = lowered.compile()
    quarter = one_chip_step[1].memory_analysis().argument_size_in_bytes / 4
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert abs(per_chip - quarter) <= chip_smoke.SPREAD_RTOL * quarter
    assert compiled.memory_analysis().temp_size_in_bytes <= \
        one_chip_step[1].memory_analysis().temp_size_in_bytes
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo and "all-gather" in hlo
    assert_scope_names(hlo)     # inside the shard_map too
    # ... and there the column-block path engaged, two heads a block
    assert_attention_in_the_models_layout(hlo)
    assert not hlo_text.instructions(hlo.splitlines(), "all-to-all")
    layer_scans = [lines for lines in hlo_text.loop_bodies(hlo).values()
                   if any("/blk/" in ln for ln in lines)]
    assert len(layer_scans) == 2        # forward and backward
    for lines in layer_scans:
        assert hlo_text.instructions(lines, "all-gather")
        assert not [ln for ln in hlo_text.instructions(lines, "all-reduce")
                    if "(%dynamic-update-slice" in ln
                    and hlo_text.result_elements(ln) < 1e5]


def test_olmoe_step_compiles_for_one_chip_with_its_scopes_and_fits():
    """The WHOLE step of the benchmark's ``olmoe-train-1chip-s4096`` cell
    (OLMoE-1B-7B at depth 1, 4 x 4096 tokens, ZeRO-3, through the family's
    ``lower_train_step``) is accepted for a 16 GB chip, its program peaks
    under the 15.75 GiB a program may use, and every scope the benchmark
    reads reaches an ``op_name`` of the compiled text."""
    from benchmark import manifest
    bench = manifest.load()
    cell = manifest.cell_of(bench, "olmoe-train-1chip-s4096")
    config = manifest.config_of(bench, cell)
    lowered = manifest.family_module(config).lower_train_step(
        config, manifest.traffic_of(cell), topo().devices[:1])
    assert kernel_names(lowered.as_text()) == {
        "_fwd_kernel_chunked", "_bwd_kernel_chunked", "kernel"}
    # head-major, from ``models/llama.py``: ISSUE 30's path is bypassed
    assert default_registry().peek_gauge(
        "attention/flash_heads_per_block") == 0
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert 6.0e9 < ma.argument_size_in_bytes < 6.5e9      # 625.6M x 10 B
    assert 10e9 < ma.peak_memory_in_bytes < 15.75 * 2 ** 30
    hlo = compiled.as_text()
    assert not re.search(r"all-gather|all-reduce|reduce-scatter", hlo)
    kernels = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert kernels and all(re.search(
        r'op_name="[^"]*/(flash_(fwd|bwd)|moe_gmm)[a-z_]*/', ln)
        for ln in kernels)
    for scope in ("moe_gmm", "moe_gmm_dlhs", "moe_gmm_drhs", "moe_router",
                  "moe_dispatch", "moe_combine", "qk_norm", "flash_fwd_chunk",
                  # one chunk: dq leaves the kernel whole, no slabs' sum
                  "flash_bwd_chunk", "ds_loss_head", "ds_embed",
                  "ds_optimizer"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
