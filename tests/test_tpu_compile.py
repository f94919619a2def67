"""Ask the TPU compiler, without a chip, whether the main path builds.

The TPU compiler is installed here and compiles for a chip that is described
and not attached (``jax.experimental.topologies``, the on-chip-measurement
guide §2.3). Interpret-mode tests cannot see what it refuses: a block not
aligned to the tiling, too much scoped VMEM, a kernel GSPMD cannot partition,
a program that does not fit 16 GB. These compiles guard every later PR at no
chip time: the Pallas kernels of the main path at GPT-2 large (774M) widths,
and the whole programs ``chip_smoke.py`` runs — the ZeRO-3 train step on one
chip and on four, the serving engine's prefill and decode tick, and
``generate``'s static-cache decode loop — from ``jax.eval_shape`` shapes.

Nothing runs, so nothing here says a result is right or how long it takes; a
compile that passes is not a chip run.

The optional grouped-quantize kernel does not build for a TPU on the
installed jax. It is a strict xfail that quotes the compiler, so the day it
starts to compile its test says so; nothing on the main path may select it
(ROADMAP S2).
"""

import collections
import contextlib
import functools
import re

import pytest
import jax
import jax.numpy as jnp

import chip_smoke  # (the model, batch and serving block it runs)
from deepspeed_tpu.telemetry.registry import default_registry
from tests import hlo_text
from tests.described_chip import (  # noqa: F401 (the fixture: autouse)
    BF16, F32, HBM_BYTES, I8, I32, SDS, _compile_for_the_chip, _paged_pool,
    assert_dense_lse_kept, compile_on_chip, flash_calls, head_major_operands,
    kernel_names, pallas_grids, rematted, topo)


# ------------------------------------------------------- main-path kernels

@pytest.mark.parametrize("shape", [(chip_smoke.BATCH, 20, 1024, 64),
                                   (4, 25, 1024, 64), (2, 16, 2048, 64)])
def test_flash_attention_fwd_and_grad_compile_at_774m_shape(shape):
    """The whole-row kernels with their strip walk (ISSUE 28) at what a
    chip sees of GPT-2 large (8 x 20 heads) and XL (4 x 25), and at the
    longest row they take at head_dim 64 (S 2048: 256 KB): lane-dense
    lse/delta pieces, fp32 VMEM scratch for the softmax state and for
    dq/dk/dv, the transposed backward tile."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    qkv = (SDS(shape, BF16),) * 3

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(*a, causal=True)
                        .astype(F32).sum(), argnums=(0, 1, 2))(q, k, v)

    text, compiled = compile_on_chip(grads, *qkv)
    assert kernel_names(text) == {"_fwd_kernel", "_bwd_fused_kernel"}


@pytest.mark.parametrize("operands,heads", [
    ((SDS((chip_smoke.BATCH, 1024, 3840), BF16),), 20),
    ((SDS((4, 1024, 1600), BF16),) * 3, 25),
    ((SDS((2, 2048, 3840), BF16),), 20),
    ((SDS((4, 1024, 6144), BF16),), 16),
], ids=["large-qkv-in-place", "xl-q-k-v-25-heads", "s2048", "head_dim128"])
def test_flash_attention_column_blocks_compile_at_gpt2_shapes(operands,
                                                              heads):
    """The same two kernels on the model's own layout (ISSUE 30): heads as
    128-lane column blocks of [B, S, H*D]. GPT-2 large's fused projection
    read in place (E = 10 lane blocks: three views of one array), XL's q,
    k, v apart with 25 heads (E = 12.5 blocks: the last block ragged, its
    second head skipped), the longest row, and a head a block at head_dim
    128. No operand or result is head-major: nothing of [.., 1024, 64]."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention_bse

    def grads(*a):
        return jax.grad(lambda *b: flash_attention_bse(
            *b, heads=heads, causal=True).astype(F32).sum(),
            argnums=tuple(range(len(a))))(*a)

    text, compiled = compile_on_chip(grads, *operands)
    assert kernel_names(text) == {"_fwd_kernel", "_bwd_fused_kernel"}
    width = operands[0].shape[-1] // (3 if len(operands) == 1 else 1)
    assert default_registry().peek_gauge(
        "attention/flash_heads_per_block") == 128 // min(width // heads, 128)
    calls = flash_calls(compiled.as_text())
    assert len(calls) == 2 and not head_major_operands(calls)


def test_flash_attention_chunked_fwd_and_grad_compile_at_olmoe_shape():
    """4 x 16 heads x 4096 x head_dim 128, bf16, causal: a row of 1 MB takes
    the CHUNKED kernels (``_UNCHUNKED_ROW_BYTES`` 256 KB), which no GPT-2
    cell compiles. Nothing had to change for them (ISSUE 27)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    qkv = (SDS((4, 16, 4096, 128), BF16),) * 3

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(*a, causal=True)
                        .astype(F32).sum(), argnums=(0, 1, 2))(q, k, v)

    text, compiled = compile_on_chip(grads, *qkv)
    assert kernel_names(text) == {"_fwd_kernel_chunked",
                                  "_bwd_kernel_chunked"}
    # two grid dimensions: the heads' rows, and the 8 (block, chunk) pairs
    # of 8 x 1 — one chunk of S rows, 2 MiB of K + V a step (ISSUE 48; 20
    # of 8 x 4 at chunks of 1,024 before it, ISSUE 39)
    assert pallas_grids(grads, *qkv) == [(64, 8)] * 2
    hlo = compiled.as_text()
    assert_dense_lse_kept(hlo, flash_calls(hlo), "f32[64,32,1,128]")


@pytest.mark.parametrize("m,g,k,n", [
    (131072, 64, 2048, 1024),       # OLMoE's layer
    (49152, 16, 2560, 768),         # SmallThinker's slab, gate / up ...
    (49152, 16, 768, 2560),         # ... and down: no powers of two
    (6144, 8, 2688, 1856),          # Nemotron-3-Nano's up: 1,856 = 29 x 64,
    (6144, 8, 1856, 2688),          # ... and down, padded to 1,920 lanes
], ids=["olmoe", "smallthinker_up", "smallthinker_down", "nemotron_up",
        "nemotron_down"])
def test_grouped_matmul_fwd_and_grads_compile_at_olmoe_shape(m, g, k, n):
    """131,072 routed rows x 2048 against 64 experts' [2048, 1024]: the
    forward product and both gradients (dlhs against the transposed weights,
    drhs the per-group outer products), three Pallas calls, each under a
    ``moe_gmm*`` scope; the group sizes are an operand, not a shape. And at
    widths of 5 x 512 and 3 x 256, with the divisor tiles ``_clip`` gives
    them (PR 38); and at a width no multiple of 128 divides, which the call
    pads to the next one (PR 40)."""
    from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

    def bank(lhs, rhs, sizes):
        # a scope round it, as the model's modules are: JAX writes the
        # transform round the FIRST scope inside it (``jvp(mlp)/moe_gmm``)
        with jax.named_scope("mlp"):
            return grouped_matmul(lhs, rhs, sizes).astype(F32).sum()

    def grads(lhs, rhs, sizes):
        # the product is linear: only its value needs the forward kernel
        return jax.value_and_grad(lambda a, b: bank(a, b, sizes),
                                  argnums=(0, 1))(lhs, rhs)

    text, compiled = compile_on_chip(
        grads, SDS((m, k), BF16), SDS((g, k, n), BF16), SDS((g,), I32))
    assert text.count("tpu_custom_call") == 3
    hlo = compiled.as_text()
    for scope in ("moe_gmm/", "moe_gmm_dlhs/", "moe_gmm_drhs/"):
        assert re.search(r'op_name="[^"]*/' + scope, hlo), scope


def assert_rows_reach_tokens_in_one_pass(hlo, M, T):
    """What the form before ISSUE 36 left in a step: the all-pairs search of
    T tokens among M sorted rows and the shifted float32 copies of the slab."""
    assert f"[{T},{M}]" not in hlo and f"[{M},{T}]" not in hlo
    # (XLA writes a shift as a pad that carries the concatenate's op_name)
    assert not re.search(
        rf'= f32\[{M},2048\][^\n]*op_name="[^"]*/concatenate"', hlo)


@pytest.mark.parametrize("M,k", [(20480, 10), (32768, 8)],
                         ids=["qwen3next", "laguna"])
def test_rows_to_tokens_kernel_compiles_at_the_held_cells_shapes(M, k):
    """The held experts' rows back to 16,384 tokens at the two cells' slabs
    ([20480, 2048], k 10 and [32768, 2048], k 8): the float32 call of the
    forward pass under ``moe_combine`` and the bfloat16 one that is the
    backward pass of ``tokens_to_rows`` under ``moe_dispatch``, each ONE
    Pallas call under its caller's scope and a ``rows_to_tokens`` of its own
    (no kernel tag's prefix); no [T, M] compare and no shifted slab."""
    from deepspeed_tpu.moe.dropless import rows_to_tokens, tokens_to_rows
    from deepspeed_tpu.telemetry.spans import annotate
    T = 16384

    def layer(rows, x, tok):
        with jax.named_scope("mlp"):
            with annotate("moe_dispatch"):
                xs = tokens_to_rows(x, tok, k)
            with annotate("moe_combine"):
                y = rows_to_tokens(rows * xs.astype(F32), tok, T, k)
            return jnp.sum(y * y)

    text, compiled = compile_on_chip(
        jax.value_and_grad(layer, argnums=(0, 1)), SDS((M, 2048), F32),
        SDS((T, 2048), BF16), SDS((M,), I32))
    assert kernel_names(text) == {"_rows_to_tokens_kernel"}
    hlo = compiled.as_text()
    calls = flash_calls(hlo)
    assert len(calls) == 2, calls
    for scope, result in (("moe_combine", f"f32[{T},2048]"),
                          ("moe_dispatch", f"bf16[{T},2048]")):
        assert sum(bool(re.search(
            rf'op_name="[^"]*/{scope}/rows_to_tokens/', c))
            and result in c.split(" custom-call(")[0] for c in calls) == 1
    assert_rows_reach_tokens_in_one_pass(hlo, M, T)
    # the rows in token order once (float32: M x 8 KB) beside operands and
    # results; the shifted-add form held three such slabs
    assert compiled.memory_analysis().temp_size_in_bytes < 2.2 * M * 2048 * 4


@pytest.mark.parametrize("seq", [64, 128, 256])
def test_flash_attention_compiles_at_prefill_buckets(seq):
    """The serve phase's page-bucketed prompt lengths, batch 1."""
    from deepspeed_tpu.ops.attention import dot_product_attention
    qkv = (SDS((1, 20, seq, 64), BF16),) * 3
    text, _ = compile_on_chip(
        functools.partial(dot_product_attention, causal=True), *qkv)
    assert kernel_names(text) == {"_fwd_kernel"}


@pytest.mark.parametrize("rows", [1, 4], ids=["single", "multiquery"])
@pytest.mark.parametrize("bits", [0, 8], ids=["bf16", "int8"])
def test_paged_decode_attention_compiles_at_serve_pool_shape(bits, rows):
    from deepspeed_tpu.ops.pallas.decode import decode_attention_paged
    spec, pool = _paged_pool(bits)
    B, MAXP = spec.slots, spec.max_pages_per_slot
    q = SDS((B, spec.kv_heads, rows, spec.head_dim), BF16)
    pos, pt, layer = SDS((B,), I32), SDS((B, MAXP), I32), SDS((), I32)
    mq = {"rows_per_step": 1} if rows > 1 else {}

    def attend(q, pool, pos, pt, layer):
        if bits == 8:
            kc, ks, vc, vs = pool
            return decode_attention_paged(q, kc, vc, pos, pt, layer,
                                          k_scale=ks, v_scale=vs, **mq)
        return decode_attention_paged(q, *pool, pos, pt, layer, **mq)

    text, _ = compile_on_chip(attend, q, pool, pos, pt, layer)
    assert kernel_names(text) == {"_decode_attn_paged_kernel"}


@pytest.mark.parametrize("wdtype", [BF16, I8], ids=["bf16", "int8"])
def test_matvec_stacked_compiles_at_774m_widths(wdtype):
    from deepspeed_tpu.ops.pallas.decode import matvec_int8_stacked
    text, _ = compile_on_chip(
        matvec_int8_stacked, SDS((1, 1280), BF16),
        SDS((36, 1280, 1280), wdtype), SDS((36,), F32), SDS((), I32))
    assert kernel_names(text) == {"_matvec_stacked_kernel"}


def test_flash_attention_chunked_gqa_compiles_at_qwen3_next_shape():
    """2 x 16 query / 2 KV heads x 8192 x head_dim 256, bf16, causal: a row of
    4 MB takes the chunked kernels at chunk 2,048 (``_CHUNK_BYTES``: 2 MiB of
    K + V a step; 4,096 rows are 4 MiB, which the compiler refused in the dkv
    kernel of PR 48), and K and V go in at their 2 heads: the kernels' index
    maps fold a query head onto its group (``_kv_row``), nothing is repeated
    in HBM, dk and dv are summed over a group's 8 query heads after."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    def grads(q, k, v):
        return jax.grad(rematted(lambda *a: flash_attention(
            *a, causal=True).astype(F32).sum()), argnums=(0, 1, 2))(q, k, v)

    shapes = (SDS((2, 16, 8192, 256), BF16), SDS((2, 2, 8192, 256), BF16),
              SDS((2, 2, 8192, 256), BF16))
    text, compiled = compile_on_chip(grads, *shapes)
    assert kernel_names(text) == {"_fwd_kernel_chunked",
                                  "_bwd_kernel_chunked"}
    # 40 of the 16 x 4 (block, chunk) pairs, at four blocks a chunk (136 of
    # 16 x 16 at one block a chunk before ISSUE 48)
    assert pallas_grids(grads, *shapes) == [(32, 40)] * 2
    # every Pallas call reads K and V at 4 = 2 x 2 rows, none at 32
    hlo = compiled.as_text()
    calls = flash_calls(hlo)
    assert len(calls) == 2 and all("bf16[4,8192,256]" in c for c in calls)
    # under the blocks' remat policy, as the cell's attention layer is
    assert_dense_lse_kept(hlo, calls, "f32[32,64,1,128]")


@pytest.mark.parametrize("heads,kv_heads", [(48, 8), (28, 4), (32, 2)],
                         ids=["laguna", "smallthinker", "nemotron"])
def test_flash_attention_chunked_compiles_at_the_s16384_cells_shapes(
        heads, kv_heads):
    """1 x 48 / 8, 28 / 4 and 32 / 2 heads x 16,384 x head_dim 128, bf16,
    causal (the full-attention layers of the Laguna, SmallThinker and
    Nemotron cells): the two chunked kernels under their scopes on grid
    (heads, 80) — the (block, chunk) pairs a causal row needs of 32 x 4, at
    chunks of 4,096 rows (ISSUE 48; 272 of 32 x 16 before it) — K and V read
    at their own heads, under the blocks' remat policy."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    def attend(*a):
        with jax.named_scope("attn"):
            return flash_attention(*a, causal=True).astype(F32).sum()

    def grads(q, k, v):
        return jax.grad(rematted(attend), argnums=(0, 1, 2))(q, k, v)

    shapes = (SDS((1, heads, 16384, 128), BF16),
              SDS((1, kv_heads, 16384, 128), BF16),
              SDS((1, kv_heads, 16384, 128), BF16))
    text, compiled = compile_on_chip(grads, *shapes)
    assert kernel_names(text) == {"_fwd_kernel_chunked",
                                  "_bwd_kernel_chunked"}
    assert pallas_grids(grads, *shapes) == [(heads, 80)] * 2
    hlo = compiled.as_text()
    calls = flash_calls(hlo)
    assert all(f"bf16[{kv_heads},16384,128]" in c for c in calls)
    for scope in ("flash_fwd_chunk", "flash_bwd_chunk", "flash_bwd_dq_sum"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
    assert "16384,16384" not in hlo
    assert_dense_lse_kept(hlo, calls, f"f32[{heads},128,1,128]")


@pytest.mark.parametrize("dtype,pairs", [(BF16, 288), (F32, 544)],
                         ids=["bf16-chunk4096", "f32-chunk2048"])
def test_flash_attention_chunked_compiles_where_the_budget_was_first_met(
        dtype, pairs):
    """16 heads x 32,768 x head_dim 64, causal, forward + backward: the shape
    at which a 4,096-row chunk overflowed scoped VMEM by 0.9 MB in the kernels
    of PR 27 and set the first chunk budget. The kernels since (the (o, m, l)
    carry, the lane-dense statistics, the pair list) take it: 64 lanes are a
    128-lane tile in VMEM, so the one budget gives this shape what it gives
    head_dim 128 — 4,096 rows in bf16 (288 pairs a head of 64 x 8), 2,048 in
    float32 (544 of 64 x 16)."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    qkv = (SDS((1, 16, 32768, 64), dtype),) * 3

    def grads(q, k, v):
        return jax.grad(lambda *a: flash_attention(*a, causal=True)
                        .astype(F32).sum(), argnums=(0, 1, 2))(q, k, v)

    text, compiled = compile_on_chip(grads, *qkv)
    assert kernel_names(text) == {"_fwd_kernel_chunked",
                                  "_bwd_kernel_chunked"}
    assert pallas_grids(grads, *qkv) == [(16, pairs)] * 2
    assert "32768,32768" not in compiled.as_text()


def test_flash_attention_chunked_compiles_at_the_latent_attention_shape():
    """1 x 32 heads x 16,384, a q·k head of 192 (128 + the 64 rotated) and
    a value head of 128, bf16, causal (every layer of the Kanana-2 cell):
    the two chunked kernels under their scopes, K read 192 wide and V 128
    wide — V is not padded to 192 in HBM — o and dv 128 wide, dq's float32
    partials and dk 192 (dk and dv leave the backward kernel in bf16), no
    [S, S] scores, under the blocks' remat policy."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    def attend(*a):
        with jax.named_scope("attn"):
            return flash_attention(*a, causal=True).astype(F32).sum()

    def grads(q, k, v):
        return jax.grad(rematted(attend), argnums=(0, 1, 2))(q, k, v)

    shapes = (SDS((1, 32, 16384, 192), BF16), SDS((1, 32, 16384, 192), BF16),
              SDS((1, 32, 16384, 128), BF16))
    text, compiled = compile_on_chip(grads, *shapes)
    assert kernel_names(text) == {"_fwd_kernel_chunked",
                                  "_bwd_kernel_chunked"}
    hlo = compiled.as_text()
    calls = flash_calls(hlo)
    assert len(calls) == 2
    assert all("bf16[32,16384,192]" in c and "bf16[32,16384,128]" in c
               for c in calls)
    fwd, bwd = calls
    assert "f32[32,16384,128]" in fwd and "f32[32,16384,192]" not in fwd
    # the 80 pairs' dq partials, and dk / dv in the operands' dtype
    results = bwd.split(" custom-call(")[0]
    assert "f32[32,80,512,192]" in results and "f32[32,16384" not in results
    for scope in ("flash_fwd_chunk", "flash_bwd_chunk", "flash_bwd_dq_sum"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
    assert "16384,16384" not in hlo
    assert_dense_lse_kept(hlo, calls, "f32[32,128,1,128]")


@pytest.mark.parametrize("D,Dv", [(128, 128), (192, 128)],
                         ids=["d128", "d192-values-128"])
def test_chunked_forward_alone_compiles_inside_the_default_scoped_vmem(D, Dv):
    """The chunked FORWARD alone at [32, 16,384, 128] and at latent
    attention's 192 / 128, bf16, causal: one ``_fwd_kernel_chunked`` on grid
    (32, 80) — blocks of 512 under the 4,096-row chunk ``_pick_chunk`` gives
    both — that asks for NO scoped VMEM of its own and compiles inside the
    default 16 MiB: its walk keeps (o, m, l) in the revisited output block
    and two [512, 128] scratch tiles and carries nothing (ISSUE 67), beside
    the double-buffered K and V chunks. A later change to ``_CHUNK_BYTES`` or
    to what the walk holds cannot silently refuse the step."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention

    def attend(q, k, v):
        return flash_attention(q, k, v, causal=True)

    shapes = (SDS((1, 32, 16384, D), BF16), SDS((1, 32, 16384, D), BF16),
              SDS((1, 32, 16384, Dv), BF16))
    text, compiled = compile_on_chip(attend, *shapes)
    assert kernel_names(text) == {"_fwd_kernel_chunked"}
    assert pallas_grids(attend, *shapes) == [(32, 80)]
    assert not re.findall(r'size\\22: (\d+)', text)      # no vmem_limit asked
    hlo = compiled.as_text()
    (call,) = flash_calls(hlo)
    assert f"f32[32,16384,{Dv}]" in call and "16384,16384" not in hlo
    assert re.search(r'op_name="[^"]*/flash_fwd_chunk/', hlo)


@pytest.mark.parametrize("D,kernels,scopes", [
    (128, {"_gdn_fwd_kernel", "_gdn_bwd_kernel"},
     ("gdn_scan_prep/", "gdn_scan_fwd/", "gdn_scan_bwd/")),
    (64, set(), ("gdn_scan_prep/", "gdn_scan/"))])
def test_gated_delta_rule_fwd_and_grad_compile_at_qwen3_next_shape(
        D, kernels, scopes):
    """2 x 8192 tokens, 16 key / 32 value heads, bf16 operands and float32
    gates, forward and backward under the scopes the benchmark's
    ``gdn_scan_*`` readers sum: heads of 128 x 128 (the model's) take the
    Pallas kernels on the model's own [B, S, H*D] arrays, four value heads
    a grid step; heads of 64 are not lane-aligned column blocks and keep the
    XLA chunked form."""
    from deepspeed_tpu.ops.gated_delta import gated_delta_rule
    from deepspeed_tpu.telemetry.registry import default_registry

    def scan(*a):
        # a scope round it, as the model's module is: JAX writes the
        # transform round the FIRST scope inside it
        with jax.named_scope("linear_attn"):
            return gated_delta_rule(*a).astype(F32).sum()

    def grads(q, k, v, g, beta):
        return jax.grad(scan, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    text, compiled = compile_on_chip(
        grads, SDS((2, 8192, 16, D), BF16), SDS((2, 8192, 16, D), BF16),
        SDS((2, 8192, 32, D), BF16), SDS((2, 8192, 32), F32),
        SDS((2, 8192, 32), F32))
    assert kernel_names(text) == kernels
    assert default_registry().peek_gauge(
        "linear_attn/gdn_kernel_heads_per_step") == (4 if kernels else 0)
    hlo = compiled.as_text()
    for scope in scopes:
        assert re.search(r'op_name="[^"]*/' + scope, hlo), scope
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == len(kernels) and all(re.search(
        r'op_name="[^"]*/gdn_scan_(fwd|bwd)/', ln) for ln in calls)
    # the forward rule's states (268 MB) and nothing of [N, B, H, C, D]
    assert compiled.memory_analysis().peak_memory_in_bytes < (
        2e9 if kernels else 8e9)


def test_gated_delta_rule_compiles_at_olmo_hybrids_heads_off_the_lane_grid():
    """1 x 8192 tokens, 30 key = 30 value heads of the PUBLISHED 96 x 192
    (the ``olmohybrid-train-1chip-s8192`` cell's DeltaNet layer), bf16
    operands and float32 gates, forward and backward: the rule pads the
    heads to whole tiles (128 x 256) under ``gdn_scan_prep`` and runs the
    Pallas kernels, two value heads a grid step, 15 programs x 16 blocks of
    8 chunks; o comes back 192 wide. Heads of 64 keep the XLA form (the
    case above): whole tiles would double them."""
    from deepspeed_tpu.ops.gated_delta import gated_delta_rule
    from deepspeed_tpu.telemetry.registry import default_registry

    def scan(*a):
        with jax.named_scope("linear_attn"):
            o = gated_delta_rule(*a)
            assert o.shape == (1, 8192, 30, 192)
            return o.astype(F32).sum()

    def grads(q, k, v, g, beta):
        return jax.grad(scan, argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)

    shapes = (SDS((1, 8192, 30, 96), BF16), SDS((1, 8192, 30, 96), BF16),
              SDS((1, 8192, 30, 192), BF16), SDS((1, 8192, 30), F32),
              SDS((1, 8192, 30), F32))
    text, compiled = compile_on_chip(grads, *shapes)
    assert kernel_names(text) == {"_gdn_fwd_kernel", "_gdn_bwd_kernel"}
    gauge = default_registry().peek_gauge
    assert gauge("linear_attn/gdn_kernel_heads_per_step") == 2
    assert gauge("linear_attn/gdn_lane_overcompute") == pytest.approx(
        33280 / 18816)
    assert pallas_grids(grads, *shapes) == [(15, 16), (15, 16)]
    hlo = compiled.as_text()
    for scope in ("gdn_scan_prep/", "gdn_scan_fwd/", "gdn_scan_bwd/"):
        assert re.search(r'op_name="[^"]*/' + scope, hlo), scope
    # the forward rule's states at 128 x 256 (251 MB) and inverses (126 MB)
    assert compiled.memory_analysis().peak_memory_in_bytes < 1.5e9


@pytest.mark.parametrize("groups,head_blocks", [(8, 1), (1, 8)],
                         ids=["nemotron_h_8_groups", "granite_one_group"])
def test_ssd_scan_fwd_and_grad_compile_at_nemotron_h_shape(groups,
                                                           head_blocks):
    """1 x 16,384 tokens, 64 heads of 64 in 8 groups, state 128, chunk 128,
    bf16 operands and float32 gates (the ``nemotron3nano-train-1chip-s16384``
    cell's Mamba-2 layer), forward and backward under the scopes the
    benchmark's ``ssd_scan_*`` readers sum: two heads side by side a lane
    block, a group's eight heads a grid step; what is kept for the backward
    pass is a float32 state a chunk (268 MB) and nothing of [c, c] size.
    And the same heads in ONE group (``granite4hmicro-train-1chip-s16384``):
    eight head blocks of 8 read the group's one B and C, and their float32
    parts of dB and dC (8 x 8 MB each) are summed outside the kernel."""
    from deepspeed_tpu.ops.ssd import ssd_scan

    def scan(*a):
        with jax.named_scope("mamba"):
            return ssd_scan(*a).astype(F32).sum()

    def grads(*a):
        return jax.grad(scan, argnums=tuple(range(6)))(*a)

    text, compiled = compile_on_chip(
        grads, SDS((1, 16384, 64, 64), BF16), SDS((1, 16384, 64), F32),
        SDS((64,), F32), SDS((1, 16384, groups, 128), BF16),
        SDS((1, 16384, groups, 128), BF16), SDS((64,), F32))
    assert kernel_names(text) == {"_ssd_fwd_kernel", "_ssd_bwd_kernel"}
    assert default_registry().peek_gauge(
        "ssm/ssd_kernel_heads_per_step") == 8
    assert default_registry().peek_gauge(
        "ssm/ssd_head_blocks_per_group") == head_blocks
    grids = pallas_grids(grads, *(SDS(*a) for a in (
        ((1, 16384, 64, 64), BF16), ((1, 16384, 64), F32), ((64,), F32),
        ((1, 16384, groups, 128), BF16), ((1, 16384, groups, 128), BF16),
        ((64,), F32))))
    assert set(grids) == {(8, 16)}, grids       # 8 programs x 16 steps
    hlo = compiled.as_text()
    for scope in ("ssd_scan_prep/", "ssd_scan_fwd/", "ssd_scan_bwd/"):
        assert re.search(r'op_name="[^"]*/' + scope, hlo), scope
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2 and all(re.search(
        r'op_name="[^"]*/ssd_scan_(fwd|bwd)/', ln) for ln in calls)
    assert "f32[1,32,128,128,128]" in hlo       # the kept states, and ...
    assert compiled.memory_analysis().peak_memory_in_bytes < 1.2e9


def _scan_under_remat(kind):
    """(the scan of ``kind`` as a loss, its operands' shapes at the cells'
    sizes, the scopes of its two kernels)."""
    if kind == "gdn":
        from deepspeed_tpu.ops.gated_delta import gated_delta_rule as scan
        shapes = (SDS((2, 8192, 16, 128), BF16), SDS((2, 8192, 16, 128), BF16),
                  SDS((2, 8192, 32, 128), BF16), SDS((2, 8192, 32), F32),
                  SDS((2, 8192, 32), F32))
    else:
        from deepspeed_tpu.ops.ssd import ssd_scan as scan
        shapes = (SDS((1, 16384, 64, 64), BF16), SDS((1, 16384, 64), F32),
                  SDS((64,), F32), SDS((1, 16384, 8, 128), BF16),
                  SDS((1, 16384, 8, 128), BF16), SDS((64,), F32))
    return (lambda *a: scan(*a).astype(F32).sum()), shapes


@pytest.mark.parametrize("kept", [True, False], ids=["kept", "not_kept"])
@pytest.mark.parametrize("kind,residuals", [
    # o and the states bf16, T float32; y bf16 and the states float32
    ("gdn", ("bf16[2,8192,4096]", "bf16[2,32,128,128,128]",
             "f32[2,32,128,64,64]")),
    ("ssd", ("bf16[1,16384,4096]", "f32[1,32,128,128,128]"))])
def test_a_scan_whose_name_is_kept_compiles_to_one_forward_call(kind, kept,
                                                                residuals):
    """The two scans at their cells' shapes under a block's remat, for the
    described chip. A policy that keeps ``scan_states``: TWO Pallas calls —
    the forward rule's kernel in the forward pass, whose results (the
    output, the states; the delta rule's ``T``) live to the backward
    kernel — and nothing under ``rematted_computation``. One that does not:
    three, the primal call writing the output alone."""
    from deepspeed_tpu.ops.pallas.scan_residuals import SCAN_NAME
    loss, shapes = _scan_under_remat(kind)
    block = jax.checkpoint(
        loss, prevent_cse=True,
        policy=jax.checkpoint_policies.save_only_these_names(
            *((SCAN_NAME,) if kept else ())))
    # the loss too, as a step returns it: the forward pass's output is read
    grads = jax.value_and_grad(block, argnums=tuple(range(len(shapes))))
    _, compiled = compile_on_chip(grads, *shapes)
    hlo = compiled.as_text()
    calls = flash_calls(hlo)
    again = hlo_text.rematted_scopes(hlo, hlo_text.FORWARD_SCAN_SCOPES)
    forwards = [c for c in calls if re.search(r"_scan_fwd/", c)]
    if kept:
        assert len(calls) == 2 and again == [] and len(forwards) == 1
        assert all(shape in forwards[0].split(" custom-call(")[0]
                   for shape in residuals), forwards
    else:
        assert len(calls) == 3 and len(again) == 1 and len(forwards) == 2
        alone = [c for c in forwards if "rematted_computation" not in c]
        assert len(alone) == 1 and not any(
            shape in alone[0].split(" custom-call(")[0]
            for shape in residuals[1:]), alone


# what ``runtime/remat_budget.py`` keeps in the cells whose kept set PR 64
# moved, and in Kanana-2, whose ``qkv`` (3,334 MB) the compiler refused at
# 16.17 GiB when forced: names kept, their MB, ``scan_states`` among them
REMAT_RULE_CELLS = {
    "granite4hmicro-train-1chip-s16384": (3, 3282, False),
    "qwen3next-train-1chip-s8192": (6, 4217, True),
    "xing4-train-1chip-s4096": (5, 1464, False),
    "nemotron3nano-train-1chip-s16384": (5, 3632, True),
    "kanana2-train-1chip-s16384": (3, 1351, False)}


@pytest.mark.slow
@pytest.mark.parametrize("cell_name", sorted(REMAT_RULE_CELLS))
def test_the_remat_rules_kept_set_fits_the_chips_program_memory(cell_name):
    """The WHOLE step of a cell with what the byte rule keeps, compiled for
    a described v5e: at or under 15.9 GB (the program's 15.75 GiB less the
    rule's headroom), the fall-back not taken. Where ``scan_states`` is kept
    a scan's forward kernel is called once a layer and never under
    ``rematted_computation``. Kanana-2's kept set and compiled peak are the
    parent's, byte for byte (13,478,071,296: ``qkv`` stays out by the
    rule's arithmetic). 1-2.5 minutes a cell: slow-marked (the rule at
    these shapes without a compile: ``tests/test_remat_budget.py``)."""
    from benchmark import manifest
    from deepspeed_tpu.runtime import remat_budget
    names, kept_mb, scans = REMAT_RULE_CELLS[cell_name]
    bench = manifest.load()
    cell = manifest.cell_of(bench, cell_name)
    config = manifest.config_of(bench, cell)
    compiled = manifest.family_module(config).lower_train_step(
        config, manifest.traffic_of(cell), topo().devices[:1]).compile()
    gauge = default_registry().peek_gauge
    assert gauge("remat/kept_names") == names
    assert gauge("remat/kept_mb") == pytest.approx(kept_mb, abs=1)
    assert gauge("remat/scan_states_kept") == scans
    peak = compiled.memory_analysis().peak_memory_in_bytes
    assert peak <= remat_budget.PROGRAM_HBM_BYTES["TPU v5 lite"] \
        - remat_budget.HEADROOM_BYTES, peak
    if cell_name.startswith("kanana2"):
        assert peak == 13_478_071_296
    hlo = compiled.as_text()
    again = hlo_text.rematted_scopes(hlo, hlo_text.FORWARD_SCAN_SCOPES)
    forwards = [c for c in flash_calls(hlo) if re.search(r"_scan_fwd/", c)]
    if scans:
        assert again == [] and len(forwards) in (3, 4), (again, forwards)
    elif forwards:                     # granite: nine mixers, run twice
        assert len(again) == 9 and len(forwards) == 18


def mixer_sites():
    """The ``mixer/*_sites`` gauges by their short names."""
    return {name: default_registry().peek_gauge(f"mixer/{name}_sites") or 0
            for name in ("conv_kernel", "conv_xla", "norm_kernel",
                         "norm_xla")}


def mixer_calls(hlo):
    """{the scope round a mixer kernel's call: the kernels' scopes under
    it}, from the compiled text's Pallas calls."""
    found = {}
    for ln in hlo.splitlines():
        m = "tpu_custom_call" in ln and re.search(
            r'op_name="[^"]*/(\w+)/(mixer_\w+)/pallas_call', ln)
        if m:
            found.setdefault(m.group(1), set()).add(m.group(2))
    return found


@pytest.mark.parametrize("stage,scope,shapes,kw", [
    ("conv", "gdn_conv", (SDS((2, 8192, 12288), BF16), SDS((4, 8192), F32)),
     dict(runs=((2048, 128 ** -0.5), (2048, 1.0), (4096, None)),
          head_width=128)),
    ("conv", "ssm_conv", (SDS((1, 16384, 10304), BF16), SDS((4, 6144), F32),
                          SDS((6144,), F32)),
     dict(offset=4096, runs=((4096, None), (1024, None), (1024, None)))),
    ("norm", "gdn_out_norm", (SDS((2, 8192, 4096), BF16),
                              SDS((2, 8192, 12288), BF16), SDS((128,), F32)),
     dict(group=128, eps=1e-6, gate_first=False, offset=8192)),
    ("norm", "ssm_norm", (SDS((1, 16384, 4096), BF16),
                          SDS((1, 16384, 10304), BF16), SDS((4096,), F32)),
     dict(group=512, eps=1e-5, gate_first=True)),
    ("conv", "ssm_conv", (SDS((1, 16384, 8512), BF16), SDS((4, 4352), F32),
                          SDS((4352,), F32)),
     dict(offset=4096, runs=((4096, None), (128, None), (128, None)))),
    ("norm", "ssm_norm", (SDS((1, 16384, 4096), BF16),
                          SDS((1, 16384, 8512), BF16), SDS((4096,), F32)),
     dict(group=4096, eps=1e-5, gate_first=True)),
], ids=["qwen3_next_conv", "nemotron_conv", "qwen3_next_norm",
        "nemotron_norm", "granite_conv", "granite_norm_one_group_of_4096"])
def test_mixer_elementwise_fwd_and_grad_compile_at_the_cells_shapes(
        stage, scope, shapes, kw):
    """The two recurrent mixers' elementwise stages at the shapes of the
    ``qwen3next-train-1chip-s8192`` and ``nemotron3nano-train-1chip-s16384``
    cells (and of ``granite4hmicro-train-1chip-s16384``: ONE B and C of
    128 columns, the norm over one group of all 4,096 channels), bf16 in
    and out, forward and every gradient: the Pallas kernels
    take the call (columns read by offset out of the wide projection, no
    slice of it formed), under the scope the model puts round them."""
    from deepspeed_tpu.ops import mixer_elementwise as mixer
    entry = mixer.conv_act if stage == "conv" else mixer.gated_group_norm
    before = mixer_sites()

    def total(*a):
        # a scope round the stage's, as the model's module is: JAX writes
        # the transform round the FIRST scope inside it
        with jax.named_scope("layer"), jax.named_scope(scope):
            out = entry(*a, **kw)
        return sum(t.astype(F32).sum() for t in jax.tree_util.tree_leaves(
            out))

    def both(*a):
        return jax.value_and_grad(total, argnums=tuple(range(len(a))))(*a)

    text, compiled = compile_on_chip(both, *shapes)
    assert kernel_names(text) == {f"_mixer_{stage}_fwd_kernel",
                                  f"_mixer_{stage}_bwd_kernel"}
    after = mixer_sites()
    assert after[f"{stage}_kernel"] == before[f"{stage}_kernel"] + 1
    assert after[f"{stage}_xla"] == before[f"{stage}_xla"]
    hlo = compiled.as_text()
    assert mixer_calls(hlo) == {scope: {f"mixer_{stage}_fwd",
                                        f"mixer_{stage}_bwd"}}
    # no slice of the wide array (the projection's output, the operand with
    # most columns): only Pallas calls read it
    columns = max(shapes, key=lambda t: t.shape[-1] * (len(t.shape) == 3))
    wide = re.escape(f"bf16[{','.join(map(str, columns.shape))}]")
    readers = [ln for ln in hlo.splitlines() if re.search(
        r"= [^=]*\b(fusion|slice|copy)\(.*" + wide, ln)]
    assert readers == [], readers[:2]
    # bf16 blocks in, bf16 blocks out, float32 only the parameters' sums
    assert compiled.memory_analysis().peak_memory_in_bytes < 1.6e9


@pytest.mark.parametrize("cell,module,scopes", [
    ("qwen3next-train-1chip-s8192", "GatedDeltaNet",
     {"gdn_conv": "conv", "gdn_out_norm": "norm"}),
    ("nemotron3nano-train-1chip-s16384", "Mamba2Mixer",
     {"ssm_conv": "conv", "ssm_norm": "norm"}),
    # heads of 96 x 192, laid out zero-padded to whole lane tiles once
    ("olmohybrid-train-1chip-s8192", "GatedDeltaNet",
     {"gdn_conv": "conv", "gdn_out_norm": "norm"})])
def test_recurrent_mixer_layer_compiles_with_the_mixer_kernels_in_its_scopes(
        cell, module, scopes):
    """One recurrent mixer of each cell at the cell's configuration and
    batch (the benchmark's own file, through its family), forward and
    gradient under remat as a block runs it: the four kernel names sit under
    the scopes whose rows the benchmark sums into ``gdn_layer_ms`` /
    ``ssm_layer_ms``, and no site fell to the XLA forms."""
    import importlib
    from benchmark import manifest
    bench = manifest.load()
    entry = manifest.cell_of(bench, cell)
    config = manifest.config_of(bench, entry)
    family = manifest.family_module(config)
    cfg = family.model_config(config, False)
    traffic = manifest.traffic_of(entry)
    models = importlib.import_module(family._model(config, False).__module__)
    layer = getattr(models, module)(cfg)
    x = SDS((traffic["global_batch"], traffic["seq_len"], cfg.hidden_size),
            BF16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            SDS((1, 128, cfg.hidden_size), BF16))
    before = mixer_sites()

    def total(params, x):
        return rematted(lambda p, x: layer.apply(p, x).astype(F32).sum())(
            params, x)

    _, compiled = compile_on_chip(jax.grad(total, argnums=(0, 1)), params, x)
    after = mixer_sites()
    for stage in ("conv", "norm"):
        assert after[f"{stage}_kernel"] > before[f"{stage}_kernel"]
        assert after[f"{stage}_xla"] == before[f"{stage}_xla"]
    assert mixer_calls(compiled.as_text()) == {
        scope: {f"mixer_{stage}_fwd", f"mixer_{stage}_bwd"}
        for scope, stage in scopes.items()}


@pytest.mark.slow
def test_qwen3_next_step_compiles_for_one_chip_with_its_scopes_and_fits():
    """The WHOLE step of the benchmark's ``qwen3next-train-1chip-s8192`` cell
    (one period of Qwen3-Next as one of 16 expert-parallel ranks, 2 x 8192
    tokens, ZeRO-3, through the family's ``lower_train_step``) is accepted
    for a 16 GB chip, and every scope the benchmark reads reaches an
    ``op_name`` of the compiled text. ~2 minutes: slow-marked."""
    from benchmark import manifest
    bench = manifest.load()
    cell = manifest.cell_of(bench, "qwen3next-train-1chip-s8192")
    config = manifest.config_of(bench, cell)
    before = mixer_sites()
    lowered = manifest.family_module(config).lower_train_step(
        config, manifest.traffic_of(cell), topo().devices[:1])
    assert kernel_names(lowered.as_text()) == {
        "_fwd_kernel_chunked", "_bwd_kernel_chunked", "kernel",
        "_gdn_fwd_kernel", "_gdn_bwd_kernel", "_rows_to_tokens_kernel",
        "_mixer_conv_fwd_kernel", "_mixer_conv_bwd_kernel",
        "_mixer_norm_fwd_kernel", "_mixer_norm_bwd_kernel"}
    sites = mixer_sites()
    assert (sites["conv_xla"], sites["norm_xla"]) == (
        before["conv_xla"], before["norm_xla"])
    assert sites["conv_kernel"] > before["conv_kernel"]
    assert sites["norm_kernel"] > before["norm_kernel"]
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert 6.0e9 < ma.argument_size_in_bytes < 6.5e9      # 625.7M x 10 B
    assert 10e9 < ma.peak_memory_in_bytes < 15.75 * 2 ** 30
    hlo = compiled.as_text()
    assert not re.search(r"all-gather|all-reduce|reduce-scatter", hlo)
    # the attention layer's forward kernel runs once: its o and lse are kept
    assert hlo_text.rematted_forward_attention(hlo) == []
    assert default_registry().peek_gauge(
        "attention/flash_residual_mb") == pytest.approx(135.3, abs=0.1)
    for scope in ("gdn_conv", "gdn_gates", "gdn_scan_prep", "gdn_scan_fwd",
                  "gdn_scan_bwd", "gdn_out_norm", "attn_gate", "qk_norm",
                  "moe_shared", "moe_gmm", "moe_gmm_dlhs", "moe_gmm_drhs",
                  "moe_router", "moe_dispatch", "moe_combine",
                  "flash_fwd_chunk",
                  "flash_bwd_chunk", "flash_bwd_dq_sum", "linear_attn", "attn",
                  "mlp", "ds_loss_head", "ds_embed", "ds_optimizer",
                  # the held rows' way back, forward and backward: one kernel
                  "moe_combine/rows_to_tokens", "moe_dispatch/rows_to_tokens"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
    assert mixer_calls(hlo) == {
        "gdn_conv": {"mixer_conv_fwd", "mixer_conv_bwd"},
        "gdn_out_norm": {"mixer_norm_fwd", "mixer_norm_bwd"}}
    assert_rows_reach_tokens_in_one_pass(hlo, 20480, 16384)


def _window_backward_results(calls):
    """[(dtype, dims)] of what the ONE window backward call of a compiled
    text's Pallas ``calls`` returns."""
    bwd, = (c.split(" custom-call(")[0] for c in calls if "swa_bwd" in c)
    return re.findall(r"(\w+)\[([\d,]+)\]", bwd.split("=", 1)[1])


def _window_kernels_compile(H, Hkv, W, band_rows, lag, tiles, peak):
    """1 x ``H`` query / ``Hkv`` KV heads x 16,384 x head_dim 128, bf16,
    window ``W``: the two window kernels — the backward ONE call (PR 53) that
    asks for its scoped VMEM — with K and V read at their ``Hkv`` heads and
    dk, dv written there in the operands' dtype (summed over a group's query
    heads inside the kernel: no float32 gradient array for XLA to cast or
    sum), a block's whole band ONE operand block of ``band_rows`` rows — the
    third grid extent 1, the whole group's heads a backward step, the second
    ``lag`` steps past the 32 blocks for the ring's last key blocks to leave
    — and no [S, S] array."""
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    S = 16384

    def attend(*a):
        # a scope round it, as the model's module is: JAX writes the
        # transform round the FIRST scope inside it
        with jax.named_scope("attn"):
            return flash_attention(*a, causal=True,
                                   window=W).astype(F32).sum()

    def grads(q, k, v):
        return jax.grad(rematted(attend), argnums=(0, 1, 2))(q, k, v)

    shapes = (SDS((1, H, S, 128), BF16), SDS((1, Hkv, S, 128), BF16),
              SDS((1, Hkv, S, 128), BF16))
    jaxpr = jax.make_jaxpr(grads)(*shapes).jaxpr
    assert hlo_text.pallas_grids(jaxpr) == [(H, 32, 1), (Hkv, 32 + lag, 1)]
    # the band's operands: K and V, forward and backward
    assert hlo_text.pallas_element_rows(jaxpr) == [band_rows] * 4
    assert default_registry().peek_gauge(
        "attention/window_tiles_per_grid_step") == pytest.approx(tiles,
                                                                 abs=0.005)
    assert default_registry().peek_gauge(
        "attention/window_bwd_tiles_per_grid_step") == \
        H // Hkv * band_rows // 512
    text, compiled = compile_on_chip(grads, *shapes)
    assert kernel_names(text) == {"_swa_fwd_kernel", "_swa_bwd_kernel"}
    hlo = compiled.as_text()
    calls = flash_calls(hlo)
    assert len(calls) == 2 and all(f"bf16[{Hkv},16384,128]" in c
                                   for c in calls)
    # dq at the query heads, dk and dv at the KV heads, all bf16: no float32
    # gradient leaves the call, and none is a per-(block, tile) partial
    assert _window_backward_results(calls) == [
        ("bf16", f"{H},16384,128")] + [("bf16", f"{Hkv},16384,128")] * 2
    for scope in ("swa_fwd", "swa_bwd"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
    assert not re.search(r"swa_bwd_d(q|kv)", hlo)
    assert "16384,16384" not in hlo
    # under the blocks' remat policy, as the cell's window layers are
    assert_dense_lse_kept(hlo, calls, f"f32[{H},128,1,128]")
    # q, k, v, o, their gradients and the forward's fp32 output
    assert compiled.memory_analysis().peak_memory_in_bytes < peak


def test_window_kernels_fwd_and_grad_compile_at_laguna_shape():
    """64 / 8 heads, window 512: a band of 1,024 rows, 2 tiles a forward
    step (1.97 with the first block's one) and the group's 8 heads a
    backward step (Q, dO and dq 1 MiB each, the ring 2 x 0.5 MiB), under
    2.5 GB as before PR 43."""
    _window_kernels_compile(64, 8, 512, 1024, 1, 3.49, 2.5e9)


def test_window_kernels_fwd_and_grad_compile_at_smallthinker_shape():
    """28 / 4 heads, window 4,096: a band of 4,608 rows (1.18 MB a bf16
    operand, K and V double-buffered 4.7 MB), 9 tiles a step less what the
    first eight blocks clip; the group's 7 heads a backward step (which the
    split dkv kernel's per-head Q / dO bands could not hold) and a ring of
    2 x 2.36 MB."""
    _window_kernels_compile(28, 4, 4096, 4608, 8, 13.36, 1.2e9)


@pytest.mark.parametrize("H,Hkv,S,D,W,dtype,blocks,band", [
    # W 4,096 at head_dim 256: the band in two steps, the ring whole
    (16, 2, 16384, 256, 4096, BF16, {}, ((5, 2), (8, 4608, 8))),
    # float32 operands: two steps, four of the group's heads a step
    (8, 2, 16384, 128, 4096, F32, {}, ((5, 2), (8, 4608, 4))),
    # W 16,384 at S 32,768: three steps of 11 tiles, a ring of 16,896 rows
    (8, 2, 32768, 128, 16384, BF16, {}, ((11, 3), (32, 16896, 4))),
    # W no multiple of the block
    (8, 2, 16384, 128, 300, BF16, {}, ((2, 1), (1, 1024, 4))),
    (8, 2, 16384, 128, 4096, BF16, dict(block_q=256, block_k=512),
     ((9, 1), (16, 4608, 4))),
    (8, 2, 16384, 128, 4096, BF16, dict(block_q=512, block_k=256),
     ((18, 1), (8, 4608, 4))),
], ids=lambda v: str(v))
def test_window_backward_compiles_at_the_shapes_the_split_kernels_took(
        H, Hkv, S, D, W, dtype, blocks, band):
    """The shapes PR 43 showed to lower in the dq / dkv pair lower in the
    single-pass backward, under the plan ``_band_plan`` gives each (no knob
    selects): one forward and one backward call, gradients in the operands'
    dtype, dk and dv at the KV heads."""
    from tests.flash_cases import _fa
    fa = _fa()
    bq, bk = blocks.get("block_q", 512), blocks.get("block_k", 512)
    assert fa._band_plan(S, bq, bk, W, max(D, 128) * jnp.dtype(dtype).itemsize,
                         H // Hkv) == band

    def grads(q, k, v):
        return jax.grad(lambda *a: fa.flash_attention(
            *a, causal=True, window=W, **blocks).astype(F32).sum(),
            argnums=(0, 1, 2))(q, k, v)

    shapes = (SDS((1, H, S, D), dtype), SDS((1, Hkv, S, D), dtype),
              SDS((1, Hkv, S, D), dtype))
    text, compiled = compile_on_chip(grads, *shapes)
    assert kernel_names(text) == {"_swa_fwd_kernel", "_swa_bwd_kernel"}
    calls = flash_calls(compiled.as_text())
    assert len(calls) == 2
    name = "bf16" if dtype == BF16 else "f32"
    assert _window_backward_results(calls) == [
        (name, f"{H},{S},{D}")] + [(name, f"{Hkv},{S},{D}")] * 2


@pytest.mark.slow
def test_laguna_step_compiles_for_one_chip_with_its_scopes_and_fits():
    """The WHOLE step of the benchmark's ``laguna-train-1chip-s16384`` cell
    (Laguna-XS.2's layers 0-4 as one of 8 expert-parallel ranks, 1 x 16,384
    tokens, ZeRO-3, through the family's ``lower_train_step``) is accepted
    for a 16 GB chip with the two window kernels beside the causal chunked
    ones, and every scope the benchmark reads reaches an ``op_name`` of the
    compiled text. ~2 minutes: slow-marked (the kernels alone: the test
    above)."""
    from benchmark import manifest
    bench = manifest.load()
    cell = manifest.cell_of(bench, "laguna-train-1chip-s16384")
    config = manifest.config_of(bench, cell)
    lowered = manifest.family_module(config).lower_train_step(
        config, manifest.traffic_of(cell), topo().devices[:1])
    assert kernel_names(lowered.as_text()) == {
        "_fwd_kernel_chunked", "_bwd_kernel_chunked", "kernel",
        "_swa_fwd_kernel", "_swa_bwd_kernel", "_rows_to_tokens_kernel"}
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert 6.8e9 < ma.argument_size_in_bytes < 7.0e9      # 691.6M x 10 B
    # between 25 % and 100 % of the chip
    assert 0.25 * HBM_BYTES < ma.peak_memory_in_bytes < 15.75 * 2 ** 30
    hlo = compiled.as_text()
    assert not re.search(r"all-gather|all-reduce|reduce-scatter", hlo)
    assert "16384,16384" not in hlo                       # no [S, S] array
    # the five layers' forward kernels run once: o and lse are kept
    assert hlo_text.rematted_forward_attention(hlo) == []
    assert default_registry().peek_gauge(
        "attention/flash_residual_mb") == pytest.approx(1226.8, abs=0.1)
    for scope in ("swa_fwd", "swa_bwd", "flash_fwd_chunk",
                  "flash_bwd_chunk", "flash_bwd_dq_sum", "attn_gate",
                  "dense_mlp", "moe_shared", "moe_gmm", "moe_gmm_dlhs",
                  "moe_gmm_drhs", "moe_router", "moe_dispatch",
                  "moe_combine", "attn", "mlp",
                  "ds_loss_head", "ds_embed", "ds_optimizer",
                  # the held rows' way back, forward and backward: one kernel
                  "moe_combine/rows_to_tokens", "moe_dispatch/rows_to_tokens"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
    assert_rows_reach_tokens_in_one_pass(hlo, 32768, 16384)


@pytest.mark.slow
def test_kanana2_step_compiles_for_one_chip_with_its_scopes_and_fits():
    """The WHOLE step of the benchmark's ``kanana2-train-1chip-s16384`` cell
    (Kanana-2's layers 0-5 as one of 8 expert-parallel ranks, 1 x 16,384
    tokens, ZeRO-3, through the family's ``lower_train_step``) is accepted
    for a 16 GB chip: latent attention in the two chunked causal kernels
    with K 192 wide and V 128 wide in every layer, nothing [S, S], every
    scope the benchmark reads in an ``op_name`` of the compiled text. ~3
    minutes (200 s beside five other workers, PR 52): slow-marked (the
    kernels alone: the cell's two attention kernels at its own shape in
    ``test_flash_attention_chunked_compiles_at_the_latent_attention_shape``;
    the grouped matmul and ``rows_to_tokens`` in their own compile tests
    above, at the other routed cells' slabs)."""
    from benchmark import manifest
    bench = manifest.load()
    cell = manifest.cell_of(bench, "kanana2-train-1chip-s16384")
    config = manifest.config_of(bench, cell)
    lowered = manifest.family_module(config).lower_train_step(
        config, manifest.traffic_of(cell), topo().devices[:1])
    assert kernel_names(lowered.as_text()) == {
        "_fwd_kernel_chunked", "_bwd_kernel_chunked", "kernel",
        "_rows_to_tokens_kernel"}
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert 6.8e9 < ma.argument_size_in_bytes < 6.95e9     # 687.5M x 10 B
    # between 25 % and 100 % of the chip
    assert 0.25 * HBM_BYTES < ma.peak_memory_in_bytes < 15.75 * 2 ** 30
    hlo = compiled.as_text()
    assert not re.search(r"all-gather|all-reduce|reduce-scatter", hlo)
    assert "16384,16384" not in hlo                       # no [S, S] array
    # six layers x (forward, backward): the forward kernels run once, o and
    # lse are kept; K goes in 192 wide, V 128 wide, never padded to 192
    flash = [c for c in flash_calls(hlo) if "bf16[32,16384,192]" in c]
    assert len(flash) == 12
    assert all("bf16[32,16384,128]" in c for c in flash)
    assert hlo_text.rematted_forward_attention(hlo) == []
    assert default_registry().peek_gauge("attention/mla_qk_dim") == 192
    assert default_registry().peek_gauge("attention/mla_v_dim") == 128
    for scope in ("flash_fwd_chunk", "flash_bwd_chunk", "flash_bwd_dq_sum",
                  "mla_attn", "mla_latent", "mla_expand", "mla_rope",
                  "dense_mlp", "moe_shared", "moe_gmm", "moe_gmm_dlhs",
                  "moe_gmm_drhs", "moe_router", "moe_dispatch", "moe_combine",
                  "mlp", "ds_loss_head", "ds_embed", "ds_optimizer",
                  "moe_combine/rows_to_tokens", "moe_dispatch/rows_to_tokens"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope


# ------------------------------------------------- the residual streams

MHC_KERNELS = {
    "mixer": {"_mhc_mix_kernel", "_mhc_mix_bwd_kernel"},
    "write": {"_mhc_write_kernel", "_mhc_write_bwd_kernel"}}
MHC_KERNELS["branch"] = MHC_KERNELS["mixer"] | MHC_KERNELS["write"]


def mhc_calls(hlo):
    """Counter of (phase, tag) over the compiled text's Pallas calls, as the
    benchmark places them (``benchmark/scope_reduce.py`` with the Xing4.0
    family's tags)."""
    import collections
    from benchmark import scope_reduce
    from benchmark.families import xing4
    return collections.Counter(
        (scope_reduce.phase_of(op_name), scope_reduce.tag_of(op_name, xing4))
        for ln in hlo.splitlines() if "tpu_custom_call" in ln
        for op_name in re.findall(r'op_name="([^"]*)"', ln))


MHC_KERNELS["ends"] = {"_mhc_tile_kernel", "_mhc_sum_kernel"}


@pytest.mark.parametrize("entry", ["mixer", "write", "branch", "ends"])
def test_residual_stream_kernels_fwd_and_grad_compile_at_the_xing4_shape(
        entry):
    """The stream mixers at the ``xing4-train-1chip-s4096`` cell's shape —
    a stream of [1, 4096, 4 x 3584] bf16, ``phi`` [14336, 24] float32 —
    value and every gradient, each entry alone, a branch's three together
    and a trunk's two ends: all six passes, with their loops over the column
    slabs and over Sinkhorn's rounds, are accepted by Mosaic; whole rows of
    14,336 columns fit the scoped VMEM the calls ask for; the kernels take
    the call under the benchmark's scopes, each defined ONCE in the module
    (a trunk's ends twice: the call and the other end's transpose)."""
    import flax.linen as nn
    from deepspeed_tpu.models import hyper_connections as hc
    mixer = hc.StreamMixer(n=4, phi_std=0.02, gate_mean=0.25, gate_std=0.05,
                           bias_std=0.5)
    x, y = SDS((1, 4096, 14336), BF16), SDS((1, 4096, 3584), BF16)
    params = jax.eval_shape(mixer.init, jax.random.PRNGKey(0), x)["params"]
    assert (params["phi"].shape, params["phi"].dtype) == ((14336, 24), F32)
    coeff = (SDS((4, 4096), F32), SDS((4, 4096), F32),
             SDS((4, 4, 4096), F32))

    def run(params, x, y, coeff):
        with jax.named_scope("layer"):
            if entry == "write":
                return (hc.write(x, y, *coeff[1:]),)
            if entry == "mixer":
                return mixer.apply({"params": params}, x,
                                   mutable=["stats"])[0]
            if entry == "ends":
                with jax.named_scope("mhc_write"):
                    spread = hc.spread(y, 4)
                with jax.named_scope("mhc_read"):
                    return (hc.merge(x + spread, 4),)
            (u, (_, post, res), through), _ = nn.apply(
                hc.mix, mixer, mutable=["stats"])({"params": params}, x)
            return (hc.write(through, u + y, post, res),)

    def total(*a):
        return sum(t.astype(F32).sum() for t in run(*a))

    before = [default_registry().peek_gauge(f"mhc/{form}_sites") or 0
              for form in ("kernel", "xla")]
    text, compiled = compile_on_chip(
        jax.value_and_grad(total, argnums=(0, 1, 2, 3)), params, x, y, coeff)
    assert kernel_names(text) == MHC_KERNELS[entry]
    definitions = re.findall(r'kernel_name = "([^"]+)"', text)
    assert len(definitions) == len(MHC_KERNELS[entry]) * (
        2 if entry == "ends" else 1), definitions
    # the scoped VMEM each call asks for, and is built inside
    scoped = set(re.findall(r'size\\22: (\d+)', text))
    assert scoped == {str(96 * 2 ** 20)}, scoped
    after = [default_registry().peek_gauge(f"mhc/{form}_sites")
             for form in ("kernel", "xla")]
    assert after[0] > before[0] and after[1] == before[1]
    hlo = compiled.as_text()
    want = {"mixer": {("forward", "mhc_coeff"): 1,
                      ("backward", "mhc_coeff"): 1},
            "write": {("forward", "mhc_write"): 1,
                      ("backward", "mhc_write"): 1},
            "ends": {("forward", "mhc_write"): 1, ("forward", "mhc_read"): 1,
                     ("backward", "mhc_write"): 1,
                     ("backward", "mhc_read"): 1}}
    want["branch"] = {**want["mixer"], **want["write"]}
    assert mhc_calls(hlo) == want[entry]
    # the stream, its cotangents and a branch's few [4096, 3584] arrays
    assert compiled.memory_analysis().peak_memory_in_bytes < 1.2e9


def _xing4_block(remat=True):
    """(one dense ``DeepseekV3Block`` of the Xing4.0 cell's configuration
    under ``remat_block`` as the model runs it, its config)."""
    import dataclasses
    import flax.linen as nn
    from benchmark import manifest
    from deepspeed_tpu.models import deepseek_v3 as v3
    from deepspeed_tpu.models.laguna import remat_block
    bench = manifest.load()
    config = manifest.config_of(
        bench, manifest.cell_of(bench, "xing4-train-1chip-s4096"))
    cfg = dataclasses.replace(
        manifest.family_module(config).model_config(config, False),
        remat=remat)

    class OneBlock(nn.Module):
        @nn.compact
        def __call__(self, x):
            rope = v3.rope_tables(cfg, jnp.arange(x.shape[1]))
            return remat_block(cfg, self, "layer_0", v3.DeepseekV3Block)(
                cfg, False, name="layer_0")(x, rope)

    return OneBlock(), cfg


def test_xing4_block_compiles_with_the_stream_kernels_in_its_scopes():
    """ONE block of the ``xing4-train-1chip-s4096`` cell (the leading dense
    layer: four streams of 3,584, latent attention, a SwiGLU) at the cell's
    batch under ``remat_block``, forward and gradient: ``mhc_coeff`` and
    ``mhc_write`` are path elements of Pallas calls in the forward, the
    recomputed and the backward instructions — what ``mhc_stream_ms`` sums
    (``mhc_read`` holds a trunk's ``merge``, not a branch's pass: ``u`` comes
    from ``mix``) — and the recomputation runs ``mix`` twice and
    ``write`` once (the second branch's result is the block's); no float32
    copy of the stream is formed anywhere."""
    block, cfg = _xing4_block()
    assert (cfg.hc_mult, cfg.hidden_size, cfg.remat) == (4, 3584, True)
    x = SDS((1, 4096, 4 * 3584), BF16)
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0),
                            SDS((1, 128, 4 * 3584), BF16))["params"]

    def total(params, x):
        # a scalar of 128 columns: the test forms no float32 stream either
        out = block.apply({"params": params}, x, mutable=["stats"])[0]
        return out[..., :128].astype(F32).sum()

    # the value too: a gradient alone leaves nothing of the forward pass
    text, compiled = compile_on_chip(
        jax.value_and_grad(total, argnums=(0, 1)), params, x)
    assert kernel_names(text) >= MHC_KERNELS["branch"]
    hlo = compiled.as_text()
    calls = mhc_calls(hlo)
    assert {k: v for k, v in calls.items() if k[1].startswith("mhc")} == {
        ("forward", "mhc_coeff"): 2, ("forward", "mhc_write"): 2,
        ("recompute", "mhc_coeff"): 2, ("recompute", "mhc_write"): 1,
        ("backward", "mhc_write"): 2, ("backward", "mhc_coeff"): 2}
    assert not re.search(r"f32\[(1,)?4096,14336\]", hlo)


@pytest.mark.slow
def test_xing4_step_compiles_for_one_chip_with_its_scopes_and_fits():
    """The WHOLE step of the benchmark's ``xing4-train-1chip-s4096`` cell
    (Xing4.0's layers 0 and 2-5 + the prediction module as one of 8
    expert-parallel ranks, 1 x 4,096 tokens, ZeRO-3, through the family's
    ``lower_train_step``) is accepted for a 16 GB chip with the residual
    streams' six kernels in it: ``mhc_coeff`` / ``mhc_read`` /
    ``mhc_write`` are path elements of Pallas calls in forward, recomputed
    and backward instructions (twelve branches: six blocks of two), each
    kernel defined once or twice in the module for all of them, every
    scope the benchmark reads is in an ``op_name``, and the program stays
    under the chip's 16.911 GB. ~2.5 minutes: slow-marked (the kernels alone
    and one block are the tier-1 cases above)."""
    from benchmark import manifest
    bench = manifest.load()
    cell = manifest.cell_of(bench, "xing4-train-1chip-s4096")
    config = manifest.config_of(bench, cell)
    lowered = manifest.family_module(config).lower_train_step(
        config, manifest.traffic_of(cell), topo().devices[:1])
    text = lowered.as_text()
    assert kernel_names(text) == MHC_KERNELS["branch"] | MHC_KERNELS[
        "ends"] | {"_fwd_kernel_chunked", "_bwd_kernel_chunked", "kernel",
                   "_rows_to_tokens_kernel"}
    # the 74 sites below call ONE function a backward pass and two a forward
    # pass (the forward's and the recomputation's)
    defined = collections.Counter(re.findall(r'kernel_name = "([^"]+)"', text))
    assert {k: v for k, v in defined.items() if k.startswith("_mhc")} == {
        "_mhc_mix_kernel": 2, "_mhc_write_kernel": 2, "_mhc_tile_kernel": 2,
        "_mhc_sum_kernel": 2, "_mhc_mix_bwd_kernel": 1,
        "_mhc_write_bwd_kernel": 1}
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert 0.25 * HBM_BYTES < ma.peak_memory_in_bytes < 15.75 * 2 ** 30
    assert ma.peak_memory_in_bytes < 14.3e9       # 14.255 before the kernels
    hlo = compiled.as_text()
    assert not re.search(r"all-gather|all-reduce|reduce-scatter", hlo)
    calls = mhc_calls(hlo)
    # a trunk's two ends beside them (the trunk's and the prediction
    # module's: ``spread`` under ``mhc_write``, ``merge`` under ``mhc_read``,
    # each the other's backward)
    assert {k: v for k, v in calls.items() if k[1].startswith("mhc")} == {
        ("forward", "mhc_coeff"): 12, ("forward", "mhc_write"): 12 + 2,
        ("forward", "mhc_read"): 2,
        ("recompute", "mhc_coeff"): 12, ("recompute", "mhc_write"): 6,
        ("backward", "mhc_write"): 12 + 2, ("backward", "mhc_read"): 2,
        ("backward", "mhc_coeff"): 12}
    for scope in ("mhc_coeff", "mhc_read", "mhc_write", "mtp", "mla_attn",
                  "mla_latent", "mla_expand", "mla_rope", "flash_fwd_chunk",
                  "flash_bwd_chunk", "dense_mlp", "moe_shared", "moe_gmm",
                  "moe_router", "moe_dispatch", "moe_combine", "ds_loss_head",
                  "ds_embed", "ds_optimizer"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope


# ------------------------------------- optional kernels: known refusals

TILING_RULE = ("The Pallas TPU lowering currently requires that the last two "
               "dimensions of your block shape are divisible by 8 and 128 "
               "respectively, or be equal to the respective dimensions of "
               "the overall array.")


@contextlib.contextmanager
def refusing_with(*quotes):
    """The strict xfails below expect THIS refusal: the compiler's ValueError
    passes through only while it still says every quote. Another message is
    a plain failure (AssertionError is not what the xfail admits)."""
    try:
        yield
    except ValueError as e:
        missing = [q for q in quotes if q not in str(e)]
        assert not missing, f"refused for another reason: {e}"
        raise


@pytest.mark.xfail(
    strict=True, raises=ValueError,
    reason="ops/pallas/quantize.quantize(x[1280,1280], groups=1280) does not "
           "build for a TPU: " + TILING_RULE + " [block (1, 1280) of "
           "_quant_kernel]")
def test_grouped_quantize_kernel_compiles():
    from deepspeed_tpu.ops.pallas.quantize import quantize
    with refusing_with(TILING_RULE, "_quant_kernel",
                       "Blocked(block_size=1), Blocked(block_size=1280)"):
        compile_on_chip(lambda x: quantize(x, groups=1280, interpret=False),
                        SDS((1280, 1280), BF16))


@pytest.mark.parametrize("L,heads,kv_heads,block_length,grid", [
    (8192, 32, 4, 4, 64),       # sdar-train-1chip-s8192's layer
    (1280, 6, 2, 32, 35),       # an odd one: tiles of 256, 5 a half
], ids=["sdar", "L1280-b32"])
def test_block_diffusion_attention_compiles_at_the_sdar_cells_shape(
        L, heads, kv_heads, block_length, grid):
    """1 x 32 / 4 heads x 2 x 8,192 rows x head_dim 128, bf16, block length 4
    (``sdar-train-1chip-s8192``), forward and the single-pass backward under
    the blocks' remat policy: the two mask kernels under their scopes on grid
    (heads, 64) — the (query block, key chunk) pairs that hold an allowed
    score at tiles of 512 and chunks of 4,096, of the 32 x 4 a dense walk
    would take — K and V read at their own heads, the forward kernel once,
    the log-sum-exp lane-dense, and no [2L, 2L] array of any dtype."""
    from deepspeed_tpu.ops.pallas.block_diffusion_attention import \
        block_diffusion_attention

    def attend(*a):
        with jax.named_scope("attn"):
            return block_diffusion_attention(
                *a, block_length=block_length).astype(F32).sum()

    def grads(q, k, v):
        return jax.grad(rematted(attend), argnums=(0, 1, 2))(q, k, v)

    shapes = (SDS((1, heads, 2 * L, 128), BF16),
              SDS((1, kv_heads, 2 * L, 128), BF16),
              SDS((1, kv_heads, 2 * L, 128), BF16))
    text, compiled = compile_on_chip(grads, *shapes)
    assert kernel_names(text) == {"_bd_fwd_kernel", "_bd_bwd_kernel"}
    assert pallas_grids(grads, *shapes) == [(heads, grid)] * 2
    hlo = compiled.as_text()
    calls = flash_calls(hlo)
    assert all(f"bf16[{kv_heads},{2 * L},128]" in c for c in calls)
    for scope in ("bd_fwd", "bd_bwd", "bd_bwd_dq_sum"):
        assert re.search(r'op_name="[^"]*/' + scope + "/", hlo), scope
    assert f"{2 * L},{2 * L}" not in hlo
    assert_dense_lse_kept(hlo, calls, f"f32[{heads},{2 * L // 128},1,128]")


@pytest.mark.parametrize("kept", [True, False],
                         ids=["kl-grad-kept", "kl-grad-recomputed"])
def test_learned_sparse_attention_compiles_at_the_keye_cells_shape(kept):
    """1 x 32 / 4 heads x 16,384 rows x head_dim 128, an indexer of 16 heads
    of 64 keeping 2,048 keys, bf16 (``keyevl2-train-1chip-s16384``), forward
    and backward under the blocks' remat policy with the selection's name:
    the six kernels under their scopes; the selection and the pruned forward
    kernel ONCE (the recomputed forward reads the kept bits and ``flash_o`` /
    ``flash_lse``). With the KL gradient's name kept (the cell's program: the
    bytes fit) the indexer's scores and the KL pass run once too, and the
    backward forms no [S, S] array but the mask; without it (a budget of
    zero) both run again in the recomputation. The mask [16384, 16384] int8
    is the one dense array of the step beside the forward's float32 scores —
    the KL's gradient in them is its 528 causal tiles — and none has a head
    axis; the kept bits are [2048, 16384] uint8."""
    from deepspeed_tpu.models.gpt2 import block_remat_policy
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.ops.pallas import learned_sparse_attention as lsa
    S, H, Hkv, J = 16384, 32, 4, 16

    def attend(*a):
        with jax.named_scope("attn"):
            o, kl, _, _ = attention.learned_sparse_attention(*a, 2048)
            return o.astype(F32).sum() + kl.mean()

    policy = jax.checkpoint_policies.save_from_both_policies(
        block_remat_policy(), jax.checkpoint_policies.save_only_these_names(
            lsa.SELECTION_NAME, *((lsa.KL_GRAD_NAME,) if kept else ())))

    def grads(*a):
        # the value too, as a step takes it: the forward pass's KL is read
        return jax.value_and_grad(
            jax.checkpoint(attend, prevent_cse=True, policy=policy),
            argnums=tuple(range(6)))(*a)

    shapes = (SDS((1, H, S, 128), BF16), SDS((1, Hkv, S, 128), BF16),
              SDS((1, Hkv, S, 128), BF16), SDS((1, J, S, 64), BF16),
              SDS((1, S, 64), BF16), SDS((1, S, J), F32))
    text, compiled = compile_on_chip(grads, *shapes)
    assert kernel_names(text) == {
        "_indexer_kernel", "_select_kernel", "_masked_fwd_kernel",
        "_kl_kernel", "_masked_bwd_kernel", "_indexer_bwd_kernel"}
    hlo = compiled.as_text()
    count = lambda scope: sum(  # noqa: E731
        1 for ln in hlo.splitlines() if "tpu_custom_call" in ln
        and re.search(r'op_name="[^"]*/' + scope + "/", ln))
    assert (count("dsa_select"), count("dsa_fwd"), count("dsa_bwd"),
            count("dsa_indexer_bwd")) == (1, 1, 1, 1)
    assert (count("dsa_indexer"), count("dsa_kl")) == \
        ((1, 1) if kept else (2, 2))
    # a layer's KL gradient is its causal tiles; the float32 scores are each
    # ``dsa_indexer`` call's output and nothing else of that shape is formed
    assert f"bf16[1,{lsa.tiles_walked(S, 512)},512,512]" in hlo
    assert len(re.findall(rf"= f32\[1,{S},{S}\]", hlo)) == (1 if kept else 2)
    assert f"bf16[1,{S},{S}]" not in hlo
    assert f"u8[{S // 8},{S}]" in hlo
    assert f"{H},{S},{S}" not in hlo and f"{S},{S},{H}" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 6 * 2 ** 30
