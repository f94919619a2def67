"""GPT-2 family — the flagship model, TPU-first.

The reference trains GPT-2 through the external Megatron-LM client
(tests/model/Megatron_GPT2, SURVEY §4); here the model is in-tree flax with:

- bf16 activations, fp32 params (master-weight policy handled by the engine)
- optional `scan` over layers (one compiled block body — fast compiles for
  48-layer 1.5B configs, and the natural layout for pipeline stages)
- optional remat (activation checkpointing, reference
  activation_checkpointing/checkpointing.py analog via jax.checkpoint)
- flash attention via Pallas on TPU
- logical parameter axes for GSPMD: TP over heads/mlp/vocab, ZeRO-3 over the
  remaining large axis (see deepspeed_tpu/runtime/zero/partition.py)
- progressive layer drop keep-prob input (reference
  runtime/progressive_layer_drop.py:5 passes theta into fwd kwargs)
"""

import dataclasses
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn

from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.ops.attention import (from_head_major,
                                         fused_qkv_attention, to_head_major)
from deepspeed_tpu.telemetry.spans import annotate


import functools as _functools


def _gspmd_mesh():
    """Mesh for the model's GSPMD layout pins (wpe slice, wte scatter),
    or None when pins must not apply. The mesh comes from the ENGINE's
    trace-scoped mesh_lib.layout_pins(...) — never the ambient registry:
    set_current_mesh outlives its engine, and a later trace (another
    engine, the pipeline executor, a bare-model test) constraining to a
    stale foreign-device mesh crashes GSPMD (the r4 full-suite abort).
    Pins are also off inside explicit-comm (shard_map) programs, where
    data is already device-local and a NamedSharding over the global
    (Auto-axis) mesh poisons downstream avals — the engine flags those
    via no_layout_pins() because trace-context sniffing is unreliable
    (custom_vjp backwards re-trace under whatever mesh context is live
    at transpose time); the Manual axis check additionally catches
    direct shard_map use of the model."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.pinned_mesh()
    if mesh is None:
        return None
    if mesh_lib.in_manual_region():
        return None
    return mesh


@_functools.lru_cache(maxsize=None)
def _embed_lookup_fn(shape, dtype_name):
    """Token-embedding gather whose backward pins the scatter-add to the
    vocab-parallel (TP-only) layout. Without the pin, shardy propagates the
    ZeRO opt-state sharding (data axis on the vocab dim) onto the scatter
    output while the updates stay batch-sharded — GSPMD then cannot
    partition the scatter and falls back to involuntary full
    rematerialization (a whole-cotangent broadcast every step). Pinned to
    the TP spec, the scatter partitions as masked local updates + a data
    psum, and the cheap TP→opt reshard happens on the finished gradient."""
    @jax.custom_vjp
    def f(wte, ids):
        return wte[ids]

    def fwd(wte, ids):
        return wte[ids], ids

    def bwd(ids, g):
        d = jnp.zeros(shape, g.dtype).at[ids].add(g)
        from deepspeed_tpu.parallel import mesh as mesh_lib
        from jax.sharding import NamedSharding, PartitionSpec
        # the engine's layout_pins context is a PYTHON-call-scoped flag,
        # so it is still live however/whenever jax re-traces this
        # backward (custom_vjp backwards re-trace under arbitrary mesh
        # contexts at transpose time — context sniffing here misfires)
        mesh = mesh_lib.pinned_mesh()
        if mesh is not None:
            spec = PartitionSpec(mesh_lib.MODEL_AXIS, None) \
                if mesh.shape.get(mesh_lib.MODEL_AXIS, 1) > 1 \
                else PartitionSpec()
            d = jax.lax.with_sharding_constraint(
                d, NamedSharding(mesh, spec))
        return d.astype(dtype_name), None

    f.defvjp(fwd, bwd)
    return f


def _embed_lookup(wte, ids):
    return _embed_lookup_fn(tuple(wte.shape),
                            jnp.dtype(wte.dtype).name)(wte, ids)


def _expert_mesh_batch_pin(t):
    """Batch-layout constraint applied only under a live EXPERT mesh
    axis. Tiling the batch dim over the ('data','expert') axis pair
    yields a device order XLA's partitioner cannot convert to/from the
    model-axis tilings it picks inside the layer scan — the conversion
    degenerates to involuntary full rematerialization (a whole-tensor
    broadcast per step; the dryrun detector's dp×ep×tp tripper, clean
    on dp×sp×tp and dp×tp meshes). Anchoring the tensor to the batch
    layout keeps every reshard on a convertible path. No-op outside an
    engine-pinned GSPMD trace or when no expert axis is live."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    mesh = _gspmd_mesh()
    if mesh is None or mesh.shape.get(mesh_lib.EXPERT_AXIS, 1) <= 1:
        return t
    return jax.lax.with_sharding_constraint(
        t, mesh_lib.batch_sharding(mesh))


@_functools.lru_cache(maxsize=None)
def _carry_pin_fn():
    """Identity whose primal AND cotangent pin to the batch layout on
    expert meshes (the layer-scan carry spec enrichment): the backward
    scan otherwise carries the residual-stream cotangent model-major
    and remats flipping it back to batch-major."""
    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return _expert_mesh_batch_pin(x), None

    def bwd(_, g):
        # the engine's layout_pins context is Python-call-scoped, so it
        # is live however/whenever jax re-traces this backward
        return (_expert_mesh_batch_pin(g),)

    f.defvjp(fwd, bwd)
    return f


def _carry_pin(x):
    # trace-time gate: the engine's layout_pins context is live for the
    # whole trace, so whether an expert axis exists is a stable Python
    # fact — skip inserting the custom_vjp entirely on non-expert
    # meshes (the overwhelmingly common case; keeps those traces and
    # compiles free of dead identity nodes)
    from deepspeed_tpu.parallel import mesh as mesh_lib
    mesh = _gspmd_mesh()
    if mesh is None or mesh.shape.get(mesh_lib.EXPERT_AXIS, 1) <= 1:
        return x
    return _carry_pin_fn()(x)


def gather_edge_block(block_cls, parent, name):
    """``block_cls`` with the ZeRO-3 gather edge on its parameters
    (runtime/zero/partition.py): under a pinned GSPMD trace of a stage-3
    engine with a data axis, the leaves of the block ``name`` of module
    ``parent`` that rest data-sharded are pinned data-replicated where
    the block reads them, so GSPMD all-gathers the weight instead of
    re-laying the activations round a sharded one. Wrap BEFORE
    ``nn.remat``: the pin must sit inside the rematted function, or the
    gathered weights become the layer scan's saved residuals. Anywhere
    else (no engine, stages 0-2, one device, an explicit-comm program,
    ``init``) this is ``block_cls`` itself and nothing is emitted."""
    from deepspeed_tpu.parallel import mesh as mesh_lib
    edge = mesh_lib.pinned_gather_edge()
    if edge is None or parent.is_initializing():
        return block_cls
    path = parent.scope.path + (name,)
    return nn.map_variables(
        block_cls, "params",
        trans_in_fn=lambda vs: {**vs, "params": edge(path, vs["params"])})


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    n_positions: int = 1024
    n_embd: int = 768
    n_layer: int = 12
    n_head: int = 12
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    dtype: Any = jnp.bfloat16          # activation/compute dtype (MXU-friendly)
    param_dtype: Any = jnp.float32     # master params
    remat: bool = False
    remat_policy: Optional[str] = None  # None=full remat | "dots" | "offload"
    sp_backend: str = "ring"            # "ring" | "ulysses" (seq-axis attn)
    moe_experts: int = 0                # >0 → MoE FFN (expert parallel)
    moe_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_aux_coeff: float = 0.01         # load-balance loss weight
    scan_layers: bool = True
    # unroll factor for the layer scan: >1 lets XLA fuse/schedule across
    # adjacent layers and amortizes per-iteration fixed costs at the price
    # of code size / compile time. Must divide n_layer.
    scan_unroll: int = 1
    use_flash: Optional[bool] = None   # None = auto (TPU yes)
    tie_word_embeddings: bool = True
    # fused head+loss: when __call__ gets `labels`, compute the LM cross
    # entropy in chunks of this many tokens instead of materializing the
    # [B, S, V] logits (f32 lse temporaries are >1 GB at V=50k) — the
    # memory knob that lets dots-policy remat fit a 16 GB chip. 0 = off.
    loss_chunk: int = 0

    @property
    def head_dim(self):
        return self.n_embd // self.n_head

    def num_params(self):
        V, P, E, L = self.vocab_size, self.n_positions, self.n_embd, self.n_layer
        per_layer = 12 * E * E + 13 * E
        return V * E + P * E + L * per_layer + 2 * E


class SelfAttention(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        B, S, E = x.shape
        qkv = nn.Dense(3 * E, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype,
                       kernel_init=nn.initializers.normal(0.02),
                       name="c_attn")(x)
        qkv = checkpoint_name(qkv, "qkv")
        # sequence parallelism: when the active mesh has a seq axis, run
        # ring or Ulysses attention over it instead of letting GSPMD gather
        # full K/V
        from deepspeed_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.current_mesh()
        if mesh is not None and mesh.shape.get(mesh_lib.SEQ_AXIS, 1) > 1 \
                and S % mesh.shape[mesh_lib.SEQ_AXIS] == 0:
            sp = mesh.shape[mesh_lib.SEQ_AXIS]
            if cfg.sp_backend == "ulysses" and cfg.n_head % sp != 0:
                # Ulysses scatters heads over the seq axis, so it also needs
                # n_head % sp == 0; fall back to ring attention (which has no
                # head constraint) rather than tripping a trace-time assert
                # inside the a2a — but say so, the user asked for ulysses.
                from deepspeed_tpu.utils.logging import logger
                logger.warning(
                    f"sp_backend='ulysses' needs n_head ({cfg.n_head}) "
                    f"divisible by the seq axis ({sp}); falling back to "
                    f"ring attention")
            q, k, v = (to_head_major(t, cfg.n_head)
                       for t in jnp.split(qkv, 3, axis=-1))
            if cfg.sp_backend == "ulysses" and cfg.n_head % sp == 0:
                from deepspeed_tpu.parallel.ulysses import ulysses_attention
                out = ulysses_attention(q, k, v, mesh, causal=True)
            else:
                from deepspeed_tpu.parallel.ring_attention import ring_attention
                out = ring_attention(q, k, v, mesh, causal=True)
            out = from_head_major(out)
        else:
            # the fused projection as it is: the flash kernels read q, k, v
            # out of it and write [B, S, E], no head-major copy in between
            out = fused_qkv_attention(qkv, cfg.n_head, causal=True,
                                      use_flash=cfg.use_flash)
        out = nn.Dense(E, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype,
                       kernel_init=nn.initializers.normal(
                           0.02 / np.sqrt(2 * cfg.n_layer)),
                       name="c_proj")(out)
        out = checkpoint_name(out, "attn_proj")
        if cfg.dropout > 0:
            out = nn.Dropout(cfg.dropout)(out, deterministic=deterministic)
        return out


class MLP(nn.Module):
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.config
        h = nn.Dense(4 * cfg.n_embd, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     kernel_init=nn.initializers.normal(0.02),
                     name="c_fc")(x)
        h = checkpoint_name(h, "mlp_fc")
        h = nn.gelu(h, approximate=True)
        h = nn.Dense(cfg.n_embd, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype,
                     kernel_init=nn.initializers.normal(
                         0.02 / np.sqrt(2 * cfg.n_layer)),
                     name="c_proj")(h)
        h = checkpoint_name(h, "mlp_proj")
        if cfg.dropout > 0:
            h = nn.Dropout(cfg.dropout)(h, deterministic=deterministic)
        return h


class Block(nn.Module):
    """Pre-LN transformer block (GPT-2 style). ``keep_prob`` implements
    progressive layer drop: output = x + keep * sublayer(x) with the engine
    feeding the PLD theta schedule."""
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic=True, keep_prob=1.0):
        cfg = self.config
        # keep dtype stable under a traced keep_prob (PLD schedule is fp32)
        keep = jnp.asarray(keep_prob, x.dtype)
        ln1 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name="ln_1")(x)
        x = x + keep * SelfAttention(cfg, name="attn")(ln1, deterministic)
        x = _carry_pin(x)
        ln2 = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name="ln_2")(x)
        if cfg.moe_experts:
            from deepspeed_tpu.moe import MoE
            ffn_out = MoE(num_experts=cfg.moe_experts,
                          d_ff=4 * cfg.n_embd, k=cfg.moe_k,
                          capacity_factor=cfg.moe_capacity_factor,
                          dropout=cfg.dropout,
                          out_init_std=0.02 / np.sqrt(2 * cfg.n_layer),
                          dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                          name="moe")(ln2, deterministic)
        else:
            ffn_out = MLP(cfg, name="mlp")(ln2, deterministic)
        x = x + keep * ffn_out
        return _carry_pin(x)


def _remat_policy(name):
    """Named remat policies (the memory/compute knobs of the reference's
    activation_checkpointing config, SURVEY §5.7): full remat (None), keep
    matmul outputs on-chip ("dots"), or offload saved residuals to host
    memory ("offload" — the cpu_checkpointing analog)."""
    if name is None:
        return None
    if name == "dots":
        return jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    if name == "dots_lite":
        # save qkv (3E) + both residual-branch projections (2E) per layer
        # but NOT the 4E mlp fc output — 5E/9E of the "dots" footprint for
        # one extra fc matmul (1/3 of forward flops) recomputed in backward.
        return jax.checkpoint_policies.save_only_these_names(
            "qkv", "attn_proj", "mlp_proj")
    if name == "dots_flash":
        # dots_lite + the flash-attention kernel's own residuals (output +
        # logsumexp): backward runs the flash bwd kernels directly instead
        # of re-executing the forward kernel first. +1E per layer over
        # dots_lite; the best-measured fit for 16 GB at GPT-2-large/bs8
        # once optimizer moments are bf16.
        return jax.checkpoint_policies.save_only_these_names(
            "qkv", "attn_proj", "mlp_proj", "flash_o", "flash_lse")
    if name == "dots_flash_fc":
        # dots_flash but trading qkv (3E, 6-unit recompute) for mlp_fc
        # (4E, 8-unit recompute): less backward recompute per byte saved.
        # Needs grad_dtype=bf16's memory headroom at bs8/16 GB.
        return jax.checkpoint_policies.save_only_these_names(
            "attn_proj", "mlp_fc", "mlp_proj", "flash_o", "flash_lse")
    if name == "dots_plus":
        # everything "dots" keeps plus the flash residuals: no matmul or
        # attention recompute at all in backward. The roomiest policy;
        # needs bf16 grads to fit 16 GB at GPT-2-large/bs8.
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse"))
    if name == "dots_flash_fc_lean":
        # dots_flash_fc minus attn_proj: with flash_o saved, re-deriving
        # the attention projection is ONE matmul from a saved input
        # (~2/24 of forward flops) — 1E/layer of HBM back for near-zero
        # recompute. Matters when optimizer state crowds the 16 GB chip.
        return jax.checkpoint_policies.save_only_these_names(
            "mlp_fc", "mlp_proj", "flash_o", "flash_lse")
    if name == "projs":
        # save only the residual-branch projections (2E per layer): qkv and
        # fc recompute in backward (~58% of forward flops) but the big-batch
        # µbatch that feeds the MXU at full tilt fits in 16 GB — measured
        # faster end-to-end than any fuller policy at a smaller batch.
        return jax.checkpoint_policies.save_only_these_names(
            "attn_proj", "mlp_proj")
    if name == "offload":
        return jax.checkpoint_policies.offload_dot_with_no_batch_dims(
            "device", "pinned_host")
    raise ValueError(f"unknown remat_policy {name!r}")


# what a block under remat keeps whatever the memory: the router's choice
# (``moe/dropless.route``) and its attention kernel's output and log-sum-exp
REMAT_BASE_NAMES = ("moe_experts", "flash_o", "flash_lse")
# ... and what it keeps besides while the bytes fit, dearest a byte first
REMAT_CANDIDATES = ("moe_scores", "attn_proj", "mlp_proj", "qkv", "mixer_in",
                    "scan_states", "mlp_fc")


def block_remat_policy(name=None, **stack):
    """Policy of the blocks that are rematted one by one: a named policy
    ``name`` joined with ``REMAT_BASE_NAMES``; with none, these and the
    candidates that fit the trace's byte budget by ``stack``, the caller's
    figures (``runtime/remat_budget.keep_for_stack``; none: the base names)."""
    from deepspeed_tpu.runtime.remat_budget import keep_for_stack
    base = jax.checkpoint_policies.save_only_these_names(
        *REMAT_BASE_NAMES, *(keep_for_stack(REMAT_CANDIDATES, **stack)
                             if stack and name is None else ()))
    return base if name is None else \
        jax.checkpoint_policies.save_from_both_policies(
            _remat_policy(name), base)


def _maybe_remat(cfg, parent, name):
    """The Block class for the child ``name`` of ``parent``: gather edge
    innermost, remat (when configured) round it."""
    block = gather_edge_block(Block, parent, name)
    if not cfg.remat:
        return block
    return nn.remat(block, prevent_cse=False, static_argnums=(2,),
                    policy=_remat_policy(cfg.remat_policy))


class ScanBody(nn.Module):
    """One scanned layer step: returns (carry, None) as nn.scan requires."""
    config: GPT2Config

    @nn.compact
    def __call__(self, x, deterministic, keep_prob):
        block = _maybe_remat(self.config, self, "blk")
        return block(self.config, name="blk")(x, deterministic, keep_prob), None


class GPT2LMHeadModel(nn.Module):
    config: GPT2Config

    @property
    def layer_stacked_subtree(self):
        """Top-level params key whose leaves are layer-stacked
        (``[n_layer, ...]``), or None with unrolled layers: the ZeRO
        partitioner judges such leaves one layer at a time."""
        return "h" if self.config.scan_layers else None

    @property
    def sparse_grad_params(self):
        """Leaves eligible for the engine's row-sparse gradient exchange
        (sparse_gradients config). Only the UNTIED input embedding
        qualifies: a tied LM head adds a dense d(logits)/d(wte) term
        touching every vocabulary row, so compressing would drop real
        gradient."""
        return () if self.config.tie_word_embeddings else ("wte",)

    @nn.compact
    def __call__(self, input_ids, deterministic=True, keep_prob=1.0,
                 labels=None):
        cfg = self.config
        B, S = input_ids.shape
        wte = self.param("wte", nn.initializers.normal(0.02),
                         (cfg.vocab_size, cfg.n_embd), cfg.param_dtype)
        wpe = self.param("wpe", nn.initializers.normal(0.01),
                         (cfg.n_positions, cfg.n_embd), cfg.param_dtype)
        with annotate("ds_embed"):
            pos = wpe[:S]
        mesh = _gspmd_mesh()
        if mesh is not None:
            # pin the position slice replicated AT THE PARAM EDGE (fp32,
            # before the cast/broadcast): GSPMD otherwise propagates the
            # batch sharding onto the broadcast's size-1 leading dim and
            # then cannot reshard to the TP'd wpe gradient's layout without
            # an involuntary full rematerialization — a whole-tensor
            # broadcast inside every step on a real mesh
            from jax.sharding import NamedSharding, PartitionSpec
            pos = jax.lax.with_sharding_constraint(
                pos, NamedSharding(mesh, PartitionSpec()))
        posb = pos.astype(cfg.dtype)[None]
        from deepspeed_tpu.parallel import mesh as mesh_lib
        if mesh is not None and \
                mesh.shape.get(mesh_lib.EXPERT_AXIS, 1) > 1:
            # the broadcast's size-1 leading dim otherwise inherits the
            # batch sharding; on expert meshes that degenerate
            # ('data','expert')-pair tiling is unconvertible to the wpe
            # gradient's model-axis layout and remats (same family as
            # the fp32 pin above — this one anchors the POST-cast/
            # broadcast edge both directions; other meshes convert fine
            # and skip the extra node)
            from jax.sharding import NamedSharding, PartitionSpec
            posb = jax.lax.with_sharding_constraint(
                posb, NamedSharding(mesh, PartitionSpec()))
        with annotate("ds_embed"):
            x = _embed_lookup(wte, input_ids).astype(cfg.dtype) + posb
        x = _carry_pin(x)

        if cfg.scan_layers:
            scanned = nn.scan(ScanBody,
                              variable_axes={"params": 0, "losses": 0},
                              split_rngs={"params": True, "dropout": True},
                              in_axes=(nn.broadcast, nn.broadcast),
                              length=cfg.n_layer,
                              unroll=max(1, cfg.scan_unroll))
            x, _ = scanned(cfg, name="h")(x, deterministic, keep_prob)
        else:
            for i in range(cfg.n_layer):
                block = _maybe_remat(cfg, self, f"h_{i}")
                x = block(cfg, name=f"h_{i}")(x, deterministic, keep_prob)

        x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_f")(x)
        if labels is not None and cfg.loss_chunk > 0 \
                and cfg.tie_word_embeddings:
            return chunked_lm_loss(x, wte.astype(cfg.dtype), labels,
                                   cfg.loss_chunk)
        if cfg.tie_word_embeddings:
            with annotate("ds_loss_head"):
                logits = jnp.einsum("bse,ve->bsv", x, wte.astype(cfg.dtype))
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype, name="lm_head")(x)
        if labels is not None:
            return lm_loss(logits, labels)
        return logits


@annotate("ds_loss_head")
def chunked_lm_loss(hidden, wte, labels, chunk, ignore_index=-100,
                    offset=1, weights=None, normalizer=None):
    """Fused LM head + cross entropy without a [B, S, V] buffer: position i
    is scored against token ``i + offset`` (1: next-token; 2: a depth-1
    multi-token-prediction module's, ``models/deepseek_v3.py``; 0: the
    token at the position itself, a denoising objective's).

    ``weights`` (float32 [B, S], with ``normalizer``, a count fixed by the
    caller): the loss is ``sum_i weights_i nll_i / normalizer`` — a
    block-diffusion step's 1 / t over its masked rows, ``models/llama.py`` —
    through a second ``custom_vjp`` of the same shape (``_weighted_scan``);
    None is the program of before, op for op.

    Scans over chunks of ``chunk`` tokens; each chunk projects [C, E] @
    [E, V] and reduces to per-token nll immediately, so no [C, V] logits
    outlive their chunk. Differentiated, the scan forms its gradient where
    it forms its logits (``_chunk_scan_fwd``): cross entropy's gradient with
    respect to the logits is ``softmax - onehot``, known the moment the
    logits are, so the same chunk computes dlogits, ``dlogits @ wte`` and
    ``dlogitsᵀ @ h`` and hands them to the backward pass, which only scales
    them by the loss's cotangent. The head costs three matmuls a step (the
    mathematics' own count) and the backward pass derives no logits again.

    Matches ``lm_loss(logits, labels, offset=offset)`` to fp32 rounding: same
    shift, same ignore_index masking, same mean normalization.
    """
    B, S, E = hidden.shape
    xs = hidden[:, :S - offset, :].reshape(-1, E)
    tgt = labels[:, offset:].reshape(-1)
    n = xs.shape[0]
    pad = (-n) % chunk
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
        tgt = jnp.pad(tgt, (0, pad), constant_values=ignore_index)
    xs = xs.reshape(-1, chunk, E)
    tgt = tgt.reshape(-1, chunk)
    if weights is None:
        return _chunk_scan_loss(xs, wte, tgt, ignore_index)
    w = jnp.pad(weights[:, offset:].reshape(-1).astype(jnp.float32),
                (0, pad)).reshape(-1, chunk)
    return _weighted_scan(xs, wte, tgt, w, 1.0 / float(normalizer))


def _chunk_logits(h, t, wte, ignore_index):
    """One chunk's float32 logits [C, V], their log-sum-exp, the valid mask,
    the targets with the ignored ones at 0 and the chunk's nll sum."""
    logits = (h @ wte.T).astype(jnp.float32)
    valid = t != ignore_index
    t0 = jnp.where(valid, t, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    g = jnp.take_along_axis(logits, t0[:, None], axis=-1)[:, 0]
    return logits, lse, valid, t0, jnp.sum(jnp.where(valid, lse - g, 0.0))


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _chunk_scan_loss(xs, wte, tgt, ignore_index):
    """Mean nll over the valid targets of ``xs`` [chunks, C, E] against
    ``wte`` [V, E]; undifferentiated (evaluation, scoring) it is the plain
    scan: one head matmul, nothing kept."""
    def body(total, ht):
        return total + _chunk_logits(*ht, wte, ignore_index)[-1], None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, tgt))
    return total / jnp.maximum(jnp.sum(tgt != ignore_index), 1)


def _chunk_scan_fwd(xs, wte, tgt, ignore_index):
    """The loss, and as residuals its gradients for a cotangent of 1: ``dh``
    [chunks, C, E] and ``dW`` [V, E], in the operands' dtype.

    Every width is the transposed scan's of before: float32 logits and
    softmax, dlogits cast to the operands' dtype as ``astype``'s transpose
    casts them, both products in that dtype, ``dW`` carried through the scan
    in ``wte``'s dtype as a scan's cotangent carry is (on a TPU the add
    fuses into the matmul's float32 accumulator: one pass over [V, E] a
    chunk) and in the transposed scan's order of chunks. The mean's 1 / count goes into dlogits in float32 ahead of the
    cast — except under float16, whose smallest normal (6e-5) is above a
    softmax over a vocabulary divided by a batch's tokens and whose loss
    scale is known only to the backward pass: there the residuals stay
    unnormalised (dlogits in [-1, 1]) and the third residual, 1 elsewhere,
    is the 1 / count that ``_chunk_scan_bwd`` applies with the cotangent.
    """
    count = jnp.maximum(jnp.sum(tgt != ignore_index), 1)
    inv = 1.0 / count.astype(jnp.float32)
    late = jnp.finfo(xs.dtype).minexp > jnp.finfo(jnp.float32).minexp

    def body(carry, ht):
        total, dw = carry
        h, t = ht
        logits, lse, valid, t0, nll = _chunk_logits(h, t, wte, ignore_index)
        onehot = jax.nn.one_hot(t0, logits.shape[-1], dtype=jnp.float32)
        row = jnp.where(valid, 1.0 if late else inv, 0.0)[:, None]
        d = ((jnp.exp(logits - lse[:, None]) - onehot) * row).astype(h.dtype)
        return ((total + nll, dw + (d.T @ h).astype(dw.dtype)),
                (d @ wte).astype(h.dtype))

    # the barrier ties dW's zeros to the hidden states: without it the
    # compiler allots the [V, E] buffer ahead of the layers' forward pass
    xs, dw0 = jax.lax.optimization_barrier((xs, jnp.zeros_like(wte)))
    # last chunk first, the order the transposed scan walked: dW's carry
    # rounds once a chunk at the partial sum's size, and a causal model's
    # first tokens (one shared direction under attention) are its largest
    # term — added last they meet small partial sums, added first every
    # later chunk rounds at their size
    (total, dw), dh = jax.lax.scan(
        body, (jnp.float32(0.0), dw0), (xs, tgt), reverse=True)
    return total / count, (dh, dw, inv if late else jnp.float32(1.0))


def _chunk_scan_bwd(ignore_index, res, g):
    """``g`` (1, the loss scale, 1 / accumulation steps) times the
    residuals, a float32 product rounded once: no logits, no matmul."""
    dh, dw, left = res
    g = g * left
    return ((g * dh.astype(jnp.float32)).astype(dh.dtype),
            (g * dw.astype(jnp.float32)).astype(dw.dtype), None)


_chunk_scan_loss.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)


def _weighted_chunk(h, t, wt, wte):
    """One chunk's float32 logits [C, V], their log-sum-exp and its weighted
    nll sum."""
    logits = (h @ wte.T).astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    g = jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
    return logits, lse, jnp.sum(wt * (lse - g))


@_functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _weighted_scan(xs, wte, tgt, w, inv):
    """``inv`` x the sum of ``w`` [chunks, C] x the nll of ``xs`` [chunks, C,
    E] against ``wte`` [V, E] at the targets ``tgt`` (every one a token):
    ``_chunk_scan_loss`` with a weight a row where that has a valid mask, and
    a normaliser the caller fixes where that counts the valid rows."""
    def body(total, htw):
        return total + _weighted_chunk(*htw, wte)[-1], None

    total, _ = jax.lax.scan(body, jnp.float32(0.0), (xs, tgt, w))
    return total * inv


def _weighted_scan_fwd(xs, wte, tgt, w, inv):
    """``_chunk_scan_fwd`` with the row's factor ``w x inv`` where that has
    1 / count: the gradient in the chunk that forms the logits, dlogits cast
    once, ``dW`` carried in ``wte``'s dtype, last chunk first. (float16's
    late normalisation is not carried over: no float16 caller.)"""
    def body(carry, htw):
        total, dw = carry
        h, t, wt = htw
        logits, lse, nll = _weighted_chunk(h, t, wt, wte)
        onehot = jax.nn.one_hot(t, logits.shape[-1], dtype=jnp.float32)
        d = ((jnp.exp(logits - lse[:, None]) - onehot)
             * (wt * inv)[:, None]).astype(h.dtype)
        return ((total + nll, dw + (d.T @ h).astype(dw.dtype)),
                (d @ wte).astype(h.dtype))

    xs, dw0 = jax.lax.optimization_barrier((xs, jnp.zeros_like(wte)))
    (total, dw), dh = jax.lax.scan(
        body, (jnp.float32(0.0), dw0), (xs, tgt, w), reverse=True)
    return total * inv, (dh, dw)


def _weighted_scan_bwd(inv, res, g):
    dh, dw = res
    return ((g * dh.astype(jnp.float32)).astype(dh.dtype),
            (g * dw.astype(jnp.float32)).astype(dw.dtype), None, None)


_weighted_scan.defvjp(_weighted_scan_fwd, _weighted_scan_bwd)


@annotate("ds_loss_head")
def lm_loss(logits, labels, ignore_index=-100, offset=1):
    """Next-token cross entropy in fp32. ``labels`` must be the UNSHIFTED
    token ids (typically ``labels is input_ids``); the shift happens here
    (logits[:, :-1] vs labels[:, 1:]). Do not pre-shift. ``offset`` 2 scores
    position i against token i + 2 (``chunked_lm_loss``)."""
    logits = logits[:, :-offset].astype(jnp.float32)
    targets = labels[:, offset:]
    valid = targets != ignore_index
    targets = jnp.where(valid, targets, 0)
    # -log p(target) = logsumexp(logits) - logits[target]; this form never
    # materializes a [B, S, V] fp32 log-softmax in HBM (the lse and the
    # gathered target logit are both [B, S])
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jnp.where(valid, lse - tgt, 0.0)
    return nll.sum() / jnp.maximum(valid.sum(), 1)


# -- presets ---------------------------------------------------------------

def gpt2_tiny(**kw):
    base = dict(vocab_size=512, n_positions=128, n_embd=64, n_layer=2, n_head=2)
    base.update(kw)
    return GPT2Config(**base)


def gpt2_small(**kw):
    return GPT2Config(n_embd=768, n_layer=12, n_head=12, **kw)


def gpt2_medium(**kw):
    return GPT2Config(n_embd=1024, n_layer=24, n_head=16, **kw)


def gpt2_large(**kw):
    return GPT2Config(n_embd=1280, n_layer=36, n_head=20, **kw)


def gpt2_xl(**kw):
    """The 1.5B north-star config (SURVEY §6: 48L/1600h)."""
    return GPT2Config(n_embd=1600, n_layer=48, n_head=25, **kw)
