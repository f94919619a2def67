"""Qwen3-Next — a hybrid decoder: Gated DeltaNet layers with a gated
full-attention layer every ``full_attention_interval``-th, every FFN a
mixture of experts with a gated shared expert.

Layer ``i`` (0-based) is full attention where ``(i + 1) %
full_attention_interval == 0`` and Gated DeltaNet elsewhere
(``Qwen3NextConfig.layer_kinds``, derived from the two keys, typed in
nowhere); every layer is pre-norm residual, ``x += mixer(norm(x)); x +=
moe(norm(x))``, and every RMSNorm but the DeltaNet's output norm is
ZERO-CENTRED: ``x / rms(x) * (1 + w)``, ``w`` zero at initialisation.

- **Gated DeltaNet** (``linear_attn``): one projection to q, k, v, z and one
  to b, a; a causal depthwise convolution of ``linear_conv_kernel_dim`` taps
  and SiLU over q | k | v, with the L2 norm of every head of q and k (and
  q's ``Dk^-0.5``) in the same pass (``gdn_conv``:
  ``ops/mixer_elementwise.conv_act``, which reads the columns where they
  lie in the projection's output and hands q, k and v back as the scan
  takes them); ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)`` in float32 (``gdn_gates``); the chunked gated delta rule
  (``ops/gated_delta.py``, ``gdn_scan*``); an RMSNorm over each head's
  output times ``silu(z)`` (``gdn_out_norm``:
  ``ops/mixer_elementwise.gated_group_norm`` with the gate after the norm,
  z read out of the projection's output); the output projection. The fused projection's columns
  are q | k | v | z, each head-major (HF groups them per key head: q, k,
  its value heads' v, their z; a relabelling of columns).
- **Gated attention** (``attn``): ``q_proj`` gives query and gate per head;
  per-head zero-centred RMSNorm on query and key (``qk_norm``); RoPE, split
  halves, on the first ``partial_rotary_factor`` of each head; causal
  softmax attention at its own ``head_dim`` (a key of its own, not hidden /
  heads) through ``dot_product_attention``; the output times
  ``sigmoid(gate)`` (``attn_gate``); ``o_proj``.
- **MoE** (``mlp``): ``moe/dropless.DroplessMoE`` with the top-k
  renormalised, a gated shared expert, and — a configuration's to say —
  only ``experts_held`` of the ``num_experts`` held here (one rank's share of
  an expert-parallel layout).

The layer scan's body is one PERIOD of ``full_attention_interval`` unlike
layers (``l0`` .. ``l3``), each with its own ZeRO-3 gather edge innermost
and its own remat round it, as ``models/llama.py`` has for its one block;
the parameters of period p's j-th layer are slice p of the leaves under
``layers/l<j>``. Shares with the other models: ``_embed_lookup``,
``chunked_lm_loss``, ``gather_edge_block``, ``block_remat_policy``
(models/gpt2.py), ``rope_angles`` / ``apply_rope`` (models/llama.py).
The multi-token-prediction module of the published model is not here.
"""

import collections
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.models.gpt2 import (_embed_lookup, block_remat_policy,
                                       chunked_lm_loss, gather_edge_block,
                                       lm_loss)
from deepspeed_tpu.models.laguna import stack_remat_policy
from deepspeed_tpu.models.llama import apply_rope, rope_angles
from deepspeed_tpu.moe.dropless import (HELD_STAT_GAUGES, STAT_GAUGES,
                                        DroplessMoE)
from deepspeed_tpu.moe.dropless import inflight_row_bytes as moe_inflight
from deepspeed_tpu.moe.dropless import remat_row_bytes as moe_row_bytes
from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.ops.gated_delta import CHUNK, gated_delta_rule
from deepspeed_tpu.ops.pallas.flash_attention import bwd_dq_slab_rows
from deepspeed_tpu.ops.pallas.gated_delta import \
    kept_row_bytes as scan_kept_row_bytes
from deepspeed_tpu.ops.pallas.gated_delta import lane_heads
from deepspeed_tpu.ops.pallas.scan_residuals import SCAN_NAME
from deepspeed_tpu.runtime.remat_budget import (attention_inflight,
                                                projection_inflight)
from deepspeed_tpu.ops.mixer_elementwise import (conv_act, gated_group_norm,
                                                tile_group_norm)
from deepspeed_tpu.telemetry.spans import annotate


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    """Keys under the published config's names."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # gated attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    max_position_embeddings: int = 262144
    # Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_key_head_dim: int = 128
    linear_num_value_heads: int = 32
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = False   # beta = 2 sigmoid(b), in (0, 2)
    # experts
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    router_aux_loss_coef: float = 0.001
    experts_held: int = 0            # 0: all; else one rank's share ...
    expert_share: int = 0            # ... experts [held * share, ... + held)
    rms_norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: Optional[str] = None
    use_flash: Optional[bool] = None
    loss_chunk: int = 0

    @property
    def layer_kinds(self):
        """"attention" | "linear" for each layer, from the two keys."""
        return tuple("attention" if (i + 1) % self.full_attention_interval
                     == 0 else "linear" for i in range(self.num_hidden_layers))

    @property
    def n_periods(self):
        assert self.num_hidden_layers % self.full_attention_interval == 0, \
            "the layer scan runs whole periods"
        return self.num_hidden_layers // self.full_attention_interval

    def num_params(self):
        """Parameters held here (``experts_held`` experts a layer)."""
        H = self.hidden_size
        key = self.linear_num_key_heads * self.linear_key_head_dim
        val = self.linear_num_value_heads * self.linear_value_head_dim
        linear = H * (2 * key + 2 * val) + 2 * H * self.linear_num_value_heads \
            + self.linear_conv_kernel_dim * (2 * key + val) \
            + 2 * self.linear_num_value_heads + self.linear_value_head_dim \
            + val * H
        q = self.num_attention_heads * self.head_dim
        kv = self.num_key_value_heads * self.head_dim
        attention = H * (2 * q + 2 * kv) + 2 * self.head_dim + q * H
        held = self.experts_held or self.num_experts
        moe = H * self.num_experts + 3 * held * H * self.moe_intermediate_size \
            + 3 * H * self.shared_expert_intermediate_size + H + 2 * H
        n_attention = self.layer_kinds.count("attention")
        return 2 * self.vocab_size * H + H + self.num_hidden_layers * moe \
            + n_attention * attention \
            + (self.num_hidden_layers - n_attention) * linear


class ZeroCentredRMSNorm(nn.Module):
    """float32 ``x / sqrt(mean(x^2) + eps) * (1 + w)``, ``w`` zero at
    initialisation (so weight decay pulls the gain to one, not to zero)."""
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       self.param_dtype)
        xf = x.astype(jnp.float32)
        n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        return (n * (1.0 + w.astype(jnp.float32))).astype(self.dtype)


def _dense(cfg, n, name):
    return nn.Dense(n, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.normal(0.02), name=name)


def _a_log_init(key, shape, dtype):
    # HF: A = uniform(0, 16); A_log = log(A)
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.0,
                                      16.0)).astype(dtype)


def _to_lane_tiles(t, runs):
    """The last axis of ``t`` — runs of heads, ``runs`` = ((heads, width,
    tiles), ...) — with every head zero-padded from ``width`` to ``tiles``
    columns."""
    out, at = [], 0
    for heads, width, tiles in runs:
        part = t[..., at:at + heads * width].reshape(
            *t.shape[:-1], heads, width)
        at += heads * width
        out.append(jnp.pad(part, ((0, 0),) * (t.ndim - 1) + ((0, 0), (
            0, tiles - width))).reshape(*t.shape[:-1], heads * tiles))
    return jnp.concatenate(out, axis=-1)


class GatedDeltaNet(nn.Module):
    """The linear-attention branch: three projections round
    ``gated_delta_rule``, and round the rule the two elementwise stages of
    ``ops/mixer_elementwise.py`` (convolution + SiLU + q / k L2 norm before
    it, per-head RMS norm + gate after it), each one pass over HBM where
    the kernels take the shapes.

    ``config``: ``Qwen3NextConfig`` or another model's with the same
    ``linear_*`` keys (``models/olmo_hybrid.py``). ``linear_allow_neg_eigval``
    makes beta ``2 sigmoid(b)`` in (0, 2). Heads off the 128-lane grid that
    ``ops.pallas.gated_delta.lane_heads`` rounds up (96 x 192 -> 128 x 256)
    are laid out ONCE, zero-padded, between the projection and the
    convolution (under ``gdn_conv``), so that all three stages run their
    kernels on whole tiles, and cut back to their own width after the norm
    (under ``gdn_out_norm``); the parameters keep the published shapes."""
    config: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, _ = x.shape
        Hk, Dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
        Hv, Dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        Pk, Pv = lane_heads(Dk, Dv)
        tiles = (Pk, Pv) != (Dk, Dv)
        key, val = Hk * Pk, Hv * Pv
        # ``mixer_in``: kept by a rematted block that has the bytes
        # (``runtime/remat_budget.py``), the projections are not run again
        # (nor, of heads laid out in whole tiles, the re-layout)
        qkvz = _dense(cfg, 2 * Hk * Dk + 2 * Hv * Dv, "in_proj_qkvz")(x)
        if not tiles:
            qkvz = checkpoint_name(qkvz, "mixer_in")
        ba = checkpoint_name(_dense(cfg, 2 * Hv, "in_proj_ba")(x), "mixer_in")
        taps = self.param("conv", nn.initializers.normal(0.02),
                          (cfg.linear_conv_kernel_dim,
                           2 * Hk * Dk + Hv * Dv), cfg.param_dtype)
        a_log = self.param("A_log", _a_log_init, (Hv,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", nn.initializers.ones, (Hv,),
                             cfg.param_dtype)
        if tiles:
            with annotate("gdn_conv"):
                runs = ((Hk, Dk, Pk), (Hk, Dk, Pk), (Hv, Dv, Pv))
                qkvz = checkpoint_name(
                    _to_lane_tiles(qkvz, runs + ((Hv, Dv, Pv),)), "mixer_in")
                taps = _to_lane_tiles(taps, runs)
        with annotate("gdn_conv"):
            # q | k | v out of the projection's output by column offset,
            # each as the scan reads it: q and k leave L2-normalised a head
            q, k, v = conv_act(
                qkvz, taps, head_width=Pk,
                runs=((key, Dk ** -0.5), (key, 1.0), (val, None)))
        with annotate("gdn_gates"):
            b, a = (t.astype(jnp.float32) for t in jnp.split(ba, 2, axis=-1))
            beta = jax.nn.sigmoid(b)
            if getattr(cfg, "linear_allow_neg_eigval", False):
                beta = 2.0 * beta
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                a + dt_bias.astype(jnp.float32))
        o = gated_delta_rule(q.reshape(B, S, Hk, Pk), k.reshape(B, S, Hk, Pk),
                             v.reshape(B, S, Hv, Pv), g, beta,
                             heads=(Dk, Dv) if tiles else None)
        w = self.param("norm", nn.initializers.ones, (Dv,), cfg.param_dtype)
        with annotate("gdn_out_norm"):
            # a head's RMS norm, one weight for every head, then the gate z
            # (the projection's last columns, read where they lie)
            o = o.reshape(B, S, val)
            if tiles:
                o = tile_group_norm(o, qkvz, w, group=Pv, width=Dv,
                                    eps=cfg.rms_norm_eps, offset=2 * key + val)
                o = o.reshape(B, S, Hv, Pv)[..., :Dv].reshape(B, S, Hv * Dv)
            else:
                o = gated_group_norm(
                    o, qkvz, w, group=Dv, eps=cfg.rms_norm_eps,
                    gate_first=False, offset=2 * key + val)
        return checkpoint_name(_dense(cfg, cfg.hidden_size, "out_proj")(o),
                               "attn_proj")


class GatedAttention(nn.Module):
    config: Qwen3NextConfig

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        B, S, _ = x.shape
        H, Hkv, D = (cfg.num_attention_heads, cfg.num_key_value_heads,
                     cfg.head_dim)
        # ``qkv`` names what the backward pass reads: the projections as the
        # head norms read them (q and the gate one array), and below q and k
        # normed and rotated beside v, the kernel's operands
        qg = checkpoint_name(_dense(cfg, 2 * H * D, "q_proj")(x),
                             "qkv").reshape(B, S, H, 2, D)
        q, gate = qg[..., 0, :], qg[..., 1, :]
        k = checkpoint_name(_dense(cfg, Hkv * D, "k_proj")(x),
                            "qkv").reshape(B, S, Hkv, D)
        v = _dense(cfg, Hkv * D, "v_proj")(x).reshape(B, S, Hkv, D)
        with annotate("qk_norm"):
            norm = lambda name: ZeroCentredRMSNorm(  # noqa: E731
                eps=cfg.rms_norm_eps, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name)
            q = norm("q_norm")(q)
            k = norm("k_norm")(k)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B, H, S, D]
        rot = int(D * cfg.partial_rotary_factor)
        cos, sin = rope_angles(positions, rot, cfg.rope_theta)
        q, k = (jnp.concatenate([apply_rope(t[..., :rot], cos, sin),
                                 t[..., rot:]], axis=-1) for t in (q, k))
        q, k, v = (checkpoint_name(t, "qkv") for t in (q, k, v))
        out = dot_product_attention(q, k, v, causal=True,
                                    use_flash=cfg.use_flash)
        out = out.transpose(0, 2, 1, 3)                     # [B, S, H, D]
        with annotate("attn_gate"):
            out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                gate.astype(jnp.float32))).astype(cfg.dtype)
        out = _dense(cfg, cfg.hidden_size, "o_proj")(out.reshape(B, S, H * D))
        return checkpoint_name(out, "attn_proj")


class Qwen3NextBlock(nn.Module):
    config: Qwen3NextConfig
    kind: str                        # "attention" | "linear"

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        norm = lambda name: ZeroCentredRMSNorm(  # noqa: E731
            eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        h = norm("input_norm")(x)
        if self.kind == "attention":
            mixed = GatedAttention(cfg, name="attn")(h, positions)
        else:
            mixed = GatedDeltaNet(cfg, name="linear_attn")(h)
        x = x + mixed
        out = DroplessMoE(
            cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, norm_topk_prob=cfg.norm_topk_prob,
            balance_coeff=cfg.router_aux_loss_coef, z_coeff=0.0,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            experts_held=cfg.experts_held, expert_share=cfg.expert_share,
            shared_d_ff=cfg.shared_expert_intermediate_size,
            # ``_Period``'s remat policy saves the router's choice
            pin_choice=cfg.remat,
            name="mlp")(norm("post_attn_norm")(x))
        if self.is_mutable_collection("intermediates"):
            # a caller's look at the block's input after the mixer and at
            # the two branches (the benchmark's check against its
            # reference); nothing in a training step
            self.sow("intermediates", "x_mid", x)
            self.sow("intermediates", "mixer_out", mixed)
            self.sow("intermediates", "ffn_out", out)
        return x + out


def _mixer_in_cols(cfg):
    """Columns ``GatedDeltaNet``'s two input projections leave a row under
    ``mixer_in``: q | k | v | z as the kernels read them (heads off the
    lane grid zero-padded to whole tiles) and b | a."""
    Pk, Pv = lane_heads(cfg.linear_key_head_dim, cfg.linear_value_head_dim)
    return 2 * cfg.linear_num_key_heads * Pk \
        + 2 * cfg.linear_num_value_heads * (Pv + 1)


def gdn_row_bytes(cfg):
    """{checkpoint name: bytes a row} of ONE ``GatedDeltaNet`` layer."""
    b = jnp.dtype(cfg.dtype).itemsize
    Pk, Pv = lane_heads(cfg.linear_key_head_dim, cfg.linear_value_head_dim)
    return {"mixer_in": b * _mixer_in_cols(cfg),
            SCAN_NAME: scan_kept_row_bytes(cfg.linear_num_value_heads, Pk,
                                           Pv, CHUNK, b)}


def gdn_inflight_row_bytes(cfg):
    """Bytes a row a ``GatedDeltaNet`` branch holds between its
    recomputation and the end of its backward."""
    return projection_inflight(_mixer_in_cols(cfg),
                               jnp.dtype(cfg.dtype).itemsize)


def remat_row_bytes(cfg):
    """{checkpoint name: bytes a row, summed over the layers that carry
    it}: what ``models/laguna.stack_remat_policy`` weighs against its
    budget."""
    b = jnp.dtype(cfg.dtype).itemsize
    q = cfg.num_attention_heads * cfg.head_dim
    kv = cfg.num_key_value_heads * cfg.head_dim
    each = {"linear": gdn_row_bytes(cfg),
            # q with its gate and k as projected, q, k and v as the kernel
            # reads them
            "attention": {"qkv": b * (3 * q + 3 * kv)}}
    total = collections.Counter()
    for kind in cfg.layer_kinds:
        total.update(each[kind])
        total.update({"attn_proj": b * cfg.hidden_size})
        total.update(moe_row_bytes(
            cfg.num_experts, cfg.shared_expert_intermediate_size,
            itemsize=b))
    return total


def remat_inflight_row_bytes(cfg, seq_len):
    """Bytes a row the widest branch of the widest layer holds between its
    recomputation and the end of its backward: what
    ``models/laguna.stack_remat_policy`` reserves beside the block inputs.
    The gated attention's q is projected with its gate (an output gate)."""
    b = jnp.dtype(cfg.dtype).itemsize
    q = cfg.num_attention_heads * cfg.head_dim
    each = {"linear": gdn_inflight_row_bytes(cfg),
            "attention": attention_inflight(
                q, q, 2 * cfg.num_key_value_heads * cfg.head_dim, b,
                bwd_dq_slab_rows(seq_len, cfg.head_dim, cfg.head_dim, b),
                gated=True)}
    return max(
        moe_inflight(cfg.hidden_size, cfg.moe_intermediate_size,
                     cfg.num_experts_per_tok, cfg.num_experts,
                     cfg.experts_held or cfg.num_experts,
                     cfg.shared_expert_intermediate_size, itemsize=b),
        *(each[kind] for kind in cfg.layer_kinds))


class _Period(nn.Module):
    """The layer scan's body: ``full_attention_interval`` unlike layers,
    each under its own gather edge (innermost) and remat."""
    config: Qwen3NextConfig
    policy: Any = None               # the stack's ``stack_remat_policy``

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        for j, kind in enumerate(
                cfg.layer_kinds[:cfg.full_attention_interval]):
            block = gather_edge_block(Qwen3NextBlock, self, f"l{j}")
            if cfg.remat:
                # whatever the stack's policy keeps, it keeps the router's
                # choice and the attention kernel's outputs
                # (``models/gpt2.block_remat_policy``).
                # prevent_cse: several rematted blocks share one scan body,
                # and a scan of ONE period is no loop at all once XLA has
                # simplified it: without the barrier the recomputation is
                # merged back into the forward pass and everything is kept
                block = nn.remat(block, prevent_cse=True, policy=self.policy
                                 or block_remat_policy(cfg.remat_policy))
            x = block(cfg, kind, name=f"l{j}")(x, positions)
        return x, None


class Qwen3NextForCausalLM(nn.Module):
    """Decoder-only LM; ``labels`` with ``loss_chunk`` takes the fused
    chunked head + loss (``models/gpt2.chunked_lm_loss``)."""
    config: Qwen3NextConfig

    layer_stacked_subtree = "layers"
    # ``losses``: the routers' balance terms, already weighted; ``stats``:
    # scalars folded into the gauges ``stat_gauges`` names
    sown_collections = ("losses", "stats")

    @property
    def stat_gauges(self):
        """{variable sown into ``stats``: the gauge it is read under}."""
        return HELD_STAT_GAUGES if self.config.experts_held else STAT_GAUGES

    @nn.compact
    def __call__(self, input_ids, labels=None):
        cfg = self.config
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with annotate("ds_embed"):
            x = _embed_lookup(embed, input_ids).astype(cfg.dtype)
        positions = jnp.arange(input_ids.shape[1])
        scanned = nn.scan(
            _Period,
            variable_axes={"params": 0, "losses": 0, "stats": 0,
                           "intermediates": 0},
            split_rngs={"params": True}, in_axes=(nn.broadcast,),
            length=cfg.n_periods)
        policy = stack_remat_policy(
            cfg, input_ids.size, cfg.num_hidden_layers, remat_row_bytes(cfg),
            remat_inflight_row_bytes(cfg, input_ids.shape[1]))
        x, _ = scanned(cfg, policy, name="layers")(x, positions)
        x = ZeroCentredRMSNorm(eps=cfg.rms_norm_eps, dtype=cfg.dtype,
                               param_dtype=cfg.param_dtype, name="norm")(x)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.vocab_size, cfg.hidden_size),
                          cfg.param_dtype)
        if labels is not None and cfg.loss_chunk > 0:
            return chunked_lm_loss(x, head.astype(cfg.dtype), labels,
                                   cfg.loss_chunk)
        logits = jnp.einsum("bse,ve->bsv", x, head.astype(cfg.dtype))
        if labels is not None:
            return lm_loss(logits, labels)
        return logits


def qwen3_next_80b_a3b(**over):
    """Qwen3-Next-80B-A3B (Qwen/Qwen3-Next-80B-A3B-Instruct) as published:
    48 layers, 12 periods of 3 Gated DeltaNet + 1 gated attention layer,
    512 experts top-10 with a shared expert; 80B parameters, 3B active."""
    return Qwen3NextConfig(**over)


def qwen3_next_tiny(**over):
    kw = dict(vocab_size=256, hidden_size=64, num_hidden_layers=8,
              num_attention_heads=4, num_key_value_heads=2, head_dim=32,
              max_position_embeddings=128, linear_num_key_heads=2,
              linear_key_head_dim=16, linear_num_value_heads=4,
              linear_value_head_dim=16, num_experts=16,
              num_experts_per_tok=2, moe_intermediate_size=32,
              shared_expert_intermediate_size=32, dtype=jnp.float32,
              param_dtype=jnp.float32)
    kw.update(over)
    return Qwen3NextConfig(**kw)
