"""Nemotron-H — a hybrid decoder whose every layer is ONE pre-norm residual
branch, ``x += f(RMSNorm(x))``, and whose ``f`` is read a layer from the
pattern STRING ``hybrid_override_pattern``: ``M`` a Mamba-2 mixer, ``E`` an
expert layer, ``*`` attention (``-``, a plain feed-forward, is not written
here). The published NVIDIA-Nemotron-3-Nano-30B-A3B is 52 such layers, 23 /
23 / 6, the attention layers at 5, 12, 19, 26, 33 and 42.

- **Mamba-2** (``mamba``, ``Mamba2Mixer``): ``in_proj`` to ``[z | xBC | dt]``
  (``d_inner = mamba_num_heads x mamba_head_dim``; ``xBC`` is ``d_inner + 2
  n_groups ssm_state_size`` wide); ``xBC = silu(conv(xBC) + bias)``, a causal
  depthwise convolution of ``conv_kernel`` taps over all of it
  (``ssm_conv``: ``ops/mixer_elementwise.conv_act``, which reads xBC's
  columns where they lie in the projection's output and hands x, B and C
  back as the scan takes them); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` one
  scalar a head, float32 (``ssm_gates``); the state-space scan
  ``ops/ssd.ssd_scan`` over x [heads x head_dim] with B, C [groups x state]
  shared by a group's heads and the skip ``D x`` (``ssd_scan*``); ``y *
  silu(z)`` FIRST and then an RMSNorm over groups of ``d_inner / n_groups``
  channels (``ssm_norm``: ``ops/mixer_elementwise.gated_group_norm`` with
  the gate before the norm, z read out of the projection's output);
  ``out_proj``. No bias but the convolution's.
- **Experts** (``mixer`` of an ``E`` layer, ``moe/dropless.DroplessMoE``):
  a float32 router scored by each expert's own SIGMOID; the
  ``num_experts_per_tok`` experts are the largest of ``score +
  e_score_correction_bias``, their weights the scores at them WITHOUT the
  bias, renormalised (``norm_topk_prob``) and times
  ``routed_scaling_factor``; an expert is UNGATED, ``down(relu(up(x))^2)``
  of width ``moe_intermediate_size``, and the one shared expert the same
  form at ``moe_shared_expert_intermediate_size`` with no gate in front of
  it. No auxiliary loss: the bias is a buffer (``buffer_leaves``) a
  balancing rule outside the loss would move; nothing moves it here.
- **Attention** (``mixer`` of a ``*`` layer,
  ``models/laguna.LagunaAttention``): ``num_attention_heads`` query over
  ``num_key_value_heads`` KV heads at ``head_dim``, causal, no bias, no
  QK-norm, no gate and NO rotation of q and k — the Mamba layers carry
  position (``rope_theta`` / ``partial_rotary_factor`` are carried and
  unused).

Every layer stands alone (``layer_<i>``) under its own ZeRO-3 gather edge
and, where the config asks, its own remat (``models/laguna.remat_block``):
the pattern's segments between attention layers are of unequal length (6,
7, 7, 7, 7, 9, 9: ``NemotronHConfig.segments``), so no one scan body
carries them. A final ``norm_f`` and an untied head.
"""

import collections
import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.models.gpt2 import _embed_lookup, chunked_lm_loss, lm_loss
from deepspeed_tpu.models.laguna import (FULL, LagunaAttention,
                                         attention_inflight_row_bytes,
                                         qkv_row_bytes, remat_block,
                                         stack_remat_policy)
from deepspeed_tpu.models.llama import RMSNorm
from deepspeed_tpu.moe.dropless import (CHOICE_BIAS, HELD_STAT_GAUGES,
                                        STAT_GAUGES, DroplessMoE)
from deepspeed_tpu.moe.dropless import inflight_row_bytes as moe_inflight
from deepspeed_tpu.moe.dropless import remat_row_bytes as moe_row_bytes
from deepspeed_tpu.ops.mixer_elementwise import conv_act, gated_group_norm
from deepspeed_tpu.ops.pallas.scan_residuals import SCAN_NAME
from deepspeed_tpu.ops.pallas.ssd import kept_row_bytes as scan_kept_row_bytes
from deepspeed_tpu.ops.ssd import ssd_scan
from deepspeed_tpu.runtime.remat_budget import projection_inflight
from deepspeed_tpu.telemetry.spans import annotate

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    """Keys under the published config's names (``model_type: nemotron_h``);
    the defaults are NVIDIA-Nemotron-3-Nano-30B-A3B as published: 52 layers
    (23 Mamba-2, 23 expert, 6 attention), 128 experts top-6 with one shared
    expert; 31.6B parameters, 3.2B active a token."""
    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = \
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
    max_position_embeddings: int = 262144
    layer_norm_epsilon: float = 1e-5
    # Mamba-2
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    use_conv_bias: bool = True
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    rope_theta: float = 10000.0      # carried, unused: q and k are not rotated
    partial_rotary_factor: float = 1.0
    # experts
    n_routed_experts: int = 128
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    # std of the selection bias at initialisation (a checkpoint brings its
    # own; 0: the published initialisation, zeros)
    e_score_correction_bias_std: float = 0.0
    experts_held: int = 0            # 0: all; else one rank's share ...
    expert_share: int = 0            # ... experts [held * share, ... + held)
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: Optional[str] = None
    use_flash: Optional[bool] = None
    loss_chunk: int = 0

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        assert len(pattern) == self.num_hidden_layers, \
            f"the pattern has {len(pattern)} entries for " \
            f"{self.num_hidden_layers} layers"
        assert set(pattern) <= {MAMBA, EXPERTS, ATTENTION}, \
            f"a layer kind of {pattern!r} is not written here"
        assert self.n_shared_experts in (0, 1)

    # what ``LagunaAttention`` reads, under its names
    gating = False
    sliding_window = None

    @property
    def plan(self):
        """The kind of every layer, in order: the pattern's characters."""
        return tuple(self.hybrid_override_pattern)

    @property
    def segments(self):
        """Lengths of the pattern's runs, each up to and including an
        attention layer (the last run to the end): 6, 7, 7, 7, 7, 9, 9 for
        the published 52."""
        runs = self.hybrid_override_pattern.split(ATTENTION)
        out = [len(r) + 1 for r in runs[:-1]]
        return tuple(out + [len(runs[-1])] if runs[-1] else out)

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def num_params(self):
        """Parameters held here (``experts_held`` experts an ``E`` layer):
        the initialised tree's count."""
        H = self.hidden_size
        heads = self.mamba_num_heads
        mamba = H * (self.d_inner + self.conv_dim + heads) \
            + self.conv_dim * (self.conv_kernel + self.use_conv_bias) \
            + 3 * heads + self.d_inner + self.d_inner * H
        attention = 2 * H * self.num_attention_heads * self.head_dim \
            + 2 * H * self.num_key_value_heads * self.head_dim
        held = self.experts_held or self.n_routed_experts
        experts = H * self.n_routed_experts + self.n_routed_experts \
            + 2 * held * H * self.moe_intermediate_size \
            + self.n_shared_experts * 2 * H \
            * self.moe_shared_expert_intermediate_size
        each = {MAMBA: mamba, ATTENTION: attention, EXPERTS: experts}
        return 2 * self.vocab_size * H + H \
            + sum(each[kind] + H for kind in self.plan)


def _dense(cfg, n, name):
    return nn.Dense(n, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.normal(0.02), name=name)


def _dt_bias_init(cfg):
    """The inverse softplus of a step drawn log-uniformly from
    [time_step_min, time_step_max] and floored at time_step_floor."""
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)

    def init(key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, lo, hi))
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return init


def _a_log_init(key, shape, dtype):
    # A uniform in [1, 16]; A_log = log(A)
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


def _conv_init(cfg):
    """A depthwise Conv1d's default: uniform in +-1 / sqrt(taps)."""
    bound = cfg.conv_kernel ** -0.5

    def init(key, shape, dtype):
        return jax.random.uniform(key, shape, jnp.float32, -bound,
                                  bound).astype(dtype)
    return init


class Mamba2Mixer(nn.Module):
    """The Mamba-2 branch: two projections round ``ssd_scan``, and round
    the scan the two elementwise stages of ``ops/mixer_elementwise.py``
    (convolution + SiLU before it, gate + grouped RMS norm after it), each
    one pass over HBM where the kernels take the shapes. ``config`` is a
    ``NemotronHConfig`` or any config with the attributes read here
    (``models/granite_hybrid.GraniteHybridConfig`` is one); one that has an
    ``a_log_init`` draws ``A_log`` with it."""
    config: NemotronHConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, _ = x.shape
        H, P = cfg.mamba_num_heads, cfg.mamba_head_dim
        G, N = cfg.n_groups, cfg.ssm_state_size
        d_inner, f32 = cfg.d_inner, jnp.float32
        # ``mixer_in``: kept by a rematted block that has the bytes
        # (``runtime/remat_budget.py``), the projection is not run again
        zxbcdt = checkpoint_name(
            _dense(cfg, d_inner + cfg.conv_dim + H, "in_proj")(x), "mixer_in")
        dt = zxbcdt[..., d_inner + cfg.conv_dim:]
        taps = self.param("conv", _conv_init(cfg),
                          (cfg.conv_kernel, cfg.conv_dim), cfg.param_dtype)
        a_log = self.param("A_log", getattr(cfg, "a_log_init", _a_log_init),
                           (H,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init(cfg), (H,),
                             cfg.param_dtype)
        skip = self.param("D", nn.initializers.ones, (H,), cfg.param_dtype)
        with annotate("ssm_conv"):
            # x | B | C out of the projection's output by column offset (z
            # lies before them), each as the scan reads it
            xs, Bm, Cm = conv_act(
                zxbcdt, taps, self.param(
                    "conv_bias", _conv_init(cfg), (cfg.conv_dim,),
                    cfg.param_dtype) if cfg.use_conv_bias else None,
                offset=d_inner, runs=((d_inner, None), (G * N, None),
                                      (G * N, None)))
        with annotate("ssm_gates"):
            dt = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
            A = -jnp.exp(a_log.astype(f32))
        y = ssd_scan(xs.reshape(B, S, H, P), dt, A, Bm.reshape(B, S, G, N),
                     Cm.reshape(B, S, G, N), skip.astype(f32),
                     chunk=cfg.chunk_size)
        w = self.param("norm", nn.initializers.ones, (d_inner,),
                       cfg.param_dtype)
        with annotate("ssm_norm"):
            # the gate z (the projection's first columns, read where they
            # lie) BEFORE the norm; the norm over each group's channels
            y = gated_group_norm(
                y.reshape(B, S, d_inner), zxbcdt, w, group=d_inner // G,
                eps=cfg.layer_norm_epsilon, gate_first=True)
        return checkpoint_name(_dense(cfg, cfg.hidden_size, "out_proj")(y),
                               "attn_proj")


def mixer_in_row_bytes(cfg):
    """Bytes a row one ``Mamba2Mixer`` layer holds under the name
    ``mixer_in``: ``in_proj``'s output."""
    return jnp.dtype(cfg.dtype).itemsize * (
        cfg.d_inner + cfg.conv_dim + cfg.mamba_num_heads)


def mixer_row_bytes(cfg):
    """{checkpoint name: bytes a row} of one ``Mamba2Mixer`` layer: its
    input projection, and what the scan's forward rule writes."""
    return {"mixer_in": mixer_in_row_bytes(cfg),
            SCAN_NAME: scan_kept_row_bytes(
                cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.ssm_state_size,
                cfg.chunk_size, jnp.dtype(cfg.dtype).itemsize)}


def mixer_inflight_row_bytes(cfg):
    """Bytes a row one ``Mamba2Mixer`` layer's backward holds in flight:
    ``in_proj``'s output and its cotangent."""
    b = jnp.dtype(cfg.dtype).itemsize
    return projection_inflight(mixer_in_row_bytes(cfg) // b, b)


def remat_row_bytes(cfg):
    """{checkpoint name: bytes a row, summed over the layers that carry
    it}: what ``models/laguna.stack_remat_policy`` weighs against its
    budget."""
    b = jnp.dtype(cfg.dtype).itemsize
    # a layer is ONE branch: nothing in its backward pass reads the
    # branch's output (``attn_proj``), kept or not
    each = {MAMBA: mixer_row_bytes(cfg),
            ATTENTION: {"qkv": qkv_row_bytes(cfg, cfg.num_attention_heads)},
            EXPERTS: moe_row_bytes(
                cfg.n_routed_experts, cfg.n_shared_experts
                * cfg.moe_shared_expert_intermediate_size, gated=False,
                itemsize=b)}
    total = collections.Counter()
    for kind in cfg.plan:
        total.update(each[kind])
    return total


def remat_inflight_row_bytes(cfg, seq_len):
    """Bytes a row the widest layer holds between its recomputation and the
    end of its backward: what ``models/laguna.stack_remat_policy`` reserves
    beside the block inputs."""
    each = {MAMBA: mixer_inflight_row_bytes(cfg),
            ATTENTION: attention_inflight_row_bytes(
                cfg, cfg.num_attention_heads, seq_len),
            EXPERTS: moe_inflight(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.num_experts_per_tok, cfg.n_routed_experts,
                cfg.experts_held or cfg.n_routed_experts,
                cfg.n_shared_experts
                * cfg.moe_shared_expert_intermediate_size, gated=False,
                itemsize=jnp.dtype(cfg.dtype).itemsize)}
    return max(each[kind] for kind in cfg.plan)


class NemotronHBlock(nn.Module):
    config: NemotronHConfig
    kind: str                        # MAMBA | EXPERTS | ATTENTION

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        h = RMSNorm(eps=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="norm")(x)
        if self.kind == MAMBA:
            out = Mamba2Mixer(cfg, name="mamba")(h)
        elif self.kind == ATTENTION:
            out = LagunaAttention(cfg, FULL, cfg.num_attention_heads,
                                  name="mixer")(h, {FULL: None})
        else:
            out = DroplessMoE(
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size,
                norm_topk_prob=cfg.norm_topk_prob, balance_coeff=0.0,
                z_coeff=0.0, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                experts_held=cfg.experts_held, expert_share=cfg.expert_share,
                shared_d_ff=cfg.n_shared_experts
                * cfg.moe_shared_expert_intermediate_size,
                routed_scale=cfg.routed_scaling_factor, act="relu2",
                gated=False, shared_gate=False, score="sigmoid",
                choice_bias=True, choice_bias_init=nn.initializers.normal(
                    cfg.e_score_correction_bias_std)
                if cfg.e_score_correction_bias_std else nn.initializers.zeros,
                # ``remat_block``'s policy saves the router's choice
                pin_choice=cfg.remat, name="mixer")(h)
        if self.is_mutable_collection("intermediates"):
            # a caller's look at the branch and at the stream it is added to
            # (the benchmark's check against its reference); nothing in a
            # training step
            self.sow("intermediates", "x_in", x)
            self.sow("intermediates", "branch_out", out)
        return x + out


class NemotronHForCausalLM(nn.Module):
    """Decoder-only LM with an untied head; ``labels`` with ``loss_chunk``
    takes the fused chunked head + loss (``models/gpt2.chunked_lm_loss``)."""
    config: NemotronHConfig

    sown_collections = ("losses", "stats")
    # leaves the engine hands back from a step as they came: the routers'
    # selection bias (``moe/dropless.DroplessMoE``)
    buffer_leaves = (CHOICE_BIAS,)

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
        policy = stack_remat_policy(
            cfg, input_ids.size, len(cfg.plan), remat_row_bytes(cfg),
            remat_inflight_row_bytes(cfg, input_ids.shape[1]))
        for i, kind in enumerate(cfg.plan):
            x = remat_block(cfg, self, f"layer_{i}", NemotronHBlock, policy)(
                cfg, kind, name=f"layer_{i}")(x)
        x = RMSNorm(eps=cfg.layer_norm_epsilon, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="norm_f")(x)
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


def nemotron_h_tiny(**over):
    """Nine layers ``MEMEM*EME`` at tiny widths: 4 Mamba heads of 8 in 2
    groups with a state of 16, 4 / 2 attention heads, 8 experts top-2 of a
    width no power of two."""
    pattern = over.get("hybrid_override_pattern", "MEMEM*EME")
    kw = dict(vocab_size=256, hidden_size=64, num_hidden_layers=len(pattern),
              hybrid_override_pattern=pattern, max_position_embeddings=256,
              mamba_num_heads=4, mamba_head_dim=8, n_groups=2,
              ssm_state_size=16, chunk_size=16, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, n_routed_experts=8,
              num_experts_per_tok=2, moe_intermediate_size=24,
              moe_shared_expert_intermediate_size=48,
              e_score_correction_bias_std=0.1, dtype=jnp.float32,
              param_dtype=jnp.float32)
    kw.update(over)
    return NemotronHConfig(**kw)
