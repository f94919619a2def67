"""Granite 4.0-H (``model_type: granitemoehybrid``) — a hybrid decoder of
TWO pre-norm residual branches a layer, a mixer and a dense SwiGLU, with
muP-style multipliers on the embedding, both branches, the attention scores
and the logits, and a head TIED to the embedding:

    h0 = embedding_multiplier * E[ids]
    h <- h + residual_multiplier * Mixer_i(RMSNorm(h))
    h <- h + residual_multiplier * MLP(RMSNorm(h))
    logits = RMSNorm(h_L) E^T / logits_scaling

The mixer of layer i is read from the published list ``layer_types``:
``"mamba"`` or ``"attention"`` (ibm-granite/granite-4.0-h-micro: 40 layers,
attention at 5, 15, 25 and 35, Mamba-2 elsewhere).

- **Mamba-2** (``mamba``): ``models/nemotron_h.Mamba2Mixer`` as it stands,
  read under this family's key names — ``mamba_n_heads`` heads of
  ``mamba_d_head`` channels, a state of ``mamba_d_state``, a convolution of
  ``mamba_d_conv`` taps with bias and ``mamba_n_groups`` groups: ONE in
  every published Granite 4.0-H, so that all heads read one B and one C a
  token and the gated RMS norm (the gate FIRST) runs over all ``d_inner``
  channels at once (``ops/pallas/ssd.py`` cuts the group into head blocks,
  ``ops/pallas/mixer_elementwise.py`` takes a group as its own column
  block). ``dt`` is not clamped (no ``time_step_limit``).
- **Attention** (``attn``): ``models/laguna.LagunaAttention`` — grouped
  keys, no bias, NO rotation (``position_embedding_type: nope``), the
  scores multiplied by ``attention_multiplier`` (1 / 64 at a head of 64:
  not the habit's 1 / 8).
- **MLP** (``shared_mlp``, the published module's name; ``num_local_experts
  0``: no expert branch exists): ``(silu(g) * p) W_out`` with ``[g | p] = u
  W_in`` ONE matrix of ``hidden_size x 2 shared_intermediate_size``.

The multipliers fold into the operations beside them and have no scope.
Every layer stands alone (``layer_<i>``) under its own ZeRO-3 gather edge
and, where the config asks, its own remat (``models/laguna.remat_block``):
no layer scan (``models/nemotron_h.py`` and ``models/deepseek_v3.py`` say
what a scan's stacked leaves cost the benchmark's float32 reference; ten
blocks trace in seconds).
"""

import collections
import dataclasses
from typing import Any, Optional

import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.models.gpt2 import _embed_lookup, chunked_lm_loss, lm_loss
from deepspeed_tpu.models.laguna import (FULL, LagunaAttention, _dense,
                                         attention_inflight_row_bytes,
                                         qkv_row_bytes, remat_block,
                                         stack_remat_policy)
from deepspeed_tpu.models.llama import RMSNorm
from deepspeed_tpu.models.nemotron_h import (Mamba2Mixer,
                                             mixer_inflight_row_bytes,
                                             mixer_row_bytes)
from deepspeed_tpu.runtime.remat_budget import mlp_inflight
from deepspeed_tpu.telemetry.spans import annotate

MAMBA, ATTENTION = "mamba", "attention"


def _a_log_init(key, shape, dtype):
    """``A = 1 .. heads`` (the published implementation's initialisation):
    decays from ``exp(-dt)`` to ``exp(-heads dt)`` a token."""
    del key
    return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32)).astype(
        dtype)


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    """Keys under the published config's names; the defaults are
    granite-4.0-h-micro as published but for ``layer_types``, which is
    required and goes in as the config file has it: 3.19B parameters."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    shared_intermediate_size: int = 8192
    num_hidden_layers: int = 40
    layer_types: Any = dataclasses.field(kw_only=True)
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-5
    # Mamba-2
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    # the published ``mamba_chunk_size`` (256) is the source kernel's tile
    # and no part of the recurrence: ``ops/ssd.py`` walks chunks of this
    ssd_chunk: int = 128
    # initialisation of dt_bias (the Mamba-2 convention; not published)
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    # the multipliers
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: Optional[str] = None
    use_flash: Optional[bool] = None
    loss_chunk: int = 0

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        assert len(self.layer_types) == self.num_hidden_layers, \
            f"layer_types has {len(self.layer_types)} entries for " \
            f"{self.num_hidden_layers} layers"
        assert set(self.layer_types) <= {MAMBA, ATTENTION}, self.layer_types

    # what ``Mamba2Mixer`` and ``LagunaAttention`` read, under their names
    gating = False
    sliding_window = None
    a_log_init = staticmethod(_a_log_init)
    mamba_num_heads = property(lambda self: self.mamba_n_heads)
    mamba_head_dim = property(lambda self: self.mamba_d_head)
    n_groups = property(lambda self: self.mamba_n_groups)
    ssm_state_size = property(lambda self: self.mamba_d_state)
    conv_kernel = property(lambda self: self.mamba_d_conv)
    use_conv_bias = property(lambda self: self.mamba_conv_bias)
    chunk_size = property(lambda self: self.ssd_chunk)
    layer_norm_epsilon = property(lambda self: self.rms_norm_eps)

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    def num_params(self):
        """The initialised tree's count (the head is the embedding)."""
        H, heads = self.hidden_size, self.mamba_n_heads
        mamba = H * (self.d_inner + self.conv_dim + heads) \
            + self.conv_dim * (self.mamba_d_conv + self.mamba_conv_bias) \
            + 3 * heads + self.d_inner + self.d_inner * H
        attention = 2 * H * self.num_attention_heads * self.head_dim \
            + 2 * H * self.num_key_value_heads * self.head_dim
        mlp = 3 * H * self.shared_intermediate_size
        each = {MAMBA: mamba, ATTENTION: attention}
        return self.vocab_size * H + H \
            + sum(each[kind] + mlp + 2 * H for kind in self.layer_types)


class GraniteSharedMLP(nn.Module):
    """The dense SwiGLU of every layer: gate and value halves of ONE input
    matrix."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        F = cfg.shared_intermediate_size
        # ``mlp_fc`` names what the activation's backward pass reads
        gp = checkpoint_name(_dense(cfg, 2 * F, "input_linear")(x), "mlp_fc")
        h = nn.silu(gp[..., :F]) * gp[..., F:]
        return checkpoint_name(
            _dense(cfg, cfg.hidden_size, "output_linear")(h), "mlp_proj")


def _add_branch(x, multiplier, branch):
    """``x + multiplier * branch``, product and sum in float32 and rounded
    once: 0.22 is no bfloat16 number (0.2197 is the nearest, 0.12 % under
    it, and every layer's gradient with it); one fused pass either way."""
    return (x.astype(jnp.float32) + multiplier * branch.astype(
        jnp.float32)).astype(x.dtype)


class GraniteHybridBlock(nn.Module):
    config: GraniteHybridConfig
    kind: str                        # MAMBA | ATTENTION

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        h = norm("input_norm")(x)
        if self.kind == MAMBA:
            mixed = Mamba2Mixer(cfg, name="mamba")(h)
        else:
            mixed = LagunaAttention(
                cfg, FULL, cfg.num_attention_heads,
                scale=cfg.attention_multiplier, name="attn")(h, {FULL: None})
        mid = _add_branch(x, cfg.residual_multiplier, mixed)
        out = GraniteSharedMLP(cfg, name="shared_mlp")(norm("post_norm")(mid))
        if self.is_mutable_collection("intermediates"):
            # a caller's look at the stream the layer starts from and after
            # the mixer and at the two branches, as they leave their modules
            # (the benchmark's check against its reference); nothing in a
            # training step
            self.sow("intermediates", "x_in", x)
            self.sow("intermediates", "x_mid", mid)
            self.sow("intermediates", "mixer_out", mixed)
            self.sow("intermediates", "mlp_out", out)
        return _add_branch(mid, cfg.residual_multiplier, out)


def remat_row_bytes(cfg):
    """{checkpoint name: bytes a row, summed over the layers that carry
    it}: what ``models/laguna.stack_remat_policy`` weighs against its
    budget."""
    b = jnp.dtype(cfg.dtype).itemsize
    each = {MAMBA: mixer_row_bytes(cfg),
            ATTENTION: {"qkv": qkv_row_bytes(cfg, cfg.num_attention_heads)}}
    total = collections.Counter()
    for kind in cfg.layer_types:
        total.update(each[kind])
        total.update({"attn_proj": b * cfg.hidden_size,
                      "mlp_fc": 2 * b * cfg.shared_intermediate_size})
    return total


def remat_inflight_row_bytes(cfg, seq_len):
    """Bytes a row the widest branch of the widest layer holds between its
    recomputation and the end of its backward: what
    ``models/laguna.stack_remat_policy`` reserves beside the block inputs."""
    each = {MAMBA: mixer_inflight_row_bytes(cfg),
            ATTENTION: attention_inflight_row_bytes(
                cfg, cfg.num_attention_heads, seq_len)}
    return max(mlp_inflight(cfg.shared_intermediate_size,
                            jnp.dtype(cfg.dtype).itemsize),
               *(each[kind] for kind in cfg.layer_types))


class GraniteHybridForCausalLM(nn.Module):
    """Decoder-only LM whose head is its embedding; ``labels`` with
    ``loss_chunk`` takes the fused chunked head + loss
    (``models/gpt2.chunked_lm_loss``) on the final stream over
    ``logits_scaling``."""
    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, input_ids, labels=None):
        cfg = self.config
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with annotate("ds_embed"):
            x = (_embed_lookup(embed, input_ids)
                 * cfg.embedding_multiplier).astype(cfg.dtype)
        policy = stack_remat_policy(
            cfg, input_ids.size, len(cfg.layer_types), remat_row_bytes(cfg),
            remat_inflight_row_bytes(cfg, input_ids.shape[1]))
        for i, kind in enumerate(cfg.layer_types):
            x = remat_block(cfg, self, f"layer_{i}", GraniteHybridBlock,
                            policy)(cfg, kind, name=f"layer_{i}")(x)
        x = RMSNorm(eps=cfg.rms_norm_eps, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="norm")(x)
        x = (x / cfg.logits_scaling).astype(cfg.dtype)
        head = embed.astype(cfg.dtype)
        if labels is not None and cfg.loss_chunk > 0:
            return chunked_lm_loss(x, head, labels, cfg.loss_chunk)
        logits = jnp.einsum("bse,ve->bsv", x, head)
        if labels is not None:
            return lm_loss(logits, labels)
        return logits


def granite_hybrid_tiny(**over):
    """Five layers, the attention layer third, at tiny widths: 4 Mamba
    heads of 8 in ONE group with a state of 16, 4 / 2 attention heads of
    16; the multipliers as published."""
    kinds = over.get("layer_types", (MAMBA, MAMBA, ATTENTION, MAMBA, MAMBA))
    kw = dict(vocab_size=256, hidden_size=64, shared_intermediate_size=96,
              num_hidden_layers=len(kinds), layer_types=kinds,
              max_position_embeddings=256, mamba_n_heads=4, mamba_d_head=8,
              mamba_d_state=16, ssd_chunk=16, num_attention_heads=4,
              num_key_value_heads=2, dtype=jnp.float32,
              param_dtype=jnp.float32)
    kw.update(over)
    return GraniteHybridConfig(**kw)
