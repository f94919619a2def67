"""DeepSeek-V3-style decoder (``model_type: deepseek_v3``; Kanana-2 is one):
multi-head LATENT attention in every layer, a leading dense layer and expert
layers with a sigmoid router after it.

Every layer is pre-norm residual with a plain RMSNorm (weight one at
initialisation): ``x += attn(norm(x)); x += ffn(norm(x))``; a final RMSNorm;
an untied head; no bias anywhere.

- **Latent attention** (``mla_attn``), ``H`` heads. Queries are projected
  whole (``q_lora_rank`` null: no query compression), ``H x (qk_nope_head_dim
  + qk_rope_head_dim)``. Keys and values come from ONE down-projection of
  the token to ``kv_lora_rank + qk_rope_head_dim`` numbers (``kv_a_proj``):
  the first ``kv_lora_rank`` are the latent, RMS-normed (``kv_a_norm``) and
  expanded by ``kv_b_proj`` into every head's key WITHOUT position
  (``qk_nope_head_dim``) and value (``v_head_dim``); the last
  ``qk_rope_head_dim`` are ONE rotated key a token that all heads share.
  RoPE (``rope_theta``, no scaling) turns the pairs ``(2i, 2i+1)`` of the
  query's last ``qk_rope_head_dim`` columns and of the shared key
  (``rope_interleave``; ``rope_pairs`` says how). A head's score is
  ``(q_nope·k_nope + q_rope·k_rope) / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)`` under a causal softmax and its output ``v_head_dim``
  wide. In TRAINING that is multi-head attention with a q·k head wider than
  the value head (192 / 128): each head's key is materialised as ``[k_nope
  ; k_rope]`` and the three CHUNKED flash kernels take the two widths as
  they are (``ops/pallas/flash_attention.py``: V is never padded to the
  score's width, nothing ``[S, S]`` exists). The latent form pays off in a
  decode cache (``kv_lora_rank + qk_rope_head_dim`` numbers a token), which
  this repo does not have yet (ROADMAP R4).
- **Dense FFN** (layers before ``first_k_dense_replace``): SwiGLU of width
  ``intermediate_size``.
- **Expert FFN**: ``moe/dropless.DroplessMoE`` — ``s = sigmoid(x W_r)``,
  the ``num_experts_per_tok`` largest of ``s + e_score_correction_bias`` (a
  buffer with zero gradient; ``n_group = topk_group = 1``: no group limit),
  weights ``s`` at them, renormalised, times ``routed_scaling_factor``;
  SwiGLU experts of ``moe_intermediate_size``; ``n_shared_experts`` shared
  experts as ONE SwiGLU of their summed width, added ungated; and — a
  configuration's to say — only ``experts_held`` of the ``n_routed_experts``
  held here (one expert-parallel rank's share; nothing stands in for the
  other ranks, their rows or their all-to-all).

Every layer stands alone (``layer_<i>``), under its own gather edge and
remat (``models/laguna.remat_block``), as ``models/nemotron_h.py``'s do: NO
layer scan. A scan's stacked leaves cost whoever reads the tree a layer at a
time (the benchmark's float32 reference) a copy of every slice — 6 GB at the
benchmark's six layers, which a 16 GB chip does not have beside the engine's
state (PERF.md Findings PR 47); a deployment of 48 layers that wants the
scan's compile time back brings it with a reader that takes stacked leaves.
No multi-token-prediction module and no auxiliary loss (the published
config has a key for neither).
"""

import dataclasses
import math
from typing import Any, Optional

import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.models.gpt2 import (_embed_lookup, chunked_lm_loss,
                                       lm_loss)
from deepspeed_tpu.models.laguna import remat_block
from deepspeed_tpu.models.llama import RMSNorm, rope_angles
from deepspeed_tpu.moe.dropless import (CHOICE_BIAS, HELD_STAT_GAUGES,
                                        STAT_GAUGES, DroplessMoE)
from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.telemetry.spans import annotate


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """Keys under the published config's names (Kanana-2-30B-A3B's values).
    The published ``head_dim`` (64) is HF's name for the RoPE width and is
    read by nothing: the three head widths are ``qk_nope_head_dim``,
    ``qk_rope_head_dim`` and ``v_head_dim``."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    max_position_embeddings: int = 32768
    rope_theta: float = 1000000.0
    rope_interleave: bool = True
    first_k_dense_replace: int = 1
    # experts
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    n_group: int = 1
    topk_group: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    # a configuration's std of the DRAWN selection bias (0: zeros, as
    # published; a checkpoint brings the values its balancing rule left)
    e_score_correction_bias_std: float = 0.0
    experts_held: int = 0            # 0: all; else one rank's share ...
    expert_share: int = 0            # ... experts [held * share, ... + held)
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: Optional[str] = None
    use_flash: Optional[bool] = None
    loss_chunk: int = 0

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise NotImplementedError(
                f"q_lora_rank={self.q_lora_rank}: query compression is not "
                "written (the configurations this model runs project "
                "queries whole)")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: "
                "group-limited routing is not written")
        assert 0 <= self.first_k_dense_replace <= self.num_hidden_layers

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def attention_params(self):
        """Matmul parameters of one attention module + its latent norm."""
        H, n = self.hidden_size, self.num_attention_heads
        R = self.kv_lora_rank
        return H * n * self.qk_head_dim \
            + H * (R + self.qk_rope_head_dim) \
            + R * n * (self.qk_nope_head_dim + self.v_head_dim) \
            + n * self.v_head_dim * H + R

    def num_params(self):
        """Parameters held here (``experts_held`` experts an expert layer;
        the selection bias counted: it is a leaf of the tree)."""
        H, L = self.hidden_size, self.num_hidden_layers
        held = self.experts_held or self.n_routed_experts
        dense = 3 * H * self.intermediate_size
        sparse = H * self.n_routed_experts + self.n_routed_experts \
            + 3 * held * H * self.moe_intermediate_size \
            + 3 * H * self.n_shared_experts * self.moe_intermediate_size
        lead = self.first_k_dense_replace
        return 2 * self.vocab_size * H + H \
            + L * (self.attention_params() + 2 * H) \
            + lead * dense + (L - lead) * sparse


def _dense(cfg, n, name):
    return nn.Dense(n, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.normal(cfg.initializer_range),
                    name=name)


def rope_pairs(x, cos, sin, interleaved=True):
    """RoPE on the last axis of ``x`` [B, S, heads, d] at positions 0..S-1
    (``cos``, ``sin`` [S, d / 2], float32). ``interleaved``: the published
    layout, column 2i with column 2i+1 a pair turned by ``pos x
    theta^(-2i/d)``; the result comes back HALF-SPLIT (the pairs' first
    members, then their second: HF's ``apply_rotary_pos_emb_interleave``
    permutes so and then rotates halves) — the same permutation on a query
    and on the key it meets leaves their product as it was, so for the
    scores the layout is a relabelling of the columns of ``q_proj`` and
    ``kv_a_proj``. Not ``interleaved``: the columns are half-split as they
    come."""
    xf = x.astype(jnp.float32)
    if interleaved:
        pairs = xf.reshape(*xf.shape[:-1], xf.shape[-1] // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = jnp.split(xf, 2, axis=-1)
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(x.dtype)


class MLAttention(nn.Module):
    """The latent-attention branch of the module docstring. ``rope``:
    (cos, sin) [S, qk_rope_head_dim / 2]."""
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        B, S, _ = x.shape
        H, R = cfg.num_attention_heads, cfg.kv_lora_rank
        Dn, Dr, Dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        q = _dense(cfg, H * (Dn + Dr), "q_proj")(x).reshape(B, S, H, Dn + Dr)
        with annotate("mla_latent"):
            down = _dense(cfg, R + Dr, "kv_a_proj")(x)
            latent = RMSNorm(eps=cfg.rms_norm_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype,
                             name="kv_a_norm")(down[..., :R])
        with annotate("mla_expand"):
            kv = _dense(cfg, H * (Dn + Dv), "kv_b_proj")(latent).reshape(
                B, S, H, Dn + Dv)
        with annotate("mla_rope"):
            q_rope = rope_pairs(q[..., Dn:], *rope, cfg.rope_interleave)
            k_rope = rope_pairs(down[..., None, R:], *rope,
                                cfg.rope_interleave)        # [B, S, 1, Dr]
            q = jnp.concatenate([q[..., :Dn], q_rope], axis=-1)
        with annotate("mla_expand"):
            # the kernels' K operand: every head's own key without position
            # beside the ONE rotated key of the token, head-major
            k = jnp.concatenate(
                [kv[..., :Dn], jnp.broadcast_to(k_rope, (B, S, H, Dr))],
                axis=-1)
            v = kv[..., Dn:]
        q, k, v = (checkpoint_name(t, "qkv").transpose(0, 2, 1, 3)
                   for t in (q, k, v))
        # scale 1 / sqrt(Dn + Dr): ``rope_scaling`` null, so no mscale
        out = dot_product_attention(q, k, v, causal=True,
                                    scale=1.0 / math.sqrt(Dn + Dr),
                                    use_flash=cfg.use_flash)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * Dv)
        return checkpoint_name(_dense(cfg, cfg.hidden_size, "o_proj")(out),
                               "attn_proj")


class DenseMLP(nn.Module):
    config: DeepseekV3Config

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        with annotate("dense_mlp"):
            h = nn.silu(_dense(cfg, cfg.intermediate_size, "gate_proj")(x)) \
                * _dense(cfg, cfg.intermediate_size, "up_proj")(x)
            h = checkpoint_name(h, "mlp_fc")
            return checkpoint_name(
                _dense(cfg, cfg.hidden_size, "down_proj")(h), "mlp_proj")


class DeepseekV3Block(nn.Module):
    config: DeepseekV3Config
    sparse: bool

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        mixed = MLAttention(cfg, name="mla_attn")(norm("input_norm")(x), rope)
        x = x + mixed
        h = norm("post_attn_norm")(x)
        if not self.sparse:
            out = DenseMLP(cfg, name="mlp")(h)
        else:
            std = cfg.e_score_correction_bias_std
            out = DroplessMoE(
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size,
                norm_topk_prob=cfg.norm_topk_prob, balance_coeff=0.0,
                z_coeff=0.0, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                experts_held=cfg.experts_held, expert_share=cfg.expert_share,
                shared_d_ff=cfg.n_shared_experts * cfg.moe_intermediate_size,
                routed_scale=cfg.routed_scaling_factor, shared_gate=False,
                score="sigmoid", choice_bias=True,
                choice_bias_init=nn.initializers.normal(std) if std
                else nn.initializers.zeros,
                # ``remat_block``'s policy saves the router's choice
                pin_choice=cfg.remat, name="mlp")(h)
        if self.is_mutable_collection("intermediates"):
            # a caller's look at the stream after the mixer and at the two
            # branches (the benchmark's check against its reference);
            # nothing in a training step
            self.sow("intermediates", "x_mid", x)
            self.sow("intermediates", "mixer_out", mixed)
            self.sow("intermediates", "ffn_out", out)
        return x + out


class DeepseekV3ForCausalLM(nn.Module):
    """Decoder-only LM; ``labels`` with ``loss_chunk`` takes the fused
    chunked head + loss (``models/gpt2.chunked_lm_loss``)."""
    config: DeepseekV3Config

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
        lead = cfg.first_k_dense_replace
        embed = self.param("embed_tokens",
                           nn.initializers.normal(cfg.initializer_range),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with annotate("ds_embed"):
            x = _embed_lookup(embed, input_ids).astype(cfg.dtype)
        rope = rope_angles(jnp.arange(input_ids.shape[1]),
                           cfg.qk_rope_head_dim, cfg.rope_theta)
        for i in range(cfg.num_hidden_layers):
            x = remat_block(cfg, self, f"layer_{i}", DeepseekV3Block)(
                cfg, i >= lead, name=f"layer_{i}")(x, rope)
        x = RMSNorm(eps=cfg.rms_norm_eps, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="norm")(x)
        head = self.param("lm_head",
                          nn.initializers.normal(cfg.initializer_range),
                          (cfg.vocab_size, cfg.hidden_size),
                          cfg.param_dtype)
        if labels is not None and cfg.loss_chunk > 0:
            return chunked_lm_loss(x, head.astype(cfg.dtype), labels,
                                   cfg.loss_chunk)
        logits = jnp.einsum("bse,ve->bsv", x, head.astype(cfg.dtype))
        if labels is not None:
            return lm_loss(logits, labels)
        return logits


def deepseek_v3_tiny(**over):
    """Four layers (1 dense + 3 expert) at tiny widths: 4 heads with a q·k
    head of 48 = 32 + 16 rotated and a value head of 32 over a latent of 24,
    8 experts top-2 and two shared."""
    kw = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
              moe_intermediate_size=24, num_hidden_layers=4,
              num_attention_heads=4, kv_lora_rank=24, qk_nope_head_dim=32,
              qk_rope_head_dim=16, v_head_dim=32, max_position_embeddings=256,
              n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=2,
              e_score_correction_bias_std=0.1, dtype=jnp.float32,
              param_dtype=jnp.float32)
    kw.update(over)
    return DeepseekV3Config(**kw)
