"""DeepSeek-V3-style decoder (``model_type: deepseek_v3``; Kanana-2 is one,
Xing4.0 — ``model_type: xing4_0`` — another): multi-head LATENT attention in
every layer, a leading dense layer and expert layers with a sigmoid router
after it.

Every layer is pre-norm residual with a plain RMSNorm (weight one at
initialisation): ``x += attn(norm(x)); x += ffn(norm(x))``; a final RMSNorm;
an untied head; no bias anywhere. With ``hc_mult`` n > 1 the residual path is
n streams and each of the two adds becomes a read and a write through learned
mixes (``models/hyper_connections.py``); ``hc_mult`` 1 is the one stream of
the line above, letter for letter.

- **Latent attention** (``mla_attn``), ``H`` heads. Queries are ``H x
  (qk_nope_head_dim + qk_rope_head_dim)`` wide: projected whole (``q_proj``)
  where ``q_lora_rank`` is null, else COMPRESSED — ``q_a_proj`` down to
  ``q_lora_rank`` numbers, an RMSNorm over them (``q_a_norm``), ``q_b_proj``
  up. Keys and values come from ONE down-projection of
  the token to ``kv_lora_rank + qk_rope_head_dim`` numbers (``kv_a_proj``):
  the first ``kv_lora_rank`` are the latent, RMS-normed (``kv_a_norm``) and
  expanded by ``kv_b_proj`` into every head's key WITHOUT position
  (``qk_nope_head_dim``) and value (``v_head_dim``); the last
  ``qk_rope_head_dim`` are ONE rotated key a token that all heads share.
  RoPE (``rope_theta``) turns the pairs ``(2i, 2i+1)`` of the
  query's last ``qk_rope_head_dim`` columns and of the shared key
  (``rope_interleave``; ``rope_pairs`` says how). A head's score is
  ``(q_nope·k_nope + q_rope·k_rope) / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)`` under a causal softmax and its output ``v_head_dim``
  wide. ``rope_scaling`` null is plain RoPE; type ``yarn`` is DeepSeek-V3's
  form (``rope_tables``): blended inverse frequencies
  (``models/laguna.yarn_rope_angles``), cos and sin times ``yarn_mscale(factor,
  mscale) / yarn_mscale(factor, mscale_all_dim)`` and the softmax scale times
  ``yarn_mscale(factor, mscale_all_dim)^2``. In TRAINING that is multi-head
  attention with a q·k head wider than the value head (192 / 128): each
  head's key is materialised as ``[k_nope
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
No auxiliary loss.

**Multi-token prediction** (``num_nextn_predict_layers`` 1; DeepSeek-V3,
arXiv 2412.19437 eq. 21-25 at depth 1), a TRAINING loss term: ``h'_i =
[RMSNorm_h(h_i) ; RMSNorm_e(Emb(t_{i+1}))] M`` with ``h_i`` the trunk's state
BEFORE the final norm (the summed streams) and ``M`` ``mtp_eh_proj`` [2C, C];
one more expert block (``mtp_layer``, its own stream mixers, the copy into the
streams and their sum at its ends), its own head norm (``mtp_norm``), the
SHARED ``lm_head`` and ``embed_tokens``; ``loss = loss_main +
mtp_loss_weight x CE(head(h^1_i), t_{i+2})``, the second term sown into
``stats`` as ``mtp_loss``. Everything of it runs under the scope ``mtp``.
Without ``labels`` the module is not run (in serving it is a drafter, which
this repo does not have: ROADMAP R8).
"""

import collections
import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.models import hyper_connections as hc
from deepspeed_tpu.models.gpt2 import _embed_lookup, chunked_lm_loss, lm_loss
from deepspeed_tpu.models.laguna import (remat_block, stack_remat_policy,
                                         yarn_rope_angles)
from deepspeed_tpu.models.llama import RMSNorm, rope_angles
from deepspeed_tpu.moe.dropless import (CHOICE_BIAS, HELD_STAT_GAUGES,
                                        STAT_GAUGES, DroplessMoE)
from deepspeed_tpu.moe.dropless import inflight_row_bytes as moe_inflight
from deepspeed_tpu.moe.dropless import remat_row_bytes as moe_row_bytes
from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.ops.pallas.flash_attention import bwd_dq_slab_rows
from deepspeed_tpu.runtime.remat_budget import (attention_inflight,
                                                mlp_inflight)
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
    # None, or the published dict of type "yarn": factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim (kept as a tuple of items)
    rope_scaling: Any = None
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
    # residual streams (models/hyper_connections.py); 1: the one stream
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    # a configuration's way to DRAW the stream mixers' leaves (a checkpoint
    # brings trained values): phi normal, the three gates round a mean, the
    # offsets round zero
    hc_phi_std: float = 0.02
    hc_gate_mean: float = 1.0
    hc_gate_std: float = 0.0
    hc_bias_std: float = 0.0
    # multi-token prediction: 0 or 1 module, its loss's weight
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: Optional[str] = None
    use_flash: Optional[bool] = None
    loss_chunk: int = 0

    def __post_init__(self):
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.rope_scaling is not None:
            kind = dict(self.rope_scaling).get(
                "type", dict(self.rope_scaling).get("rope_type"))
            if kind != "yarn":
                raise NotImplementedError(
                    f"rope_scaling type {kind!r}: only null and 'yarn' are "
                    "written")
        if self.num_nextn_predict_layers not in (0, 1):
            raise NotImplementedError(
                f"num_nextn_predict_layers={self.num_nextn_predict_layers}: "
                "one multi-token-prediction module (depth 1) is written")
        if self.n_group != 1 or self.topk_group != 1:
            raise NotImplementedError(
                f"n_group={self.n_group}, topk_group={self.topk_group}: "
                "group-limited routing is not written")
        assert 0 <= self.first_k_dense_replace <= self.num_hidden_layers

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def yarn(self):
        """The ``rope_scaling`` dict (None: plain RoPE)."""
        return None if self.rope_scaling is None else dict(self.rope_scaling)

    @property
    def softmax_scale(self):
        """1 / sqrt(q·k head) — under YaRN times ``yarn_mscale(factor,
        mscale_all_dim)^2`` (DeepSeek-V3's attention; 2.0047 at factor 64)."""
        scale = 1.0 / math.sqrt(self.qk_head_dim)
        if self.yarn and self.yarn.get("mscale_all_dim", 0):
            scale *= yarn_mscale(self.yarn["factor"],
                                 self.yarn["mscale_all_dim"]) ** 2
        return scale

    def attention_params(self):
        """Matmul parameters of one attention module + its latent norms."""
        H, n = self.hidden_size, self.num_attention_heads
        R, Q = self.kv_lora_rank, self.q_lora_rank
        q = H * n * self.qk_head_dim if Q is None \
            else H * Q + Q + Q * n * self.qk_head_dim
        return q + H * (R + self.qk_rope_head_dim) \
            + R * n * (self.qk_nope_head_dim + self.v_head_dim) \
            + n * self.v_head_dim * H + R

    def stream_mixer_params(self):
        """One branch's stream mixer: phi, its offsets, three gates (0 with
        one stream)."""
        n = self.hc_mult
        width = 2 * n + n * n
        return 0 if n == 1 else n * self.hidden_size * width + width + 3

    def layer_params(self, sparse):
        """One layer: attention, the two block norms, the two stream mixers
        and its FFN (``experts_held`` experts an expert layer; the selection
        bias counted: it is a leaf of the tree)."""
        H = self.hidden_size
        held = self.experts_held or self.n_routed_experts
        ffn = 3 * H * self.intermediate_size if not sparse \
            else H * self.n_routed_experts + self.n_routed_experts \
            + 3 * held * H * self.moe_intermediate_size \
            + 3 * H * self.n_shared_experts * self.moe_intermediate_size
        return self.attention_params() + 2 * H \
            + 2 * self.stream_mixer_params() + ffn

    def mtp_params(self):
        """The prediction module's own leaves: ``mtp_eh_proj``, three norms
        and one expert block (embedding and head are the trunk's)."""
        H = self.hidden_size
        return self.num_nextn_predict_layers * (
            2 * H * H + 3 * H + self.layer_params(True))

    def num_params(self):
        """Parameters held here."""
        H, L = self.hidden_size, self.num_hidden_layers
        lead = self.first_k_dense_replace
        return 2 * self.vocab_size * H + H \
            + lead * self.layer_params(False) \
            + (L - lead) * self.layer_params(True) + self.mtp_params()


def _dense(cfg, n, name):
    return nn.Dense(n, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.normal(cfg.initializer_range),
                    name=name)


def _norm(cfg, name):
    return RMSNorm(eps=cfg.rms_norm_eps, dtype=cfg.dtype,
                   param_dtype=cfg.param_dtype, name=name)


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


def yarn_mscale(factor, mscale):
    """DeepSeek's ``yarn_get_mscale``: ``0.1 mscale ln factor + 1`` (1 for a
    factor of at most 1)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(cfg, positions):
    """(cos, sin) [S, qk_rope_head_dim / 2] float32: plain RoPE, or under
    ``rope_scaling`` of type yarn the blended frequencies with cos and sin
    times ``yarn_mscale(factor, mscale) / yarn_mscale(factor,
    mscale_all_dim)`` (the score's share of the scaling is
    ``DeepseekV3Config.softmax_scale``)."""
    y = cfg.yarn
    if y is None:
        return rope_angles(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
    factor = float(y["factor"])
    return yarn_rope_angles(
        positions, cfg.qk_rope_head_dim, float(cfg.rope_theta), factor,
        y["original_max_position_embeddings"],
        float(y.get("beta_fast", 32.0)), float(y.get("beta_slow", 1.0)),
        attention_factor=yarn_mscale(factor, y.get("mscale", 1))
        / yarn_mscale(factor, y.get("mscale_all_dim", 0)))


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
        if cfg.q_lora_rank is None:
            q = _dense(cfg, H * (Dn + Dr), "q_proj")(x)
        else:
            with annotate("mla_latent"):
                c_q = _norm(cfg, "q_a_norm")(checkpoint_name(
                    _dense(cfg, cfg.q_lora_rank, "q_a_proj")(x), "qkv"))
            q = _dense(cfg, H * (Dn + Dr), "q_b_proj")(c_q)
        q = q.reshape(B, S, H, Dn + Dr)
        with annotate("mla_latent"):
            # ``qkv``: what the backward pass reads of the projections, the
            # latents ahead of their norms here, the kernels' operands below
            down = checkpoint_name(_dense(cfg, R + Dr, "kv_a_proj")(x), "qkv")
            latent = _norm(cfg, "kv_a_norm")(down[..., :R])
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
        # 1 / sqrt(Dn + Dr), under YaRN times its mscale squared
        out = dot_product_attention(q, k, v, causal=True,
                                    scale=cfg.softmax_scale,
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
            # ``mlp_fc`` names what the activation's backward pass reads
            pre = lambda name: checkpoint_name(  # noqa: E731
                _dense(cfg, cfg.intermediate_size, name)(x), "mlp_fc")
            h = nn.silu(pre("gate_proj")) * pre("up_proj")
            return checkpoint_name(
                _dense(cfg, cfg.hidden_size, "down_proj")(h), "mlp_proj")


class DeepseekV3Block(nn.Module):
    config: DeepseekV3Config
    sparse: bool

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        if cfg.hc_mult > 1:
            return self._streams(x, rope)
        mixed = MLAttention(cfg, name="mla_attn")(
            _norm(cfg, "input_norm")(x), rope)
        x = x + mixed
        out = self._ffn(_norm(cfg, "post_attn_norm")(x))
        if self.is_mutable_collection("intermediates"):
            # a caller's look at the stream after the mixer and at the two
            # branches (the benchmark's check against its reference);
            # nothing in a training step
            self.sow("intermediates", "x_mid", x)
            self.sow("intermediates", "mixer_out", mixed)
            self.sow("intermediates", "ffn_out", out)
        return x + out

    @nn.nowrap
    def _streams(self, x, rope):
        """The block over ``hc_mult`` streams, ``x`` [B, S, n C]: each
        branch reads ``u = H_pre X`` and writes ``H_res X + H_post y``."""
        cfg = self.config
        look = self.is_mutable_collection("intermediates")

        def branch(x, name, f):
            # the coefficients and ``u`` from one pass over the stream; the
            # stream comes back for ``write`` so that its cotangent joins
            # the others inside the mixer's backward rule
            u, coeff, x = hc.mix(hc.StreamMixer(
                n=cfg.hc_mult, sinkhorn_iters=cfg.hc_sinkhorn_iters,
                eps=cfg.hc_eps,
                clamp=(cfg.mhc_h_res_clamp_min, cfg.mhc_h_res_clamp_max),
                phi_std=cfg.hc_phi_std, gate_mean=cfg.hc_gate_mean,
                gate_std=cfg.hc_gate_std, bias_std=cfg.hc_bias_std,
                param_dtype=cfg.param_dtype, name=name), x)
            _, h_post, h_res = coeff
            y = f(u)
            if look:
                self.sow("intermediates", name + "_coeff", coeff)
            return hc.write(x, y, h_post, h_res), y

        x, mixed = branch(x, "attn_hc", lambda u: MLAttention(
            cfg, name="mla_attn")(_norm(cfg, "input_norm")(u), rope))
        if look:
            self.sow("intermediates", "x_mid", x)
            self.sow("intermediates", "mixer_out", mixed)
        x, out = branch(x, "ffn_hc", lambda u: self._ffn(
            _norm(cfg, "post_attn_norm")(u)))
        if look:
            self.sow("intermediates", "ffn_out", out)
            self.sow("intermediates", "x_out", x)
        return x

    @nn.nowrap
    def _ffn(self, h):
        cfg = self.config
        if not self.sparse:
            return DenseMLP(cfg, name="mlp")(h)
        std = cfg.e_score_correction_bias_std
        return DroplessMoE(
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


def remat_row_bytes(cfg):
    """{checkpoint name: bytes a row, summed over the blocks that carry it
    — the layers and the prediction module's}: what
    ``models/laguna.stack_remat_policy`` weighs against its budget."""
    b = jnp.dtype(cfg.dtype).itemsize
    heads = cfg.num_attention_heads
    attention = {
        "qkv": b * (heads * (2 * cfg.qk_head_dim + cfg.v_head_dim)
                    + cfg.kv_lora_rank + cfg.qk_rope_head_dim
                    + (cfg.q_lora_rank or 0)),
        "attn_proj": b * cfg.hidden_size,
        # several streams: ``hc.write``'s backward pass reads the branch it
        # adds, the FFN's as the mixer's (one stream: nothing reads it)
        "mlp_proj": b * cfg.hidden_size * (cfg.hc_mult > 1)}
    experts = moe_row_bytes(
        cfg.n_routed_experts,
        cfg.n_shared_experts * cfg.moe_intermediate_size, itemsize=b)
    total = collections.Counter()
    for i in range(cfg.num_hidden_layers + cfg.num_nextn_predict_layers):
        total.update(attention)
        total.update({"mlp_fc": 2 * b * cfg.intermediate_size}
                     if i < cfg.first_k_dense_replace else experts)
    return total


def remat_inflight_row_bytes(cfg, seq_len):
    """Bytes a row the widest branch of a block holds between its
    recomputation and the end of its backward: what
    ``models/laguna.stack_remat_policy`` reserves beside the block inputs.
    Latent attention's k and v stand expanded, a head each, as q does."""
    b = jnp.dtype(cfg.dtype).itemsize
    heads = cfg.num_attention_heads
    q, v = heads * cfg.qk_head_dim, heads * cfg.v_head_dim
    blocks = cfg.num_hidden_layers + cfg.num_nextn_predict_layers
    return max(
        attention_inflight(q, v, q + v, b, bwd_dq_slab_rows(
            seq_len, cfg.qk_head_dim, cfg.v_head_dim, b)),
        mlp_inflight(cfg.intermediate_size, b)
        if cfg.first_k_dense_replace else 0,
        moe_inflight(
            cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok, cfg.n_routed_experts,
            cfg.experts_held or cfg.n_routed_experts,
            cfg.n_shared_experts * cfg.moe_intermediate_size, itemsize=b)
        if cfg.first_k_dense_replace < blocks else 0)


class DeepseekV3ForCausalLM(nn.Module):
    """Decoder-only LM; ``labels`` with ``loss_chunk`` takes the fused
    chunked head + loss (``models/gpt2.chunked_lm_loss``)."""
    config: DeepseekV3Config

    sown_collections = ("losses", "stats")
    # leaves the engine hands back from a step as they came: the routers'
    # selection bias (``moe/dropless.DroplessMoE``)
    buffer_leaves = (CHOICE_BIAS,)

    # of ``stats``, what the engine folds as the LARGEST value sown in the
    # step where it folds the others' mean
    stat_maxima = tuple(hc.STAT_GAUGES)

    @property
    def stat_gauges(self):
        """{variable sown into ``stats``: the gauge it is read under}."""
        cfg = self.config
        return {**(HELD_STAT_GAUGES if cfg.experts_held else STAT_GAUGES),
                **(hc.STAT_GAUGES if cfg.hc_mult > 1 else {}),
                **({"mtp_loss": "mtp/loss"}
                   if cfg.num_nextn_predict_layers else {})}

    @nn.compact
    def __call__(self, input_ids, labels=None):
        cfg = self.config
        lead, n = cfg.first_k_dense_replace, cfg.hc_mult
        embed = self.param("embed_tokens",
                           nn.initializers.normal(cfg.initializer_range),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with annotate("ds_embed"):
            x = _embed_lookup(embed, input_ids).astype(cfg.dtype)
        rope = rope_tables(cfg, jnp.arange(input_ids.shape[1]))

        # ONE remat policy object for all the blocks, the prediction
        # module's among them; a block's input is its n streams
        policy = stack_remat_policy(
            cfg, input_ids.size,
            cfg.num_hidden_layers + cfg.num_nextn_predict_layers,
            remat_row_bytes(cfg),
            remat_inflight_row_bytes(cfg, input_ids.shape[1]), streams=n)

        def layers(x, names, sparse):
            """``x`` [B, S, C] through the blocks ``names``: copied into the
            streams ahead of them and the streams summed after, where there
            are several."""
            if n > 1:
                with annotate("mhc_write"):
                    x = hc.spread(x, n)
            for name, kind in zip(names, sparse):
                x = remat_block(cfg, self, name, DeepseekV3Block, policy)(
                    cfg, kind, name=name)(x, rope)
            if n > 1:
                with annotate("mhc_read"):
                    x = hc.merge(x, n)
            return x

        depth = range(cfg.num_hidden_layers)
        trunk = layers(x, [f"layer_{i}" for i in depth],
                       [i >= lead for i in depth])
        x = _norm(cfg, "norm")(trunk)
        head = self.param("lm_head",
                          nn.initializers.normal(cfg.initializer_range),
                          (cfg.vocab_size, cfg.hidden_size),
                          cfg.param_dtype)

        def loss_of(x, offset):
            if cfg.loss_chunk > 0:
                return chunked_lm_loss(x, head.astype(cfg.dtype), labels,
                                       cfg.loss_chunk, offset=offset)
            return lm_loss(jnp.einsum("bse,ve->bsv", x,
                                      head.astype(cfg.dtype)), labels,
                           offset=offset)

        predicted = None
        if cfg.num_nextn_predict_layers and (labels is not None
                                             or self.is_initializing()):
            with annotate("mtp"):
                # position i joins the trunk's state with the embedding of
                # token i + 1 and is scored against token i + 2; the roll's
                # wrapped last column lies behind every scored position
                with annotate("ds_embed"):
                    nxt = _embed_lookup(
                        embed, jnp.roll(input_ids, -1, axis=1)).astype(
                            cfg.dtype)
                joined = _dense(cfg, cfg.hidden_size, "mtp_eh_proj")(
                    jnp.concatenate([_norm(cfg, "mtp_hnorm")(trunk),
                                     _norm(cfg, "mtp_enorm")(nxt)], axis=-1))
                if self.is_mutable_collection("intermediates"):
                    self.sow("intermediates", "mtp_joined", joined)
                predicted = _norm(cfg, "mtp_norm")(
                    layers(joined, ["mtp_layer"], [True]))
        if labels is None:
            return jnp.einsum("bse,ve->bsv", x, head.astype(cfg.dtype))
        loss = loss_of(x, 1)
        if predicted is not None:
            with annotate("mtp"):
                mtp_loss = loss_of(predicted, 2)
            self.sow("stats", "mtp_loss", jax.lax.stop_gradient(mtp_loss))
            loss = loss + cfg.mtp_loss_weight * mtp_loss
        return loss


def xing4_tiny(**over):
    """``deepseek_v3_tiny`` with what Xing4.0 adds: compressed queries (a
    query latent of 40), YaRN, four residual streams with drawn mixers and
    the multi-token-prediction module."""
    kw = dict(q_lora_rank=40, rope_theta=10000.0,
              rope_scaling={"type": "yarn", "factor": 64.0,
                            "original_max_position_embeddings": 16,
                            "beta_fast": 32, "beta_slow": 1, "mscale": 1,
                            "mscale_all_dim": 1},
              hc_mult=4, hc_phi_std=0.1, hc_gate_mean=0.5, hc_gate_std=0.1,
              hc_bias_std=0.5, num_nextn_predict_layers=1)
    kw.update(over)
    return deepseek_v3_tiny(**kw)


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
