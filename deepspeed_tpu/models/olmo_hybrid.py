"""Olmo Hybrid (``model_type: olmo_hybrid``) — a dense hybrid decoder: Gated
DeltaNet layers with a full-attention layer where the published list
``layer_types`` says (allenai/Olmo-Hybrid-7B: 32 layers, 8 periods of
``linear_attention`` x 3 + ``full_attention``), every FFN a dense SwiGLU,
and OLMo 2 / 3's REORDERED norms — a norm on each branch's OUTPUT, none on
its input:

    h = x + RMSNorm(Mixer(x))
    y = h + RMSNorm(W_down(silu(W_gate h) * (W_up h)))

RMSNorm is plain ``x / rms(x) * w`` (``models/llama.RMSNorm``), w one at
initialisation; no bias anywhere.

- **Gated DeltaNet** (``linear_attn``): ``models/qwen3_next.GatedDeltaNet``
  as it stands, read under this family's ``linear_*`` keys — as many value
  heads as key heads (30), a value head TWICE as wide as a key head (96 x
  192: neither on the 128-lane grid, so the module lays q | k | v | z out
  zero-padded to 128 x 256 once and all three stages run their kernels on
  whole tiles) and ``linear_allow_neg_eigval``: beta = 2 sigmoid(b) in
  (0, 2), a token's transition ``I - beta k k^T`` with an eigenvalue in
  (-1, 1).
- **Attention** (``attn``): plain multi-head attention, every query head its
  own KV head, an RMSNorm with a learned weight over the WHOLE q projection
  and one over the whole k (``qk_norm``: OLMo 2's, what ``models/llama.
  LlamaAttention`` calls ``qk_norm=True``), NO rotation (``rope_theta``
  null: the DeltaNet layers carry position), causal softmax at ``head_dim
  ** -0.5`` through ``dot_product_attention``.
- **MLP** (``mlp``): ``models/laguna.LagunaDenseMLP``, ``W_down(silu(W_gate
  h) * (W_up h))`` at ``intermediate_size``.

The layer scan's body is one PERIOD of the list (``OlmoHybridConfig.
period``: the shortest prefix whose repetition is the list), each layer
under its own ZeRO-3 gather edge and, where the config asks, its own remat
(``models/laguna.remat_block``), as ``models/qwen3_next._Period``; the
parameters of period p's j-th layer are slice p of the leaves under
``layers/l<j>``.
"""

import collections
import dataclasses
from typing import Any, Optional

import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.models.gpt2 import _embed_lookup, chunked_lm_loss, lm_loss
from deepspeed_tpu.models.laguna import (LagunaDenseMLP, remat_block,
                                         stack_remat_policy)
from deepspeed_tpu.models.llama import RMSNorm
from deepspeed_tpu.models.qwen3_next import (GatedDeltaNet, _dense,
                                             gdn_inflight_row_bytes,
                                             gdn_row_bytes)
from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.ops.pallas.flash_attention import bwd_dq_slab_rows
from deepspeed_tpu.runtime.remat_budget import (attention_inflight,
                                                mlp_inflight)
from deepspeed_tpu.telemetry.spans import annotate

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class OlmoHybridConfig:
    """Keys under the published config's names; the defaults are
    Olmo-Hybrid-7B as published (``layer_types`` None: the published
    pattern, three DeltaNet layers then one of attention): 7.43B
    parameters."""
    vocab_size: int = 100352
    hidden_size: int = 3840
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    layer_types: Any = None
    num_attention_heads: int = 30
    num_key_value_heads: int = 30
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    # Gated DeltaNet
    linear_num_key_heads: int = 30
    linear_num_value_heads: int = 30
    linear_key_head_dim: int = 96
    linear_value_head_dim: int = 192
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: Optional[str] = None
    use_flash: Optional[bool] = None
    loss_chunk: int = 0

    def __post_init__(self):
        kinds = self.layer_types or tuple(
            FULL if i % 4 == 3 else LINEAR
            for i in range(self.num_hidden_layers))
        object.__setattr__(self, "layer_types", tuple(kinds))
        assert len(self.layer_types) == self.num_hidden_layers, \
            f"layer_types has {len(self.layer_types)} entries for " \
            f"{self.num_hidden_layers} layers"
        assert set(self.layer_types) <= {LINEAR, FULL}, self.layer_types
        assert self.num_key_value_heads == self.num_attention_heads, \
            "every query head has its own KV head"

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def period(self):
        """Layers of the scan's body: the shortest prefix of ``layer_types``
        whose repetition is the list."""
        kinds = self.layer_types
        return next(n for n in range(1, len(kinds) + 1)
                    if len(kinds) % n == 0
                    and kinds == kinds[:n] * (len(kinds) // n))

    def num_params(self):
        """The initialised tree's count."""
        H = self.hidden_size
        key = self.linear_num_key_heads * self.linear_key_head_dim
        val = self.linear_num_value_heads * self.linear_value_head_dim
        heads = self.linear_num_value_heads
        linear = H * (2 * key + 2 * val) + 2 * H * heads \
            + self.linear_conv_kernel_dim * (2 * key + val) + 2 * heads \
            + self.linear_value_head_dim + val * H
        attention = 4 * H * H + 2 * H
        mlp = 3 * H * self.intermediate_size
        each = {LINEAR: linear, FULL: attention}
        return 2 * self.vocab_size * H + H \
            + sum(each[kind] + mlp + 2 * H for kind in self.layer_types)


class OlmoHybridAttention(nn.Module):
    config: OlmoHybridConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        B, S, E = x.shape
        H, D = cfg.num_attention_heads, cfg.head_dim
        q, k, v = (_dense(cfg, H * D, name)(x)
                   for name in ("q_proj", "k_proj", "v_proj"))
        with annotate("qk_norm"):
            # one weight vector over the WHOLE projection, not a head's
            norm = lambda name: RMSNorm(  # noqa: E731
                eps=cfg.rms_norm_eps, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, name=name)
            q, k = norm("q_norm")(q), norm("k_norm")(k)
        # ``qkv`` names what the backward pass reads, the kernels' operands:
        # kept, neither a projection nor a norm is run again
        q, k, v = (checkpoint_name(
            t.reshape(B, S, H, D).transpose(0, 2, 1, 3), "qkv")
            for t in (q, k, v))
        out = dot_product_attention(q, k, v, causal=True,
                                    use_flash=cfg.use_flash)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        return checkpoint_name(_dense(cfg, E, "o_proj")(out), "attn_proj")


class OlmoHybridBlock(nn.Module):
    config: OlmoHybridConfig
    kind: str                        # LINEAR | FULL

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        if self.kind == LINEAR:
            mixed = GatedDeltaNet(cfg, name="linear_attn")(x)
        else:
            mixed = OlmoHybridAttention(cfg, name="attn")(x)
        mixed = norm("post_attn_norm")(mixed)
        mid = x + mixed
        out = norm("post_ffn_norm")(LagunaDenseMLP(cfg, name="mlp")(mid))
        if self.is_mutable_collection("intermediates"):
            # a caller's look at the stream the layer starts from and at the
            # two branches as they are added (the benchmark's check against
            # its reference); nothing in a training step
            self.sow("intermediates", "x_in", x)
            self.sow("intermediates", "mixer_out", mixed)
            self.sow("intermediates", "mlp_out", out)
        return mid + out


def remat_row_bytes(cfg):
    """{checkpoint name: bytes a row, summed over the layers that carry
    it}: what ``models/laguna.stack_remat_policy`` weighs against its
    budget."""
    b = jnp.dtype(cfg.dtype).itemsize
    each = {LINEAR: gdn_row_bytes(cfg),
            FULL: {"qkv": 3 * b * cfg.hidden_size}}
    total = collections.Counter()
    for kind in cfg.layer_types:
        total.update(each[kind])
        total.update({"attn_proj": b * cfg.hidden_size,
                      "mlp_fc": 2 * b * cfg.intermediate_size})
    return total


def remat_inflight_row_bytes(cfg, seq_len):
    """Bytes a row the widest branch of the widest layer holds between its
    recomputation and the end of its backward: what
    ``models/laguna.stack_remat_policy`` reserves beside the block inputs."""
    b = jnp.dtype(cfg.dtype).itemsize
    H, D = cfg.hidden_size, cfg.head_dim
    each = {LINEAR: gdn_inflight_row_bytes(cfg),
            FULL: attention_inflight(
                H, H, 2 * H, b, bwd_dq_slab_rows(seq_len, D, D, b))}
    return max(mlp_inflight(cfg.intermediate_size, b),
               *(each[kind] for kind in cfg.layer_types))


class _Period(nn.Module):
    """The layer scan's body: one period of unlike blocks."""
    config: OlmoHybridConfig
    policy: Any = None               # the stack's ``stack_remat_policy``

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        for j, kind in enumerate(cfg.layer_types[:cfg.period]):
            x = remat_block(cfg, self, f"l{j}", OlmoHybridBlock,
                            self.policy)(cfg, kind, name=f"l{j}")(x)
        return x, None


class OlmoHybridForCausalLM(nn.Module):
    """Decoder-only LM with an untied head; ``labels`` with ``loss_chunk``
    takes the fused chunked head + loss
    (``models/gpt2.chunked_lm_loss``)."""
    config: OlmoHybridConfig

    layer_stacked_subtree = "layers"

    @nn.compact
    def __call__(self, input_ids, labels=None):
        cfg = self.config
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with annotate("ds_embed"):
            x = _embed_lookup(embed, input_ids).astype(cfg.dtype)
        scanned = nn.scan(
            _Period, variable_axes={"params": 0, "intermediates": 0},
            split_rngs={"params": True},
            length=cfg.num_hidden_layers // cfg.period)
        policy = stack_remat_policy(
            cfg, input_ids.size, cfg.num_hidden_layers, remat_row_bytes(cfg),
            remat_inflight_row_bytes(cfg, input_ids.shape[1]))
        x, _ = scanned(cfg, policy, name="layers")(x)
        x = RMSNorm(eps=cfg.rms_norm_eps, dtype=cfg.dtype,
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


def olmo_hybrid_tiny(**over):
    """Two periods at tiny widths: 2 DeltaNet heads of 8 x 16 (a value head
    twice a key head, as published), 2 attention heads of 16."""
    kw = dict(vocab_size=256, hidden_size=32, intermediate_size=48,
              num_hidden_layers=8, num_attention_heads=2,
              num_key_value_heads=2, max_position_embeddings=256,
              linear_num_key_heads=2, linear_num_value_heads=2,
              linear_key_head_dim=8, linear_value_head_dim=16,
              dtype=jnp.float32, param_dtype=jnp.float32)
    kw.update(over)
    return OlmoHybridConfig(**kw)
