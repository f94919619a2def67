"""SmallThinker — a decoder whose ROUTER sits ahead of the mixer: the expert
logits of a block are read from the block's INPUT (the residual stream
before any norm), the experts from the normed stream after attention.

Per layer, read from the two published lists ``sliding_window_layout`` and
``rope_layout`` (one 0 / 1 a layer) and typed in nowhere else:

- **Attention** (``attn``, ``models/laguna.LagunaAttention``: the same
  module, no output gate): ``num_attention_heads`` query heads over
  ``num_key_value_heads`` KV heads at ``head_dim``, no bias, no QK-norm. A
  layer whose ``sliding_window_layout`` is 1 sees the last
  ``sliding_window_size`` keys (``dot_product_attention(window=...)``: the
  window kernels on a TPU), one whose entry is 0 every key behind it. A
  layer whose ``rope_layout`` is 1 rotates all of q and k (rotate-half,
  ``rope_theta``, no scaling); one whose entry is 0 carries NO position
  encoding — q and k go to the kernel as projected.
- **Experts** (``mlp``, ``moe/dropless.DroplessMoE``): a float32 softmax
  router over ``moe_num_primary_experts`` fed the block's input, the
  ``moe_num_active_primary_experts`` largest renormalised
  (``norm_topk_prob``); ReGLU experts ``relu(h W_g) * (h W_u)`` of width
  ``moe_ffn_hidden_size``; no shared expert; a configuration may hold
  ``experts_held`` of them (one rank's share).

Every layer is pre-norm residual with a plain RMSNorm:
``r = x W_r; x += attn(norm(x)); x += experts(norm(x), router logits r)``.
There is no leading dense layer: the whole depth is a scan over PERIODS of
the lists' repeating pattern (``layers/l<j>``, ``models/laguna.py``'s
layout), each block under ``models/laguna.remat_block``; layers past the
last whole period are a tail outside the scan (``tail_<j>``). The published
52 layers are 13 periods of (full without RoPE, sliding x 3).
"""

import dataclasses
from typing import Any, Optional

import jax.numpy as jnp
import flax.linen as nn

from deepspeed_tpu.models.gpt2 import _embed_lookup, chunked_lm_loss, lm_loss
from deepspeed_tpu.models.laguna import (FULL, SLIDING, LagunaAttention,
                                         attention_inflight_row_bytes,
                                         qkv_row_bytes, remat_block,
                                         rope_tables, stack_remat_policy)
from deepspeed_tpu.models.llama import RMSNorm
from deepspeed_tpu.moe.dropless import (HELD_STAT_GAUGES, STAT_GAUGES,
                                        DroplessMoE)
from deepspeed_tpu.moe.dropless import inflight_row_bytes as moe_inflight
from deepspeed_tpu.moe.dropless import remat_row_bytes as moe_row_bytes
from deepspeed_tpu.telemetry.spans import annotate


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    """Keys under the published config's names. The two per-layer lists are
    required and go in as the config file has them."""
    vocab_size: int = 151936
    hidden_size: int = 2560
    num_hidden_layers: int = 52
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    head_dim: int = 128
    max_position_embeddings: int = 16384
    sliding_window_size: int = 4096
    rope_theta: float = 1.5e6
    # required: one 0 / 1 a layer (1: a window / a rotation)
    sliding_window_layout: Any = dataclasses.field(kw_only=True)
    rope_layout: Any = dataclasses.field(kw_only=True)
    # experts
    moe_num_primary_experts: int = 64
    moe_num_active_primary_experts: int = 6
    moe_ffn_hidden_size: int = 768
    moe_primary_router_apply_softmax: bool = True
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

    def __post_init__(self):
        L = self.num_hidden_layers
        for key in ("sliding_window_layout", "rope_layout"):
            value = tuple(int(v) for v in getattr(self, key))
            assert len(value) == L, \
                f"{key} has {len(value)} entries for {L} layers"
            object.__setattr__(self, key, value)
        assert self.moe_primary_router_apply_softmax, \
            "a router without its softmax is not written here"
        # ``LagunaAttention`` finds a layer's table by its TYPE: a type
        # rotates or it does not
        assert all(self._rotates[t] == r for t, r in zip(
            self.layer_types, self.rope_layout)), \
            "rope_layout differs between layers of one window kind"

    # what ``LagunaAttention`` and ``rope_tables`` read, under their names
    gating = False

    @property
    def sliding_window(self):
        return self.sliding_window_size

    @property
    def layer_types(self):
        return tuple(SLIDING if w else FULL
                     for w in self.sliding_window_layout)

    @property
    def _rotates(self):
        """{layer type: its ``rope_layout`` entry}."""
        return dict(zip(self.layer_types, self.rope_layout))

    def rope_of(self, layer_type):
        """The parameter set of a layer type; None where it does not
        rotate."""
        if not self._rotates[layer_type]:
            return None
        return {"rope_type": "default", "rope_theta": self.rope_theta,
                "partial_rotary_factor": 1.0}

    @property
    def plan(self):
        """(period, n_periods, tail): the shortest ``period`` that carries
        the layers' types (a type says whether it rotates), ``n_periods``
        whole times, ``tail`` layers left over. 52 published layers:
        (4, 13, 0)."""
        kinds = self.layer_types
        period = next(p for p in range(1, len(kinds) + 1) if all(
            kinds[i] == kinds[i % p] for i in range(len(kinds))))
        return period, len(kinds) // period, len(kinds) % period

    def num_params(self):
        """Parameters held here (``experts_held`` experts a layer)."""
        H, D = self.hidden_size, self.head_dim
        held = self.experts_held or self.moe_num_primary_experts
        layer = 2 * H * self.num_attention_heads * D \
            + 2 * H * self.num_key_value_heads * D \
            + H * self.moe_num_primary_experts \
            + 3 * held * H * self.moe_ffn_hidden_size + 2 * H
        return 2 * self.vocab_size * H + H + self.num_hidden_layers * layer


class SmallThinkerBlock(nn.Module):
    config: SmallThinkerConfig
    layer_type: str                  # FULL | SLIDING

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        block_in = x                 # what the router reads: before any norm
        mixed = LagunaAttention(cfg, self.layer_type,
                                cfg.num_attention_heads,
                                name="attn")(norm("input_norm")(x), rope)
        x = x + mixed
        out = DroplessMoE(
            cfg.moe_num_primary_experts, cfg.moe_num_active_primary_experts,
            cfg.moe_ffn_hidden_size, norm_topk_prob=cfg.norm_topk_prob,
            balance_coeff=cfg.router_aux_loss_coef, z_coeff=0.0,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            experts_held=cfg.experts_held, expert_share=cfg.expert_share,
            act="relu",
            # ``remat_block``'s policy saves the router's choice
            pin_choice=cfg.remat, name="mlp")(
            norm("post_attn_norm")(x), router_x=block_in)
        if self.is_mutable_collection("intermediates"):
            # a caller's look at the stream after the mixer and at the two
            # branches (the benchmark's check against its reference)
            self.sow("intermediates", "x_mid", x)
            self.sow("intermediates", "mixer_out", mixed)
            self.sow("intermediates", "ffn_out", out)
        return x + out


def remat_row_bytes(cfg):
    """{checkpoint name: bytes a row, summed over the layers}: what
    ``models/laguna.stack_remat_policy`` weighs against its budget."""
    b = jnp.dtype(cfg.dtype).itemsize
    layer = {"qkv": qkv_row_bytes(cfg, cfg.num_attention_heads),
             "attn_proj": b * cfg.hidden_size,
             **moe_row_bytes(cfg.moe_num_primary_experts, itemsize=b)}
    return {name: cfg.num_hidden_layers * v for name, v in layer.items()}


def remat_inflight_row_bytes(cfg, seq_len):
    """Bytes a row the widest branch of a layer holds between its
    recomputation and the end of its backward: what
    ``models/laguna.stack_remat_policy`` reserves beside the block inputs."""
    return max(
        moe_inflight(cfg.hidden_size, cfg.moe_ffn_hidden_size,
                     cfg.moe_num_active_primary_experts,
                     cfg.moe_num_primary_experts,
                     cfg.experts_held or cfg.moe_num_primary_experts,
                     itemsize=jnp.dtype(cfg.dtype).itemsize),
        *(attention_inflight_row_bytes(
            cfg, cfg.num_attention_heads, seq_len,
            kind == SLIDING and cfg.sliding_window < seq_len)
          for kind in cfg.layer_types))


class _Period(nn.Module):
    """The layer scan's body: one period of unlike blocks."""
    config: SmallThinkerConfig
    policy: Any = None               # the stack's ``stack_remat_policy``

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        for j, kind in enumerate(cfg.layer_types[:cfg.plan[0]]):
            x = remat_block(cfg, self, f"l{j}", SmallThinkerBlock,
                            self.policy)(cfg, kind, name=f"l{j}")(x, rope)
        return x, None


class SmallThinkerForCausalLM(nn.Module):
    """Decoder-only LM with an untied head; ``labels`` with ``loss_chunk``
    takes the fused chunked head + loss (``models/gpt2.chunked_lm_loss``)."""
    config: SmallThinkerConfig

    layer_stacked_subtree = "layers"
    sown_collections = ("losses", "stats")

    @property
    def stat_gauges(self):
        """{variable sown into ``stats``: the gauge it is read under}."""
        return HELD_STAT_GAUGES if self.config.experts_held else STAT_GAUGES

    @nn.compact
    def __call__(self, input_ids, labels=None):
        cfg = self.config
        period, n_periods, tail = cfg.plan
        kinds = cfg.layer_types
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with annotate("ds_embed"):
            x = _embed_lookup(embed, input_ids).astype(cfg.dtype)
        rope = rope_tables(cfg, jnp.arange(input_ids.shape[1]))
        scanned = nn.scan(
            _Period,
            variable_axes={"params": 0, "losses": 0, "stats": 0,
                           "intermediates": 0},
            split_rngs={"params": True}, in_axes=(nn.broadcast,),
            length=n_periods)
        policy = stack_remat_policy(
            cfg, input_ids.size, len(kinds), remat_row_bytes(cfg),
            remat_inflight_row_bytes(cfg, input_ids.shape[1]))
        x, _ = scanned(cfg, policy, name="layers")(x, rope)
        for j in range(tail):
            x = remat_block(cfg, self, f"tail_{j}", SmallThinkerBlock, policy)(
                cfg, kinds[len(kinds) - tail + j], name=f"tail_{j}")(x, rope)
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


def block_paths(cfg):
    """Where layer i's leaves (and sown values) live: a list of (top-level
    key, sub-key or None, scan slice or None) in layer order."""
    period, n_periods, tail = cfg.plan
    return [("layers", "l%d" % j, p) for p in range(n_periods)
            for j in range(period)] \
        + [("tail_%d" % j, None, None) for j in range(tail)]


def smallthinker_tiny(**over):
    """Eight layers (2 x (full without RoPE, sliding x 3)) at tiny widths,
    a KV group of 3 query heads."""
    L = over.get("num_hidden_layers", 8)
    layout = [0 if i % 4 == 0 else 1 for i in range(L)]
    kw = dict(vocab_size=256, hidden_size=64, num_hidden_layers=L,
              num_attention_heads=6, num_key_value_heads=2, head_dim=32,
              max_position_embeddings=256, sliding_window_size=16,
              rope_theta=10000.0, sliding_window_layout=layout,
              rope_layout=layout, moe_num_primary_experts=16,
              moe_num_active_primary_experts=2, moe_ffn_hidden_size=32,
              dtype=jnp.float32, param_dtype=jnp.float32)
    kw.update(over)
    return SmallThinkerConfig(**kw)
