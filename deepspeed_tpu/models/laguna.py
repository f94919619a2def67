"""Laguna — a decoder whose layers differ by POSITION: sliding-window and
full attention layers with unlike query-head counts and RoPE parameters, a
per-head output gate, a leading dense layer and expert layers after it.

Everything per layer is read from the three published lists — ``layer_types``
("full_attention" | "sliding_attention"), ``mlp_layer_types`` ("dense" |
"sparse") and ``num_attention_heads_per_layer`` — and from
``rope_parameters`` (a parameter set a layer type), typed in nowhere else.
Every layer is pre-norm residual with a plain RMSNorm (weight one at
initialisation): ``x += attn(norm(x)); x += ffn(norm(x))``.

- **Attention** (``attn``): ``H_l`` query heads over ``num_key_value_heads``
  KV heads at ``head_dim``; RoPE (split halves) on the first
  ``partial_rotary_factor`` of each head with the layer type's parameters —
  ``default`` or ``yarn`` (``yarn_rope_angles``); causal softmax attention,
  inside ``sliding_window`` keys where the layer is a sliding one
  (``dot_product_attention(window=...)``: the window kernels on a TPU); the
  per-head gate ``sigmoid(h W_g)``, one scalar a head a token, read from the
  block's normed input (``attn_gate``); ``o_proj``. No QK-norm, no bias.
- **Dense FFN** (``dense_mlp``): SwiGLU of width ``intermediate_size``.
- **Sparse FFN** (``mlp``): ``moe/dropless.DroplessMoE`` — softmax router,
  the top-k renormalised and multiplied by ``moe_routed_scaling_factor``
  (on the experts' output), a gated shared expert, and — a configuration's
  to say — only ``experts_held`` of the ``num_experts`` held here.

The layers are grouped by what the lists say (``LagunaConfig.plan``): the
leading layers up to the first sparse one stand alone (``lead_<i>``), each
under its own gather edge and remat; the rest is a scan over PERIODS of the
lists' repeating pattern (``layers/l<j>``: period p's j-th layer is slice p
of those leaves; ``models/qwen3_next._Period`` is the pattern), the blocks
of a period unlike each other in head count, RoPE table and window; what is
left over after the last whole period is a tail outside the scan
(``tail_<j>``). The published 40 layers are 1 + 9 x 4 + 3. The per-type
RoPE tables are computed once a step and broadcast into the scan.
"""

import collections
import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.models.gpt2 import (_embed_lookup, block_remat_policy,
                                       chunked_lm_loss, gather_edge_block,
                                       lm_loss)
from deepspeed_tpu.models.llama import RMSNorm, apply_rope, rope_angles
from deepspeed_tpu.moe.dropless import (HELD_STAT_GAUGES, STAT_GAUGES,
                                        DroplessMoE)
from deepspeed_tpu.moe.dropless import inflight_row_bytes as moe_inflight
from deepspeed_tpu.moe.dropless import remat_row_bytes as moe_row_bytes
from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.ops.pallas.flash_attention import bwd_dq_slab_rows
from deepspeed_tpu.runtime.remat_budget import (attention_inflight,
                                                mlp_inflight)
from deepspeed_tpu.telemetry.spans import annotate

FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"


def _frozen(x):
    """Lists and dicts of a published config as hashable tuples."""
    if isinstance(x, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in x.items()))
    if isinstance(x, (list, tuple)):
        return tuple(_frozen(v) for v in x)
    return x


def yarn_rope_angles(positions, rotary_dim, theta, factor,
                     original_max_position_embeddings, beta_fast=32.0,
                     beta_slow=1.0, attention_factor=None):
    """``rope_angles`` under YaRN scaling (arXiv:2309.00071, as HF's
    ``_compute_yarn_parameters`` has it): [S] positions -> (cos, sin)
    [S, rotary_dim // 2] float32. Each inverse frequency is a blend of the
    plain one, ``theta^(-2i / rotary_dim)``, and that over ``factor``: the
    plain one below the dimension at which ``beta_fast`` rotations fit the
    original context (floored), the scaled one above the dimension at which
    ``beta_slow`` fit (ceiled), a linear ramp between; cos and sin are
    multiplied by ``attention_factor`` (``0.1 ln factor + 1`` where not
    given). ``factor`` 1 is plain RoPE."""
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0

    def correction_dim(rotations):
        return rotary_dim * math.log(original_max_position_embeddings
                                     / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), rotary_dim - 1)
    if low == high:
        high += 0.001
    plain = 1.0 / theta ** (jnp.arange(0, rotary_dim, 2, dtype=jnp.float32)
                            / rotary_dim)
    ramp = jnp.clip((jnp.arange(rotary_dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = plain / factor * ramp + plain * (1.0 - ramp)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang) * attention_factor, jnp.sin(ang) * attention_factor


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """Keys under the published config's names. The three per-layer lists
    and ``rope_parameters`` are required and go in as the config file has
    them (lists, a dict of dicts); they are kept as tuples."""
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48       # published; the per-layer list rules
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    sliding_window: int = 512
    gating: bool = True
    # required, and typed in nowhere in the program: one entry a layer, and
    # {layer type: {rope_type, rope_theta, partial_rotary_factor, ...}}
    layer_types: Any = dataclasses.field(kw_only=True)
    mlp_layer_types: Any = dataclasses.field(kw_only=True)
    num_attention_heads_per_layer: Any = dataclasses.field(kw_only=True)
    rope_parameters: Any = dataclasses.field(kw_only=True)
    # experts
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    moe_routed_scaling_factor: float = 2.5
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
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer", "rope_parameters"):
            object.__setattr__(self, key, _frozen(getattr(self, key)))
        L = self.num_hidden_layers
        for key in ("layer_types", "mlp_layer_types",
                    "num_attention_heads_per_layer"):
            assert len(getattr(self, key)) == L, \
                f"{key} has {len(getattr(self, key))} entries for {L} layers"

    def rope_of(self, layer_type):
        """The published parameter set of a layer type, as a dict."""
        return dict(dict(self.rope_parameters)[layer_type])

    @property
    def layer_kinds(self):
        """(layer type, query heads, FFN type) of every layer."""
        return tuple(zip(self.layer_types,
                         self.num_attention_heads_per_layer,
                         self.mlp_layer_types))

    @property
    def plan(self):
        """(lead, period, n_periods, tail): the leading layers up to the
        first sparse one stand alone; the rest repeats with the shortest
        ``period`` that carries its kinds, ``n_periods`` whole times, and
        ``tail`` layers are left over. 40 published layers: (1, 4, 9, 3)."""
        kinds = self.layer_kinds
        lead = next((i for i, k in enumerate(kinds) if k[2] == SPARSE),
                    len(kinds))
        body = kinds[lead:]
        period = next((p for p in range(1, len(body) + 1) if all(
            body[i] == body[i % p] for i in range(len(body)))), 1)
        return lead, period, len(body) // period, len(body) % period

    def num_params(self):
        """Parameters held here (``experts_held`` experts a sparse layer)."""
        H, D = self.hidden_size, self.head_dim
        kv = self.num_key_value_heads * D
        held = self.experts_held or self.num_experts
        sparse = H * self.num_experts \
            + 3 * held * H * self.moe_intermediate_size \
            + 3 * H * self.shared_expert_intermediate_size + H
        total = 2 * self.vocab_size * H + H
        for _, heads, mlp in self.layer_kinds:
            attn = 2 * H * heads * D + 2 * H * kv \
                + (H * heads if self.gating else 0)
            ffn = 3 * H * self.intermediate_size if mlp == DENSE else sparse
            total += attn + ffn + 2 * H
        return total


def _dense(cfg, n, name):
    return nn.Dense(n, use_bias=False, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype,
                    kernel_init=nn.initializers.normal(0.02), name=name)


def rope_tables(cfg, positions):
    """{layer type: (cos, sin)} for the layer types the config has: each
    type's own rotary width, base and scaling, computed once a step. A type
    whose parameter set is ``None`` carries no position encoding: its table
    is ``None`` and ``LagunaAttention`` leaves q and k as projected."""
    out = {}
    for kind in sorted(set(cfg.layer_types)):
        p = cfg.rope_of(kind)
        if p is None:
            out[kind] = None
            continue
        rot = int(cfg.head_dim * p.get("partial_rotary_factor", 1.0))
        if p.get("rope_type", "default") == "yarn":
            out[kind] = yarn_rope_angles(
                positions, rot, float(p["rope_theta"]), float(p["factor"]),
                p["original_max_position_embeddings"],
                float(p.get("beta_fast", 32.0)),
                float(p.get("beta_slow", 1.0)), p.get("attention_factor"))
        else:
            out[kind] = rope_angles(positions, rot, float(p["rope_theta"]))
    return out


class LagunaAttention(nn.Module):
    """The attention branch of the module docstring. ``config`` is a
    ``LagunaConfig`` or any config with the attributes read here
    (``hidden_size``, ``num_key_value_heads``, ``head_dim``,
    ``sliding_window``, ``gating``, ``use_flash``, ``dtype``,
    ``param_dtype``): ``models/smallthinker.SmallThinkerConfig`` is one.
    ``rope[layer_type]`` ``None``: no rotation (a NoPE layer). ``scale``:
    what the scores are multiplied by ahead of the softmax (None: the
    habit, ``head_dim ** -0.5``; granite-4.0-h's ``attention_multiplier``
    is ``1 / head_dim``)."""
    config: LagunaConfig
    layer_type: str
    heads: int
    scale: Optional[float] = None

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        B, S, _ = x.shape
        H, Hkv, D = self.heads, cfg.num_key_value_heads, cfg.head_dim
        q = _dense(cfg, H * D, "q_proj")(x).reshape(B, S, H, D)
        k = _dense(cfg, Hkv * D, "k_proj")(x).reshape(B, S, Hkv, D)
        v = _dense(cfg, Hkv * D, "v_proj")(x).reshape(B, S, Hkv, D)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))  # [B, H, S, D]
        if rope[self.layer_type] is not None:
            cos, sin = rope[self.layer_type]
            rot = 2 * cos.shape[-1]
            q, k = (apply_rope(t, cos, sin) if rot == D else jnp.concatenate(
                [apply_rope(t[..., :rot], cos, sin), t[..., rot:]], axis=-1)
                for t in (q, k))
        # ``qkv`` names what the backward pass reads, the kernels' operands:
        # kept, neither a projection nor the rotation is run again
        q, k, v = (checkpoint_name(t, "qkv") for t in (q, k, v))
        out = dot_product_attention(
            q, k, v, causal=True, scale=self.scale, use_flash=cfg.use_flash,
            window=cfg.sliding_window if self.layer_type == SLIDING else None)
        out = out.transpose(0, 2, 1, 3)                     # [B, S, H, D]
        if cfg.gating:
            # per HEAD, from the block's normed input (the config says
            # ``gating: true`` and no more: the configuration file's
            # ``assumed`` has the evidence)
            gate = checkpoint_name(_dense(cfg, H, "g_proj")(x), "qkv")
            with annotate("attn_gate"):
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(
                    gate.astype(jnp.float32))[..., None]).astype(cfg.dtype)
        out = _dense(cfg, cfg.hidden_size, "o_proj")(out.reshape(B, S, H * D))
        return checkpoint_name(out, "attn_proj")


class LagunaDenseMLP(nn.Module):
    config: LagunaConfig

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


class LagunaBlock(nn.Module):
    config: LagunaConfig
    layer_type: str                  # FULL | SLIDING
    heads: int
    mlp_type: str                    # DENSE | SPARSE

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.rms_norm_eps, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, name=name)
        mixed = LagunaAttention(cfg, self.layer_type, self.heads,
                                name="attn")(norm("input_norm")(x), rope)
        x = x + mixed
        h = norm("post_attn_norm")(x)
        if self.mlp_type == DENSE:
            out = LagunaDenseMLP(cfg, name="mlp")(h)
        else:
            out = DroplessMoE(
                cfg.num_experts, cfg.num_experts_per_tok,
                cfg.moe_intermediate_size, norm_topk_prob=cfg.norm_topk_prob,
                balance_coeff=cfg.router_aux_loss_coef, z_coeff=0.0,
                dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                experts_held=cfg.experts_held, expert_share=cfg.expert_share,
                shared_d_ff=cfg.shared_expert_intermediate_size,
                routed_scale=cfg.moe_routed_scaling_factor,
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


def qkv_row_bytes(cfg, heads):
    """Bytes a row one ``LagunaAttention`` layer of ``heads`` query heads
    holds under the name ``qkv``: q, k, v as the kernel reads them and the
    gate's projection."""
    return jnp.dtype(cfg.dtype).itemsize * (
        (heads + 2 * cfg.num_key_value_heads) * cfg.head_dim
        + (heads if cfg.gating else 0))


def remat_row_bytes(cfg):
    """{checkpoint name: bytes a row, summed over the layers that carry
    it}: what ``stack_remat_policy`` weighs against its budget."""
    b = jnp.dtype(cfg.dtype).itemsize
    total = collections.Counter()
    for _, heads, mlp in cfg.layer_kinds:
        total.update({"qkv": qkv_row_bytes(cfg, heads),
                      "attn_proj": b * cfg.hidden_size})
        total.update(
            {"mlp_fc": 2 * b * cfg.intermediate_size} if mlp == DENSE
            else moe_row_bytes(
                cfg.num_experts, cfg.shared_expert_intermediate_size,
                itemsize=b))
    return total


def attention_inflight_row_bytes(cfg, heads, seq_len, windowed=False):
    """Bytes a row the backward of one ``LagunaAttention`` layer of
    ``heads`` query heads holds in flight over ``seq_len`` rows
    (``runtime/remat_budget.attention_inflight``); ``windowed``: a layer
    whose window is shorter than the sequence runs the window kernels,
    which leave no float32 dq partials."""
    b = jnp.dtype(cfg.dtype).itemsize
    D = cfg.head_dim
    return attention_inflight(
        heads * D, heads * D, 2 * cfg.num_key_value_heads * D, b,
        0.0 if windowed else bwd_dq_slab_rows(seq_len, D, D, b),
        gated=cfg.gating)


def remat_inflight_row_bytes(cfg, seq_len):
    """Bytes a row the widest branch of the widest layer holds between its
    recomputation and the end of its backward: what ``stack_remat_policy``
    reserves beside the block inputs."""
    b = jnp.dtype(cfg.dtype).itemsize
    return max(max(
        attention_inflight_row_bytes(
            cfg, heads, seq_len,
            kind == SLIDING and cfg.sliding_window < seq_len),
        mlp_inflight(cfg.intermediate_size, b) if mlp == DENSE
        else moe_inflight(
            cfg.hidden_size, cfg.moe_intermediate_size,
            cfg.num_experts_per_tok, cfg.num_experts,
            cfg.experts_held or cfg.num_experts,
            cfg.shared_expert_intermediate_size, itemsize=b))
        for kind, heads, mlp in cfg.layer_kinds)


def stack_remat_policy(cfg, rows, layers, row_bytes, inflight_row_bytes,
                       streams=1):
    """The ONE policy object a stack's rematted blocks share (None without
    remat): ``models/gpt2.block_remat_policy`` over the stack's figures —
    ``rows`` in flight through ``layers`` blocks whose input is ``streams``
    residual streams of ``cfg.hidden_size``, ``row_bytes`` a model's
    ``remat_row_bytes`` and ``inflight_row_bytes`` its
    ``remat_inflight_row_bytes``. One object, so a name is kept for all its
    layers or none, and JAX makes one copy of a ``jax.jit`` function the
    blocks call (it keys that on the policy OBJECT: PERF.md Findings PR
    58)."""
    if not cfg.remat:
        return None
    return block_remat_policy(
        cfg.remat_policy, rows=rows, hidden=cfg.hidden_size, layers=layers,
        itemsize=jnp.dtype(cfg.dtype).itemsize, row_bytes=row_bytes,
        inflight_row_bytes=inflight_row_bytes, streams=streams)


def remat_block(cfg, parent, name, block=None, policy=None):
    """``block`` (``LagunaBlock`` where not given; another model's block of
    the same calling convention: ``models/smallthinker.py``) under its own
    ZeRO-3 gather edge (innermost) and, where the config asks, its own remat
    under ``policy``, a stack's ``stack_remat_policy`` (None: the base names
    of ``models/gpt2.block_remat_policy``, this block's own object);
    ``prevent_cse`` because several rematted blocks share one scan body and
    a scan of ONE period is no loop once XLA has simplified it
    (``models/qwen3_next._Period``)."""
    block = gather_edge_block(block or LagunaBlock, parent, name)
    if cfg.remat:
        policy = policy or block_remat_policy(cfg.remat_policy)
        block = nn.remat(block, prevent_cse=True, policy=policy)
    return block


class _Period(nn.Module):
    """The layer scan's body: one period of unlike blocks."""
    config: LagunaConfig
    policy: Any = None               # the stack's ``stack_remat_policy``

    @nn.compact
    def __call__(self, x, rope):
        cfg = self.config
        lead, period, _, _ = cfg.plan
        for j, kind in enumerate(cfg.layer_kinds[lead:lead + period]):
            x = remat_block(cfg, self, f"l{j}", policy=self.policy)(
                cfg, *kind, name=f"l{j}")(x, rope)
        return x, None


class LagunaForCausalLM(nn.Module):
    """Decoder-only LM; ``labels`` with ``loss_chunk`` takes the fused
    chunked head + loss (``models/gpt2.chunked_lm_loss``)."""
    config: LagunaConfig

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
        lead, period, n_periods, tail = cfg.plan
        kinds = cfg.layer_kinds
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        with annotate("ds_embed"):
            x = _embed_lookup(embed, input_ids).astype(cfg.dtype)
        rope = rope_tables(cfg, jnp.arange(input_ids.shape[1]))
        policy = stack_remat_policy(
            cfg, input_ids.size, len(kinds), remat_row_bytes(cfg),
            remat_inflight_row_bytes(cfg, input_ids.shape[1]))
        for i in range(lead):
            x = remat_block(cfg, self, f"lead_{i}", policy=policy)(
                cfg, *kinds[i], name=f"lead_{i}")(x, rope)
        if n_periods:
            scanned = nn.scan(
                _Period,
                variable_axes={"params": 0, "losses": 0, "stats": 0,
                               "intermediates": 0},
                split_rngs={"params": True}, in_axes=(nn.broadcast,),
                length=n_periods)
            x, _ = scanned(cfg, policy, name="layers")(x, rope)
        for j in range(tail):
            x = remat_block(cfg, self, f"tail_{j}", policy=policy)(
                cfg, *kinds[len(kinds) - tail + j], name=f"tail_{j}")(x, rope)
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
    lead, period, n_periods, tail = cfg.plan
    return [("lead_%d" % i, None, None) for i in range(lead)] \
        + [("layers", "l%d" % j, p) for p in range(n_periods)
           for j in range(period)] \
        + [("tail_%d" % j, None, None) for j in range(tail)]


def laguna_tiny(**over):
    """Nine layers (1 + 2 x 4) at tiny widths, head counts 3 / 4."""
    L = over.get("num_hidden_layers", 9)
    kw = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
              num_hidden_layers=L, num_key_value_heads=1, head_dim=32,
              max_position_embeddings=256, sliding_window=16,
              layer_types=[FULL if i % 4 == 0 else SLIDING
                           for i in range(L)],
              mlp_layer_types=[DENSE if i == 0 else SPARSE
                               for i in range(L)],
              num_attention_heads_per_layer=[3 if i % 4 == 0 else 4
                                             for i in range(L)],
              rope_parameters={
                  FULL: {"rope_theta": 500000, "rope_type": "yarn",
                         "factor": 8, "original_max_position_embeddings": 32,
                         "beta_slow": 1, "beta_fast": 4,
                         "partial_rotary_factor": 0.5},
                  SLIDING: {"rope_type": "default", "rope_theta": 10000,
                            "partial_rotary_factor": 1}},
              num_experts=16, num_experts_per_tok=2, moe_intermediate_size=32,
              shared_expert_intermediate_size=32, dtype=jnp.float32,
              param_dtype=jnp.float32)
    kw.update(over)
    return LagunaConfig(**kw)
