"""LLaMA family — RoPE + RMSNorm + SwiGLU + grouped-query attention,
TPU-first.

The reference serves/trains LLaMA through HF + module injection
(deepspeed/module_inject/containers/llama.py); here the family is
in-tree flax with the same TPU design as the GPT-2 flagship
(models/gpt2.py): bf16 activations over fp32 masters, `nn.scan` layers,
remat with the SAME named-residual policies ("qkv"/"attn_proj"/
"mlp_fc"/"mlp_proj" + the flash kernel's "flash_o"/"flash_lse" — so
every GPT2Config remat_policy string works unchanged), Pallas flash
attention, fused chunked head+loss, and sequence parallelism over a
live mesh seq axis (ring or Ulysses).

GQA: ``n_kv_heads < n_heads`` stores/computes K/V (and their decode
caches) at the reduced head count; the projections, optimizer state and
cache memory all shrink by H/Hkv. The flash FORWARD (training and
prefill) consumes the reduced-head K/V directly — Hkv-aware block index
maps fold each query head onto its KV head, so full-head K/V is never
materialized in HBM on the forward path. The flash BACKWARD still
repeats K/V transiently (bwd-only) and sums dk/dv over the rep query
heads; a dk/dv-accumulating GQA backward kernel is the remaining
optimization. SP backends (ring/Ulysses) rotate K/V at full head count.
"""

import dataclasses
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn

from jax.ad_checkpoint import checkpoint_name

from deepspeed_tpu.ops.attention import dot_product_attention
from deepspeed_tpu.models.gpt2 import (_embed_lookup, _remat_policy,
                                       block_remat_policy, chunked_lm_loss,
                                       gather_edge_block, lm_loss)
from deepspeed_tpu.telemetry.spans import annotate


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 0              # 0 → MHA (= n_heads); <n_heads → GQA
    max_seq_len: int = 2048
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    remat_policy: Optional[str] = None
    scan_layers: bool = True
    scan_unroll: int = 1
    sp_backend: str = "ring"         # mesh seq-axis attention backend
    use_flash: Optional[bool] = None
    loss_chunk: int = 0              # fused chunked head+loss (see gpt2)
    # What OLMoE's published config has and LLaMA's lacks, under its key
    # names; every default is LLaMA's behaviour.
    num_experts: int = 0             # >0 → the FFN is a dropless MoE of
    #                                  num_experts SwiGLU experts, each
    #                                  intermediate_size wide (moe/dropless)
    num_experts_per_tok: int = 0     # experts a token is routed to
    norm_topk_prob: bool = False     # renormalise the top-k probabilities
    qk_norm: Any = False             # True: RMSNorm over the whole q and k
    #                                  projections, before heads and RoPE
    #                                  (OLMoE); "head": one RMSNorm a HEAD,
    #                                  a weight of head_dim shared by the
    #                                  heads, before RoPE
    router_aux_loss_coef: float = 0.01   # load balancing, E·Σ f_e·P_e
    router_z_loss_coef: float = 0.001    # mean logsumexp(router logits)²
    head_width: int = 0              # the published ``head_dim`` where it is
    #                                  a key of its own; 0 → hidden / heads
    experts_held: int = 0            # >0 → this rank holds that many of the
    expert_share: int = 0            #   num_experts, the share-th such group
    #                                  (moe/dropless.DroplessMoE)
    # Block-diffusion training (BD3-LMs, arXiv 2503.09573): 0 → next-token
    # training, every program as before. >0 → a forward with ``labels``
    # noises the sequence block by block (``block_diffusion_noise``), runs
    # the layers over [noised, clean] rows under the block-diffusion mask
    # and scores the noised half against the tokens themselves, weighted 1/t
    block_length: int = 0
    mask_token_id: int = 0           # the id a noised position carries
    noise_eps: float = 1e-3          # t = eps + (1 - eps) u, a block

    def __post_init__(self):
        if self.block_length > 0 and self.loss_chunk <= 0:
            # the engine hands ``labels`` to a model only with the fused head
            # (``runtime/engine.default_loss``): without it the step would be
            # a causal next-token one, silently
            raise ValueError("block_length > 0 (block-diffusion training) "
                             "needs the chunked head: set loss_chunk > 0")

    @property
    def kv_heads(self):
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self):
        return self.head_width or self.hidden_size // self.n_heads

    def num_params(self):
        E, F, L, V = (self.hidden_size, self.intermediate_size,
                      self.n_layers, self.vocab_size)
        Dq, Dkv = (h * self.head_dim for h in (self.n_heads, self.kv_heads))
        ffn = 3 * E * F
        if self.num_experts:
            ffn = (self.experts_held or self.num_experts) * ffn \
                + E * self.num_experts                            # + router
        per_layer = 2 * E * Dq + 2 * E * Dkv + ffn + 2 * E
        if self.qk_norm == "head":
            per_layer += 2 * self.head_dim
        elif self.qk_norm:
            per_layer += Dq + Dkv
        return 2 * V * E + L * per_layer + E


class RMSNorm(nn.Module):
    eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones,
                       (x.shape[-1],), self.param_dtype)
        xf = x.astype(jnp.float32)
        n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        return (n * w.astype(jnp.float32)).astype(self.dtype)


def rope_angles(positions, head_dim, theta):
    """[S] positions → (cos, sin) [S, head_dim//2] fp32."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """Rotary embedding on [B, H, S, D] (split-halves convention — the
    same rotation HF's LLaMA applies; conversion from the interleaved
    convention is folded into weight import)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, None].astype(x.dtype)
    s = sin[None, None].astype(x.dtype)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


class LlamaAttention(nn.Module):
    config: LlamaConfig
    max_out_tokens: int = 0      # >0 → serving mode with a KV cache
    diffusion: bool = False      # the rows are [noised, clean] halves of a
    #                              block-diffusion step: its mask, not causal

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        B, S, E = x.shape
        H, Hkv, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02), name=name)
        q = dense(H * D, "q_proj")(x)
        k = dense(Hkv * D, "k_proj")(x)
        v = dense(Hkv * D, "v_proj")(x)
        # all three projections carry the 'qkv' tag so every GPT2Config
        # remat_policy string (which saves 'qkv' residuals) works
        # unchanged on this model
        if cfg.qk_norm:
            # OLMoE: one RMSNorm with a learned weight over the WHOLE q
            # projection and one over the whole k, not per head; "head":
            # over each head's head_dim, one weight for all heads
            with annotate("qk_norm"):
                norm = lambda name: RMSNorm(  # noqa: E731
                    eps=cfg.rms_eps, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)
                if cfg.qk_norm == "head":
                    q = norm("q_norm")(q.reshape(B, S, H, D)).reshape(q.shape)
                    k = norm("k_norm")(k.reshape(B, S, Hkv, D)) \
                        .reshape(k.shape)
                else:
                    q = norm("q_norm")(q)
                    k = norm("k_norm")(k)
        q = checkpoint_name(q, "qkv")
        k = checkpoint_name(k, "qkv")
        v = checkpoint_name(v, "qkv")
        qh = q.reshape(B, S, H, D).transpose(0, 2, 1, 3)
        kh = k.reshape(B, S, Hkv, D).transpose(0, 2, 1, 3)
        vh = v.reshape(B, S, Hkv, D).transpose(0, 2, 1, 3)
        cos, sin = rope_angles(positions, D, cfg.rope_theta)
        qh = apply_rope(qh, cos, sin)
        kh = apply_rope(kh, cos, sin)

        use_cache = self.max_out_tokens > 0 and (
            self.has_variable("cache", "cached_key")
            or self.is_mutable_collection("cache"))
        if use_cache:
            # serving: append RoPE'd K/V to the head-major cache and
            # attend to the filled prefix (same layout/overflow contract
            # as the fused GPT-2 stack, ops/transformer/inference.py)
            L = self.max_out_tokens
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               (B, Hkv, L, D), kh.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               (B, Hkv, L, D), vh.dtype)
            idx = self.variable("cache", "cache_index",
                                lambda: jnp.zeros((), jnp.int32))
            start = idx.value
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, kh, (0, 0, start, 0))
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, vh, (0, 0, start, 0))
            idx.value = start + S
            overflow = (start + S) > L
            qh = jnp.where(overflow,
                           jnp.float32(jnp.nan).astype(qh.dtype), qh)
            # GQA without materializing a repeated cache: fold the
            # rep = H/Hkv query heads sharing each KV head into the
            # contraction's row dim (q heads are grouped consecutively
            # per KV head, so this is a pure reshape) — the decode loop
            # reads the Hkv-head cache directly instead of rep x the
            # bytes every token
            rep = H // Hkv
            qg = qh.reshape(B, Hkv, rep * S, D)
            q_pos = start + jnp.arange(S)[:, None]
            visible = jnp.arange(L)[None, :] <= q_pos        # [S, L]
            vis_g = jnp.broadcast_to(visible[None],
                                     (rep, S, L)).reshape(rep * S, L)
            dn_qk = (((3,), (3,)), ((0, 1), (0, 1)))
            scores = jax.lax.dot_general(
                qg, ck.value, dn_qk).astype(jnp.float32) / np.sqrt(D)
            scores = jnp.where(vis_g[None, None], scores,
                               jnp.float32(-1e30))
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = jax.lax.dot_general(
                probs.astype(qh.dtype), cv.value,
                (((3,), (2,)), ((0, 1), (0, 1))))           # [B,Hkv,rS,D]
            ctx = ctx.reshape(B, H, S, D)
            out = ctx.transpose(0, 2, 1, 3).reshape(B, S, H * D)
            return dense(E, "o_proj")(out)

        from deepspeed_tpu.parallel import mesh as mesh_lib
        mesh = mesh_lib.current_mesh()
        if self.diffusion:
            from deepspeed_tpu.ops.attention import block_diffusion_attention
            out = block_diffusion_attention(qh, kh, vh, cfg.block_length,
                                            use_flash=cfg.use_flash)
        elif mesh is not None and mesh.shape.get(mesh_lib.SEQ_AXIS, 1) > 1 \
                and S % mesh.shape[mesh_lib.SEQ_AXIS] == 0:
            # the SP backends shard/rotate K/V across the seq axis at
            # full head count — repeat for them only
            if Hkv != H:
                rep = H // Hkv
                kh = jnp.repeat(kh, rep, axis=1)
                vh = jnp.repeat(vh, rep, axis=1)
            sp = mesh.shape[mesh_lib.SEQ_AXIS]
            if cfg.sp_backend == "ulysses" and H % sp == 0:
                from deepspeed_tpu.parallel.ulysses import ulysses_attention
                out = ulysses_attention(qh, kh, vh, mesh, causal=True)
            else:
                from deepspeed_tpu.parallel.ring_attention import \
                    ring_attention
                out = ring_attention(qh, kh, vh, mesh, causal=True)
        else:
            # GQA K/V go in at Hkv heads: the flash kernel's Hkv-aware
            # block maps stream the reduced cache — no full-head
            # materialization in the forward (module docstring promise)
            out = dot_product_attention(qh, kh, vh, causal=True,
                                        use_flash=cfg.use_flash)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        out = dense(E, "o_proj")(out)
        return checkpoint_name(out, "attn_proj")


class LlamaMLP(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.config
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02), name=name)
        gate = dense(cfg.intermediate_size, "gate_proj")(x)
        up = dense(cfg.intermediate_size, "up_proj")(x)
        h = checkpoint_name(nn.silu(gate) * up, "mlp_fc")
        out = dense(cfg.hidden_size, "down_proj")(h)
        return checkpoint_name(out, "mlp_proj")


class LlamaBlock(nn.Module):
    config: LlamaConfig
    max_out_tokens: int = 0
    diffusion: bool = False

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        norm = lambda name: RMSNorm(  # noqa: E731
            eps=cfg.rms_eps, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name=name)
        attn = _attn_cls(cfg)(cfg, self.max_out_tokens, self.diffusion,
                              name="attn")(norm("input_norm")(x), positions)
        x = x + attn
        if cfg.num_experts:
            from deepspeed_tpu.moe.dropless import DroplessMoE
            ffn = DroplessMoE(
                cfg.num_experts, cfg.num_experts_per_tok,
                cfg.intermediate_size, norm_topk_prob=cfg.norm_topk_prob,
                balance_coeff=cfg.router_aux_loss_coef,
                z_coeff=cfg.router_z_loss_coef, dtype=cfg.dtype,
                param_dtype=cfg.param_dtype, experts_held=cfg.experts_held,
                expert_share=cfg.expert_share,
                # the "block" remat policy saves the router's choice
                pin_choice=cfg.remat and cfg.remat_policy == "block",
                name="mlp")
        else:
            ffn = LlamaMLP(cfg, name="mlp")
        out = ffn(norm("post_attn_norm")(x))
        if self.is_mutable_collection("intermediates"):
            # a caller's look at the two branches (the benchmark's check
            # against its reference); nothing in a training step
            self.sow("intermediates", "attn_out", attn)
            self.sow("intermediates", "ffn_out", out)
        return x + out


def _maybe_remat(cfg, parent, name, x):
    """The block class for the child ``name`` of ``parent``: the ZeRO-3
    gather edge innermost (models/gpt2.gather_edge_block), remat round
    it."""
    block = gather_edge_block(LlamaBlock, parent, name)
    if not cfg.remat:
        return block
    # "block": what ``models/gpt2.block_remat_policy`` keeps (the router's
    # choice, the attention kernel's output and log-sum-exp), nothing else
    policy = block_remat_policy() if cfg.remat_policy == "block" \
        else _remat_policy(cfg.remat_policy)
    return nn.remat(block, prevent_cse=False, policy=_pinned(cfg, policy, x))


class _ScanBody(nn.Module):
    config: LlamaConfig
    max_out_tokens: int = 0
    diffusion: bool = False

    @nn.compact
    def __call__(self, x, positions):
        block = _maybe_remat(self.config, self, "blk", x)
        return block(self.config, self.max_out_tokens, self.diffusion,
                     name="blk")(x, positions), None


# what a block-diffusion forward sows into ``stats``, and the gauges
DIFFUSION_STAT_GAUGES = {"diffusion_masked_share": "diffusion/masked_share",
                         "diffusion_weight_max": "diffusion/weight_max"}


def block_diffusion_noise(key, input_ids, block_length, eps, mask_token_id):
    """(noisy_ids [B, L] int, masked [B, L] bool, t_row [B, L] float32) of
    ``input_ids`` [B, L], L a whole number of blocks of ``block_length``: the
    forward process of block-diffusion training (BD3-LMs, arXiv 2503.09573;
    linear schedule, one noise level a BLOCK). A pure function of ``key``, in
    this order: ``key_t, key_v = split(key)``; ``u`` uniform [B, L /
    block_length) from ``key_t`` and ``t = eps + (1 - eps) u``, a block;
    ``v`` uniform [B, L) from ``key_v``; position i is masked where ``v_i <
    t`` of its block and then carries ``mask_token_id``. ``t_row`` is each
    position's own block's t (the loss weighs a masked row by 1 / t)."""
    B, L = input_ids.shape
    if L % block_length:
        raise ValueError(f"a sequence of {L} tokens is no whole number of "
                         f"diffusion blocks of {block_length}")
    key_t, key_v = jax.random.split(key)
    u = jax.random.uniform(key_t, (B, L // block_length), jnp.float32)
    t_row = jnp.repeat(eps + (1.0 - eps) * u, block_length, axis=1)
    masked = jax.random.uniform(key_v, (B, L), jnp.float32) < t_row
    noisy = jnp.where(masked, jnp.asarray(mask_token_id, input_ids.dtype),
                      input_ids)
    return noisy, masked, t_row


class LlamaForCausalLM(nn.Module):
    """Decoder-only LLaMA LM. ``labels`` triggers the fused chunked
    head+loss (models/gpt2.chunked_lm_loss works for any untied head via
    the lm_head kernel). With ``config.block_length`` > 0 a forward with
    ``labels`` is a block-diffusion training step (``LlamaConfig``) and
    draws its noise from the rng stream ``diffusion``."""
    config: LlamaConfig
    max_out_tokens: int = 0      # >0 → serving mode (KV caches)

    @property
    def layer_stacked_subtree(self):
        """Top-level params key whose leaves are layer-stacked, or None
        with unrolled layers (see GPT2LMHeadModel)."""
        return "layers" if self.config.scan_layers else None

    @property
    def sown_collections(self):
        """Collections a training forward sows into, for the engine to
        make mutable: ``losses`` (terms it adds to the objective as they
        are — the MoE router's two, already weighted) and ``stats``
        (scalars it carries out of the step and folds into the gauges
        ``stat_gauges`` names). None without experts."""
        return ("losses", "stats") if self.config.num_experts \
            else ("stats",) if self.config.block_length else ()

    @property
    def stat_gauges(self):
        """{variable sown into ``stats``: the gauge it is read under}."""
        from deepspeed_tpu.moe.dropless import HELD_STAT_GAUGES, STAT_GAUGES
        cfg = self.config
        gauges = _with_dsa_gauges(cfg, {} if not cfg.num_experts else (
            HELD_STAT_GAUGES if cfg.experts_held else STAT_GAUGES))
        return dict(gauges, **DIFFUSION_STAT_GAUGES) if cfg.block_length \
            else gauges

    @property
    def stat_maxima(self):
        """The ``stats`` folded as the largest value sown, not the mean."""
        return ("diffusion_weight_max",) if self.config.block_length else ()

    @property
    def rng_streams(self):
        """The rng streams a training forward draws from: the engine hands
        the step's key under each (``runtime/engine.py``)."""
        return ("diffusion",) if self.config.block_length else ()

    @nn.compact
    def __call__(self, input_ids, labels=None, deterministic=True,
                 keep_prob=1.0, position_offset=0, positions=None):
        cfg = self.config
        B, S = input_ids.shape
        embed = self.param("embed_tokens", nn.initializers.normal(0.02),
                           (cfg.vocab_size, cfg.hidden_size),
                           cfg.param_dtype)
        diffusion = cfg.block_length > 0 and labels is not None
        if diffusion:
            # the rows of every layer: the noised sequence, then the clean
            # one, each at positions 0 .. S-1
            with annotate("bd_noise"):
                noisy, masked, t_row = block_diffusion_noise(
                    self.make_rng("diffusion"), input_ids, cfg.block_length,
                    cfg.noise_eps, cfg.mask_token_id)
                input_ids = jnp.concatenate([noisy, input_ids], axis=1)
                weights = jnp.where(masked, 1.0 / t_row, 0.0)
            self.sow("stats", "diffusion_masked_share",
                     jnp.mean(masked.astype(jnp.float32)))
            self.sow("stats", "diffusion_weight_max", jnp.max(weights))
            if self.is_mutable_collection("intermediates"):
                self.sow("intermediates", "bd_noise", (noisy, masked, t_row))
        with annotate("ds_embed"):
            x = _embed_lookup(embed, input_ids).astype(cfg.dtype)
        positions = _positions(positions, position_offset, S)
        if diffusion:
            positions = jnp.concatenate([positions, positions])

        if cfg.scan_layers:
            axes = {"params": 0, "cache": 0}
            if cfg.num_experts:
                axes.update(losses=0, stats=0, intermediates=0)
            scanned = nn.scan(_ScanBody,
                              variable_axes=axes,
                              split_rngs={"params": True},
                              in_axes=(nn.broadcast,),
                              length=cfg.n_layers,
                              unroll=max(1, cfg.scan_unroll))
            x, _ = scanned(cfg, self.max_out_tokens, diffusion,
                           name="layers")(x, positions)
        else:
            for i in range(cfg.n_layers):
                block = _maybe_remat(cfg, self, f"layers_{i}", x)
                x = block(cfg, self.max_out_tokens, diffusion,
                          name=f"layers_{i}")(x, positions)

        if diffusion:
            with annotate("bd_noise"):
                x = x[:, :S]              # the head reads the noised half
        x = RMSNorm(eps=cfg.rms_eps, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name="norm")(x)
        head = self.param("lm_head", nn.initializers.normal(0.02),
                          (cfg.vocab_size, cfg.hidden_size),
                          cfg.param_dtype)
        if diffusion:
            # row i of the noised half predicts token i itself: no shift, a
            # masked row weighs 1 / t of its block, the sum over B x S tokens
            return chunked_lm_loss(x, head.astype(cfg.dtype), labels,
                                   cfg.loss_chunk, offset=0, weights=weights,
                                   normalizer=B * S)
        if labels is not None and cfg.loss_chunk > 0:
            return chunked_lm_loss(x, head.astype(cfg.dtype), labels,
                                   cfg.loss_chunk)
        logits = jnp.einsum("bse,ve->bsv", x, head.astype(cfg.dtype))
        if labels is not None:
            return lm_loss(logits, labels)
        return logits


# ------------------------------------------------------------- serving

import functools as _ft

_LLAMA_STEP_CACHE = {}


def _llama_compiled_steps(cfg: LlamaConfig, max_out: int):
    """(prompt_pass, decode_scan) jitted once per (config, cache length)
    — the same serving shape as models/gpt2_inference._compiled_steps."""
    key = (cfg, max_out)
    if key not in _LLAMA_STEP_CACHE:
        model = LlamaForCausalLM(cfg, max_out_tokens=max_out)

        @jax.jit
        def prompt_pass(p, ids):
            logits, vars_ = model.apply({"params": p}, ids,
                                        mutable=["cache"])
            return logits[:, -1], vars_["cache"]

        @_ft.partial(jax.jit, static_argnums=(5,), donate_argnums=(1,))
        def decode_scan(p, cache, first_tok, start, rngs, steps,
                        temperature):
            def tick(carry, r):
                cache, tok, offset = carry
                logits, vars_ = model.apply(
                    {"params": p, "cache": cache}, tok[:, None],
                    position_offset=offset, mutable=["cache"])
                logits = logits[:, -1]
                nxt = jax.lax.cond(
                    temperature > 0,
                    lambda: jax.random.categorical(
                        r, logits / jnp.maximum(temperature, 1e-6),
                        axis=-1),
                    lambda: jnp.argmax(logits, axis=-1))
                return (vars_["cache"], nxt, offset + 1), tok
            (final_cache, last, _), toks = jax.lax.scan(
                tick, (cache, first_tok, start), rngs, length=steps)
            # final cache returned so the donated input aliases an output
            # (otherwise every tick copies the caches — see
            # gpt2_inference.decode_scan)
            return jnp.concatenate(
                [toks.transpose(1, 0), last[:, None]], axis=1), final_cache

        _LLAMA_STEP_CACHE[key] = (prompt_pass, decode_scan)
    return _LLAMA_STEP_CACHE[key]


def llama_generate(cfg: LlamaConfig, params, input_ids, max_new_tokens=20,
                   temperature: float = 0.0, rng=None,
                   max_out_tokens: int = 0):
    """KV-cache generation for the LLaMA family — same contract as
    models/gpt2_inference.generate: prompt pass fills the caches, the
    whole decode loop is ONE compiled lax.scan program, temperature 0 is
    greedy. RoPE positions are absolute (position_offset), so cached
    decode matches a full re-forward exactly."""
    input_ids = jnp.asarray(input_ids)
    if max_new_tokens <= 0:
        return input_ids
    B, S = input_ids.shape
    total = S + max_new_tokens
    max_out = max_out_tokens or cfg.max_seq_len
    assert total <= max_out, (total, max_out)
    prompt_pass, decode_scan = _llama_compiled_steps(cfg, max_out)
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    logits, cache = prompt_pass(params, input_ids)
    rng, sub = jax.random.split(rng)
    if temperature and temperature > 0:
        first = jax.random.categorical(sub, logits / temperature, axis=-1)
    else:
        first = jnp.argmax(logits, axis=-1)
    if max_new_tokens == 1:
        return jnp.concatenate([input_ids, first[:, None]], axis=1)
    new, _ = decode_scan(params, cache, first, jnp.asarray(S, jnp.int32),
                         jax.random.split(rng, max_new_tokens - 1),
                         max_new_tokens - 1,
                         jnp.float32(temperature or 0.0))
    return jnp.concatenate([input_ids, new], axis=1)


# ------------------------------------------------------------- TP rules

def _llama_leaf_spec(path_names, shape):
    """Megatron-style TP: q/k/v/gate/up column-parallel, o/down
    row-parallel, embeddings + head vocab-parallel, norms replicated."""
    from jax.sharding import PartitionSpec as P
    from deepspeed_tpu.parallel.mesh import MODEL_AXIS
    name = path_names[-1]
    parent = path_names[-2] if len(path_names) >= 2 else ""
    ndim = len(shape)

    def spec_dim(d, axis_name):
        s = [None] * ndim
        s[d] = axis_name
        return P(*s)

    if name in ("embed_tokens", "lm_head"):
        return spec_dim(0, MODEL_AXIS)
    if parent in ("q_proj", "k_proj", "v_proj", "gate_proj", "up_proj") \
            and name == "kernel":
        return spec_dim(ndim - 1, MODEL_AXIS)
    if parent in ("o_proj", "down_proj") and name == "kernel":
        return spec_dim(ndim - 2, MODEL_AXIS)
    return P(*([None] * ndim))


def register_llama_tp_rules():
    from deepspeed_tpu.models.sharding import register_tp_rules
    register_tp_rules("LlamaForCausalLM", _llama_leaf_spec)


register_llama_tp_rules()


# ------------------------------------------------------------- presets

def llama_tiny(**over):
    kw = dict(vocab_size=512, hidden_size=128, intermediate_size=352,
              n_layers=2, n_heads=4, n_kv_heads=2, max_seq_len=128,
              dtype=jnp.float32, param_dtype=jnp.float32)
    kw.update(over)
    return LlamaConfig(**kw)


def llama_7b(**over):
    kw = dict(vocab_size=32000, hidden_size=4096, intermediate_size=11008,
              n_layers=32, n_heads=32, max_seq_len=2048)
    kw.update(over)
    return LlamaConfig(**kw)


def olmoe_1b_7b(**over):
    """OLMoE-1B-7B (allenai/OLMoE-1B-7B-0125-Instruct): 16 layers of MHA
    at head_dim 128 with QK-norm and a dropless top-8 of 64 experts of
    width 1024, untied head; 6.9B parameters, 1.3B active a token."""
    kw = dict(vocab_size=50304, hidden_size=2048, intermediate_size=1024,
              n_layers=16, n_heads=16, max_seq_len=4096, rope_theta=10000.0,
              rms_eps=1e-5, num_experts=64, num_experts_per_tok=8,
              norm_topk_prob=False, qk_norm=True,
              router_aux_loss_coef=0.01, router_z_loss_coef=0.001)
    kw.update(over)
    return LlamaConfig(**kw)


def llama3_8b(**over):
    kw = dict(vocab_size=128256, hidden_size=4096,
              intermediate_size=14336, n_layers=32, n_heads=32,
              n_kv_heads=8, max_seq_len=8192, rope_theta=500000.0)
    kw.update(over)
    return LlamaConfig(**kw)


# ------------------------------------------------------------- HF import

def from_hf_llama(hf_model, cfg: LlamaConfig, scan_layers=True):
    """transformers LlamaForCausalLM → this model's param tree. The HF
    checkpoint uses the same split-halves RoPE convention, so weights map
    1:1 (transpose only)."""
    sd = {k: np.asarray(v.detach().cpu().numpy() if hasattr(v, "detach")
                        else v) for k, v in hf_model.state_dict().items()}

    def lin(name):
        return sd[name].T.astype(np.float32)

    layers = []
    for i in range(cfg.n_layers):
        p = f"model.layers.{i}."
        layers.append({
            "attn": {
                "q_proj": {"kernel": lin(p + "self_attn.q_proj.weight")},
                "k_proj": {"kernel": lin(p + "self_attn.k_proj.weight")},
                "v_proj": {"kernel": lin(p + "self_attn.v_proj.weight")},
                "o_proj": {"kernel": lin(p + "self_attn.o_proj.weight")},
            },
            "mlp": {
                "gate_proj": {"kernel": lin(p + "mlp.gate_proj.weight")},
                "up_proj": {"kernel": lin(p + "mlp.up_proj.weight")},
                "down_proj": {"kernel": lin(p + "mlp.down_proj.weight")},
            },
            "input_norm": {
                "scale": sd[p + "input_layernorm.weight"]
                .astype(np.float32)},
            "post_attn_norm": {
                "scale": sd[p + "post_attention_layernorm.weight"]
                .astype(np.float32)},
        })
    if scan_layers:
        stacked = jax.tree_util.tree_map(
            lambda *xs: jnp.stack([jnp.asarray(x) for x in xs]), *layers)
        tree = {"layers": {"blk": stacked}}
    else:
        tree = {f"layers_{i}": lyr for i, lyr in enumerate(layers)}
    head = sd.get("lm_head.weight",
                  sd["model.embed_tokens.weight"])  # tied fallback
    tree.update({
        "embed_tokens": jnp.asarray(
            sd["model.embed_tokens.weight"].astype(np.float32)),
        "norm": {"scale": jnp.asarray(
            sd["model.norm.weight"].astype(np.float32))},
        "lm_head": jnp.asarray(head.astype(np.float32)),
    })
    return tree


# ------------------------------------------- learned sparse attention, mrope
#
# Everything below was added at the END of the file (PR 65), and the five
# places above that reach it were changed WITHIN their lines: a Pallas
# kernel's serialized body carries the line numbers of the frames that called
# it, so a line added above ``LlamaAttention.__call__`` would change every
# lowered step that runs this file (``benchmark/tools/lowered_step_hash.py``
# holds OLMoE's and SDAR's to their parent's, unmasked).

# what a layer with an indexer sows into ``stats``, and the gauges
DSA_STAT_GAUGES = {"dsa_selected_share": "attention/dsa_selected_share",
                   "dsa_kl": "attention/dsa_kl"}

_LlamaConfigBase = LlamaConfig


@dataclasses.dataclass(frozen=True)
class LlamaConfig(_LlamaConfigBase):
    """``LlamaConfig`` with what Keye-VL-2.0's published config has and the
    others lack; every default is their behaviour, their programs letter for
    letter.

    Learned sparse attention (DeepSeek-V3.2's indexer, a published
    ``sa_config``): ``index_topk`` 0 -> off. >0 -> each layer's attention
    carries an indexer of ``index_heads`` x ``index_head_dim`` that scores
    every causal pair from the block's DETACHED input, a query attends to
    its ``index_topk`` best keys, and the indexer learns from the KL of the
    attention's mean probabilities against its own
    (``ops/attention.learned_sparse_attention``), sown into ``losses`` times
    ``dsa_kl_weight``, the mean over layers and rows.

    ``mrope_section``: multimodal rotary — frequency pair i of a head takes
    its angle from row 0, 1 or 2 of [3, S] positions (temporal, height,
    width), the sections in order; () -> one row. On text the rows are
    equal."""
    index_topk: int = 0
    index_heads: int = 0
    index_head_dim: int = 0
    dsa_kl_weight: float = 1.0
    mrope_section: tuple = ()

    def num_params(self):
        count = super().num_params()
        if self.index_topk:
            # the indexer's queries, its one key a token with a LayerNorm
            # (weight and bias), its weight a head
            E, J, Di = self.hidden_size, self.index_heads, self.index_head_dim
            count += self.n_layers * (E * J * Di + E * Di + 2 * Di + E * J)
        return count


def _positions(positions, offset, S):
    """The rotary positions of a forward: ``offset + arange(S)`` (every
    program before PR 65), or the caller's [3, S] multimodal rows for a
    config with ``mrope_section`` (``LlamaForCausalLM(positions=)``)."""
    return offset + jnp.arange(S) if positions is None else positions


def mrope_angles(positions, head_dim, theta, sections):
    """[3, S] positions (or [S]: the three rows equal) -> (cos, sin)
    [S, head_dim//2] fp32: pair i takes row 0 for i < sections[0], row 1
    for the next sections[1], row 2 for the rest. Equal rows give
    ``rope_angles`` bit for bit."""
    if sum(sections) != head_dim // 2:
        raise ValueError(f"mrope_section {tuple(sections)} does not add up "
                         f"to the {head_dim // 2} frequency pairs of a head")
    if positions.ndim == 1:
        positions = jnp.broadcast_to(positions, (3,) + positions.shape)
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    ang = positions.astype(jnp.float32)[:, :, None] * inv[None, None, :]
    row = np.repeat(np.arange(3), sections)
    ang = jnp.take_along_axis(ang, jnp.asarray(row)[None, None, :],
                              axis=0)[0]
    return jnp.cos(ang), jnp.sin(ang)


class _IndexKeyNorm(nn.Module):
    """LayerNorm with weight and bias over the indexer key's width."""
    eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                       self.param_dtype)
        b = self.param("bias", nn.initializers.zeros, (x.shape[-1],),
                       self.param_dtype)
        xf = x.astype(jnp.float32)
        xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
        n = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                               + self.eps)
        return (n * w.astype(jnp.float32)
                + b.astype(jnp.float32)).astype(self.dtype)


class _IndexedLlamaAttention(LlamaAttention):
    """``LlamaAttention`` for a config with ``mrope_section`` or an indexer
    (training: no KV cache — the indexer's key cache is ROADMAP's). The
    projections, the head norms and the names are ``LlamaAttention``'s, so a
    weight tree and a remat policy fit both; the rotary takes the sections,
    and with ``index_topk`` > 0 the indexer reads the block's normed input
    DETACHED — the cross-entropy reaches none of its weights — and its KL
    reaches nothing else (the op hands d kl to the indexer's operands
    alone)."""

    @nn.compact
    def __call__(self, x, positions):
        cfg = self.config
        if self.max_out_tokens or self.diffusion:
            raise NotImplementedError(
                "mrope / a learned indexer with a KV cache or under "
                "block-diffusion training")
        B, S, E = x.shape
        H, Hkv, D = cfg.n_heads, cfg.kv_heads, cfg.head_dim
        dense = lambda n, name: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            kernel_init=nn.initializers.normal(0.02), name=name)
        q, k, v = (dense(h * D, name)(x) for h, name in (
            (H, "q_proj"), (Hkv, "k_proj"), (Hkv, "v_proj")))
        if cfg.qk_norm:
            with annotate("qk_norm"):
                norm = lambda name: RMSNorm(  # noqa: E731
                    eps=cfg.rms_eps, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, name=name)
                if cfg.qk_norm == "head":
                    q = norm("q_norm")(q.reshape(B, S, H, D)).reshape(q.shape)
                    k = norm("k_norm")(k.reshape(B, S, Hkv, D)) \
                        .reshape(k.shape)
                else:
                    q, k = norm("q_norm")(q), norm("k_norm")(k)
        q, k, v = (checkpoint_name(t, "qkv") for t in (q, k, v))
        qh, kh, vh = (t.reshape(B, S, h, D).transpose(0, 2, 1, 3)
                      for t, h in ((q, H), (k, Hkv), (v, Hkv)))
        cos, sin = mrope_angles(positions, D, cfg.rope_theta,
                                cfg.mrope_section) if cfg.mrope_section \
            else rope_angles(positions, D, cfg.rope_theta)
        qh, kh = apply_rope(qh, cos, sin), apply_rope(kh, cos, sin)
        if cfg.index_topk:
            out = self._learned_sparse(x, qh, kh, vh, positions, dense)
        else:
            out = dot_product_attention(qh, kh, vh, causal=True,
                                        use_flash=cfg.use_flash)
        out = out.transpose(0, 2, 1, 3).reshape(B, S, H * D)
        return checkpoint_name(dense(E, "o_proj")(out), "attn_proj")

    def _learned_sparse(self, x, qh, kh, vh, positions, dense):
        from deepspeed_tpu.ops.attention import learned_sparse_attention
        cfg = self.config
        B, S, _ = x.shape
        J, Di = cfg.index_heads, cfg.index_head_dim
        with annotate("dsa_index_proj"):
            xi = jax.lax.stop_gradient(x)
            # rotate-half over all Di dims, on the temporal row
            cos, sin = rope_angles(positions if positions.ndim == 1
                                   else positions[0], Di, cfg.rope_theta)
            iq = dense(J * Di, "index_q")(xi).reshape(B, S, J, Di) \
                .transpose(0, 2, 1, 3)
            iq = apply_rope(iq, cos, sin)
            ik = _IndexKeyNorm(dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                               name="index_k_norm")(
                dense(Di, "index_k")(xi))
            ik = apply_rope(ik[:, None], cos, sin)[:, 0]
            iw = dense(J, "index_w")(xi).astype(jnp.float32) \
                * (J ** -0.5 * Di ** -0.5)
        out, kl, kept, bits = learned_sparse_attention(
            qh, kh, vh, iq, ik, iw, cfg.index_topk, use_flash=cfg.use_flash)
        if cfg.remat:
            from deepspeed_tpu.runtime.remat_budget import selection_pin_bytes
            selection_pin_bytes(B, S, cfg.n_layers)
        kl = jnp.mean(kl)
        if self.is_mutable_collection("losses"):
            self.sow("losses", "dsa_kl",
                     cfg.dsa_kl_weight / cfg.n_layers * kl)
        if self.is_mutable_collection("stats"):
            self.sow("stats", "dsa_kl", jax.lax.stop_gradient(kl))
            self.sow("stats", "dsa_selected_share",
                     jnp.sum(kept).astype(jnp.float32)
                     / (B * S * (S + 1) // 2))
        if self.is_mutable_collection("intermediates"):
            # a caller's look (the benchmark's check against its reference)
            self.sow("intermediates", "index_operands", (iq, ik, iw))
            self.sow("intermediates", "selection", bits)
            self.sow("intermediates", "attn_in", x)
        return out


def _attn_cls(cfg):
    """The attention module class of a block: ``LlamaAttention`` for every
    config without ``mrope_section`` and ``index_topk``."""
    return _IndexedLlamaAttention if cfg.index_topk or cfg.mrope_section \
        else LlamaAttention


def _pinned(cfg, policy, x):
    """``policy`` joined, for a stack whose layers carry an indexer, with
    what pins a layer's selection: the recomputed forward attends to the
    keys the first one chose (its bytes:
    ``runtime/remat_budget.selection_pin_bytes``) — and, where the chip has
    the room for it beside the blocks' input ``x`` [B, S, E]
    (``runtime/remat_budget.keep_kl_grad``), with the KL's gradient in the
    indexer's scores: the recomputed forward then runs neither the indexer
    nor the KL pass."""
    if not cfg.index_topk or policy is None:
        return policy
    from deepspeed_tpu.ops.pallas.learned_sparse_attention import \
        KL_GRAD_NAME, SELECTION_NAME
    from deepspeed_tpu.runtime.remat_budget import keep_kl_grad
    B, S, E = x.shape
    kept = keep_kl_grad(B, S, E, cfg.n_layers, jnp.dtype(cfg.dtype).itemsize,
                        remat_inflight_row_bytes(cfg, S))
    names = (SELECTION_NAME,) + ((KL_GRAD_NAME,) if kept else ())
    return jax.checkpoint_policies.save_from_both_policies(
        policy, jax.checkpoint_policies.save_only_these_names(*names))


def _with_dsa_gauges(cfg, gauges):
    return dict(gauges, **DSA_STAT_GAUGES) if cfg.index_topk else gauges


def remat_inflight_row_bytes(cfg, seq_len):
    """Bytes a row the widest branch of a block with an indexer holds
    between its recomputation and the end of its backward (what
    ``runtime/remat_budget.reserve_bytes`` counts): the experts' (or the
    MLP's), or the attention's with what the learned selection adds to it."""
    from deepspeed_tpu.moe.dropless import inflight_row_bytes
    from deepspeed_tpu.ops.pallas.flash_attention import bwd_dq_slab_rows
    from deepspeed_tpu.runtime import remat_budget as rb
    b, D = jnp.dtype(cfg.dtype).itemsize, cfg.head_dim
    q = cfg.n_heads * D
    attn = rb.attention_inflight(q, q, 2 * cfg.kv_heads * D, b,
                                 bwd_dq_slab_rows(seq_len, D, D, b)) \
        + rb.learned_sparse_inflight(seq_len, b)
    ffn = inflight_row_bytes(
        cfg.hidden_size, cfg.intermediate_size, cfg.num_experts_per_tok,
        cfg.num_experts, cfg.experts_held or cfg.num_experts, itemsize=b) \
        if cfg.num_experts else rb.mlp_inflight(cfg.intermediate_size, b)
    return max(attn, ffn)
