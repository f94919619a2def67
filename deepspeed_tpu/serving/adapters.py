"""The paged-serving skeleton: ONE adapter class for every model family.

``PagedServingAdapter`` owns every touch of the paged KV pool and
everything a family's programs repeat; a FAMILY is its layer math — the
``serving_*`` functions of ``models/gpt2_inference.py`` /
``models/llama_inference.py`` (geometry, params, embedding, qkv half,
out+FFN half, head), none of which sees the pool, the page table or the
sampler. ``serving.FAMILIES`` names the families; docs/serving.md
"Adding a family" lists what a new one writes.

An adapter compiles four KINDS of programs per model/storage
combination, so arbitrary request arrival patterns replay a small fixed
set of executables instead of retracing per request:

- ``tick``: decode steps over the whole slot set — [B_slots] tokens at
  per-slot positions, paged-attention reads through the page table,
  donated pool, idle slots masked by ``pos[b] < 0``. One program per
  step count.
- ``verify``: a K-token speculative window per slot in one dispatch —
  the same decode rows, K to a slot, in the kernel's multi-query mode.
- ``prefill``: one request's prompt pass at a BUCKETED padded length
  (pages rounded up to the next power of two), writing K/V straight
  into the slot's assigned pool pages and returning last-position
  logits. Compiled once per bucket — log2(max_pages) programs total.
- ``prefill_suffix``: the prefix-cache-hit prompt pass — only positions
  past the shared prefix, which is read back through the page table.

Decode rows run the stacked fused kernels the dense fast path serves
through (ops/pallas/decode.py): the family's ``ln_qkv_int8_stacked`` /
``out_ffn_int8_stacked`` halves (dtype-agnostic — bf16 stacks run with
scale 1) round ``decode_attention_paged``, the cached-attention read.
Appends are XLA scatters into the donated pool: row ``pos[b] % page``
of block ``page_table[b, pos[b] // page]``.
"""

import functools
import importlib

import numpy as np
import jax
import jax.numpy as jnp

from deepspeed_tpu.serving.paged_cache import PagedCacheSpec, PagedKVCache


# ----------------------------------------------------------- pool append

def _append_rows(pool, cache_q8, l, blk_ids, rows, k3, v3):
    """Scatter one new K/V row per slot into the paged pool at layer
    ``l``: (block blk_ids[b], row rows[b]). Idle slots arrive pointed at
    the trash block, so the scatter is always legal."""
    from deepspeed_tpu.ops.pallas.decode import kv_quant_int8
    if cache_q8:
        kc, ks, vc, vs = pool
        kq8, ksc, vq8, vsc = kv_quant_int8(k3, v3)
        kc = kc.at[l, blk_ids, :, rows, :].set(kq8)
        vc = vc.at[l, blk_ids, :, rows, :].set(vq8)
        ks = ks.at[l, blk_ids, :, 0, rows].set(ksc[..., 0])
        vs = vs.at[l, blk_ids, :, 0, rows].set(vsc[..., 0])
        return (kc, ks, vc, vs)
    kc, vc = pool
    kc = kc.at[l, blk_ids, :, rows, :].set(k3.astype(kc.dtype))
    vc = vc.at[l, blk_ids, :, rows, :].set(v3.astype(vc.dtype))
    return (kc, vc)


@functools.partial(jax.jit, donate_argnums=(0,))
def _copy_pool_block(pool, src, dst):
    """COW helper: duplicate one pool block (all layers, K and V and any
    scale arrays) — the sharer of a partially-filled prefix page
    continues appending into its own copy. Model-independent: every
    pool array indexes pages on axis 1."""
    return tuple(a.at[:, dst].set(a[:, src]) for a in pool)


def _quant_prompt_rows(t):
    """Per-(.., head, pos) symmetric int8 over the trailing D axis."""
    tf = t.astype(jnp.float32)
    sc = jnp.maximum(jnp.max(jnp.abs(tf), axis=-1) / 127.0, 1e-12)
    codes = jnp.clip(jnp.round(tf / sc[..., None]), -127,
                     127).astype(jnp.int8)
    return codes, sc


def _write_prompt_pages(pool, cache_q8, k, v, pages, page):
    """Blockify a prompt's K/V ([Lyr, H, Sp, D], Sp = len(pages)*page)
    and scatter the blocks into the pool at ``pages``. Page-table tails
    past the slot's allocation arrive as the trash block — duplicate
    trash writes are harmless by construction."""
    Lyr, H, Sp, D = k.shape
    npg = pages.shape[0]
    assert npg * page == Sp, (Sp, npg, page)

    def to_blocks(t):                       # → [Lyr, npg, H, page, D]
        return t.reshape(Lyr, H, npg, page, D).transpose(0, 2, 1, 3, 4)

    def to_scale_blocks(sc):                # [Lyr, H, Sp] → [Lyr,npg,H,1,page]
        return sc.reshape(Lyr, H, npg, page).transpose(0, 2, 1, 3)[
            :, :, :, None, :]

    if cache_q8:
        kc, ks, vc, vs = pool
        kq, ksc = _quant_prompt_rows(k)
        vq, vsc = _quant_prompt_rows(v)
        kc = kc.at[:, pages].set(to_blocks(kq))
        vc = vc.at[:, pages].set(to_blocks(vq))
        ks = ks.at[:, pages].set(to_scale_blocks(ksc))
        vs = vs.at[:, pages].set(to_scale_blocks(vsc))
        return (kc, ks, vc, vs)
    kc, vc = pool
    kc = kc.at[:, pages].set(to_blocks(k).astype(kc.dtype))
    vc = vc.at[:, pages].set(to_blocks(v).astype(vc.dtype))
    return (kc, vc)


def _write_suffix_rows(pool, cache_q8, k, v, blks, rows):
    """Scatter per-position K/V rows (k/v [Lyr, H, Ssuf, D]) into pool
    blocks at (blks[i], rows[i]) — the mid-page generalization of
    _write_prompt_pages for SUFFIX prefill: after a prefix-cache share
    the suffix may start mid-page (COW), so each row lands at its own
    (block, row) pair. Pad positions arrive pointed at the trash
    block."""
    if cache_q8:
        kc, ks, vc, vs = pool
        kq, ksc = _quant_prompt_rows(k)     # [Lyr,H,S,D] / [Lyr,H,S]
        vq, vsc = _quant_prompt_rows(v)
        # two advanced indices split by a slice put the row axis FIRST:
        # value layout [S, Lyr, H, ...]
        kc = kc.at[:, blks, :, rows, :].set(kq.transpose(2, 0, 1, 3))
        vc = vc.at[:, blks, :, rows, :].set(vq.transpose(2, 0, 1, 3))
        ks = ks.at[:, blks, :, 0, rows].set(ksc.transpose(2, 0, 1))
        vs = vs.at[:, blks, :, 0, rows].set(vsc.transpose(2, 0, 1))
        return (kc, ks, vc, vs)
    kc, vc = pool
    kc = kc.at[:, blks, :, rows, :].set(
        k.transpose(2, 0, 1, 3).astype(kc.dtype))
    vc = vc.at[:, blks, :, rows, :].set(
        v.transpose(2, 0, 1, 3).astype(vc.dtype))
    return (kc, vc)


def _gather_prefix_kv(pool, cache_q8, l, pre_ids, dtype):
    """Gather (and dequantize) a slot's resident prefix K/V from the
    pool at layer ``l``: pre_ids = the slot's leading page-table
    entries, padded with trash past the real prefix (those rows are
    masked off by position in the caller). Returns K, V [H, NPRE*P, D]
    in ``dtype``."""
    def fold(x):                             # [NPRE, H, P, D] -> [H, L, D]
        npg, H, P, D = x.shape
        return x.transpose(1, 0, 2, 3).reshape(H, npg * P, D)

    if cache_q8:
        kc, ks, vc, vs = pool
        kd = kc[l, pre_ids].astype(jnp.float32) \
            * ks[l, pre_ids].transpose(0, 1, 3, 2)
        vd = vc[l, pre_ids].astype(jnp.float32) \
            * vs[l, pre_ids].transpose(0, 1, 3, 2)
        return fold(kd).astype(dtype), fold(vd).astype(dtype)
    kc, vc = pool
    return (fold(kc[l, pre_ids]).astype(dtype),
            fold(vc[l, pre_ids]).astype(dtype))


def _suffix_attn_bias(start, pos_q, n_prefix_rows):
    """Additive attention bias [1, 1, Ssuf, LPRE+Ssuf] for suffix
    prefill: prefix rows are valid iff their absolute position < start
    (rows past the live prefix in the gathered pages are stale), suffix
    rows mask causally at absolute positions."""
    lpre = n_prefix_rows
    kp = jnp.concatenate([jnp.arange(lpre, dtype=jnp.int32), pos_q])
    kvalid = jnp.concatenate([
        jnp.arange(lpre, dtype=jnp.int32) < start,
        jnp.ones(pos_q.shape, bool)])
    mask = (kp[None, :] <= pos_q[:, None]) & kvalid[None, :]
    return jnp.where(mask, 0.0, -1e30).astype(jnp.float32)[None, None]


def _append_ids(pos, pt, K, page, maxp):
    """(block ids, row offsets, positions) [B*K] for appending K rows a
    slot at positions pos[b]..pos[b]+K-1 (K = 1: a decode step; K > 1: a
    speculative window). Idle slots (pos < 0) resolve inside their
    all-trash table rows."""
    B = pos.shape[0]
    posf = (pos[:, None]
            + jnp.arange(K, dtype=jnp.int32)[None, :]).reshape(B * K)
    bidx = jnp.clip(posf // page, 0, maxp - 1)
    batch = jnp.repeat(jnp.arange(B, dtype=jnp.int32), K)
    return pt[batch, bidx], posf % page, posf


def _sample_keys(seeds, idxs):
    """Per-slot stateless sampling keys: fold the request's persistent
    ``sample_key`` and its GLOBAL token index (committed tokens before
    this one, across restores) into one base key. The key depends only
    on (request, position) — never on engine-global rng state — so a
    snapshot/restore or a prefill->decode handoff replays a sampled
    request token-for-token (ISSUE 14 satellite; the PR-11 fresh-rng
    caveat)."""
    base = jax.random.PRNGKey(0)

    def one(s, i):
        return jax.random.fold_in(jax.random.fold_in(base, s), i)

    return jax.vmap(one)(seeds, idxs)


def _pick_next(logits, seeds, idxs, temps):
    """Greedy/per-slot-temperature sampling; the Gumbel pass only runs
    when some slot actually asked for it (same cond-not-where rule as
    the dense decode loops)."""
    logits32 = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits32, axis=-1)

    def _sampled():
        t = jnp.maximum(temps, 1e-6)[:, None]
        keys = _sample_keys(seeds, idxs)
        s = jax.vmap(jax.random.categorical)(keys, logits32 / t)
        return jnp.where(temps > 0, s, greedy)

    return jax.lax.cond(jnp.max(temps) > 0.0, _sampled, lambda: greedy), \
        logits32


def sample_token(logits32, seed, idx, temperature):
    """One-row invocation of the tick's sampling rule — the HOST-side
    prefill pick for sampled requests. Same fold_in key schedule and
    categorical as `_pick_next`, so a replayed request whose next token
    falls at prefill (admission) samples the token the uninterrupted
    run's decode tick would have produced."""
    tok, _ = _pick_next(
        jnp.asarray(logits32, jnp.float32)[None, :],
        jnp.asarray([seed], jnp.uint32), jnp.asarray([idx], jnp.int32),
        jnp.asarray([temperature], jnp.float32))
    return int(tok[0])   # sync-ok: the scheduler consumes the sample


def _attend_rows(pool, cache_q8, q, pos, pt, l, rows_per_step):
    """The cached-attention read of the decode rows q [B, Hkv, R, D]
    through the page table at layer ``l``."""
    from deepspeed_tpu.ops.pallas.decode import decode_attention_paged
    scale = 1.0 / np.sqrt(q.shape[-1])
    if cache_q8:
        kc, ks, vc, vs = pool
        return decode_attention_paged(
            q, kc, vc, pos, pt, l, k_scale=ks, v_scale=vs, scale=scale,
            rows_per_step=rows_per_step)
    kc, vc = pool
    return decode_attention_paged(q, kc, vc, pos, pt, l, scale=scale,
                                  rows_per_step=rows_per_step)


def _program(build):
    """Method decorator: ``build(self, *static)`` returns one program
    kind's function of (p, blk, pool, ...); the decorated method returns
    it jitted with the pool donated, built once per ``static`` ON THE
    ADAPTER — the closures hold the adapter, so a module-global cache
    would pin every model's weights for process lifetime; here they
    free with the engine."""
    @functools.wraps(build)
    def get(self, *static):
        key = (build.__name__,) + static
        if key not in self._fns:
            self._fns[key] = jax.jit(build(self, *static),
                                     donate_argnums=(2,))
        return self._fns[key]
    return get


class PagedServingAdapter:
    """Paged serving of one model: the pool, the page table, the scans,
    the sampler and the program cache. ``layer_math`` — bound by the
    subclasses below — names the module holding the model family's
    ``serving_*`` functions."""

    layer_math = None

    @classmethod
    def math(cls):
        """The family's module, imported on first use: a process that
        only routes requests or moves frames never loads a model file."""
        return importlib.import_module(cls.layer_math)

    def __init__(self, cfg, params, spec: PagedCacheSpec,
                 quantize_bits: int = 0):
        self.cfg = cfg
        self.spec = spec
        self.cache_q8 = spec.kv_cache_bits == 8
        self._geom = self.math().serving_geometry(cfg)
        for k in ("n_layers", "kv_heads", "head_dim"):
            assert getattr(spec, k) == self._geom[k], (k, spec, self._geom)
        self._p, self._blk = self.math().serving_params(cfg, params,
                                                        quantize_bits)
        self._fns = {}

    def make_cache(self) -> PagedKVCache:
        return PagedKVCache(self.spec)

    def max_prompt_len(self):
        return self._geom["max_prompt_len"]

    # -- the two passes every program is made of ---------------------------

    def _decode_rows(self, w, pool, toks, pos, pt, window=None):
        """K rows a slot — toks [B, K] ([B] when K = 1) at positions
        pos[b]..pos[b]+K-1 — through the layer stack with the pool in the carry: each layer
        appends the rows' K/V, then attends through the page table.
        ``window=None`` is a decode step (K = 1, the kernel's
        single-position mask); ``window=K`` a speculative window (its
        multi-query mode). Returns (pool, logits [B*K, V])."""
        fam, cfg, spec = self.math(), self.cfg, self.spec
        B, K = pos.shape[0], window or 1
        Hkv, D = spec.kv_heads, spec.head_dim
        blk_ids, rows, posf = _append_ids(pos, pt, K, spec.page_size,
                                          spec.max_pages_per_slot)
        x = fam.serving_row_embed(cfg, w, toks.reshape(B * K), posf)

        def layer(car, l):
            x, pool = car
            q, k, v = fam.serving_row_qkv(cfg, w, x, l, posf)
            rep = q.shape[1] // Hkv     # GQA: rep query rows a KV head
            # STEP-major query rows: row j = step * rep + r
            qg = q.reshape(B, K, Hkv, rep, D) \
                .transpose(0, 2, 1, 3, 4).reshape(B, Hkv, K * rep, D)
            pool = _append_rows(pool, self.cache_q8, l, blk_ids, rows,
                                k, v)
            ctx = _attend_rows(pool, self.cache_q8, qg, pos, pt, l,
                               rows_per_step=rep if window else None)
            ctx = ctx.reshape(B, Hkv, K, rep, D) \
                .transpose(0, 2, 1, 3, 4).reshape(B * K, Hkv * rep * D)
            return (fam.serving_row_out_ffn(cfg, w, ctx, x, l), pool), None

        (x, pool), _ = jax.lax.scan(
            layer, (x, pool), jnp.arange(spec.n_layers, dtype=jnp.int32))
        return pool, fam.serving_row_head(cfg, w, x)

    def _prompt_rows(self, w, ids, attend):
        """One request's prompt rows ids [1, S] through the layer stack;
        ``attend(q, k, v, l)`` is the caller's attention over the rows
        (and whatever prefix it reads). Returns (x [1, S, E], K, V
        [Lyr, Hkv, S, D]) — the caller writes them to the pool."""
        fam, cfg = self.math(), self.cfg
        S = ids.shape[1]

        def layer(x, l):
            q, k, v = fam.serving_prompt_qkv(cfg, w, x, l)
            ctx = attend(q, k, v, l).transpose(0, 2, 1, 3).reshape(1, S, -1)
            return fam.serving_prompt_out_ffn(cfg, w, ctx, x, l), \
                (k[0], v[0])

        x, (ks, vs) = jax.lax.scan(
            layer, fam.serving_prompt_embed(cfg, w, ids),
            jnp.arange(self.spec.n_layers, dtype=jnp.int32))
        return x, ks, vs

    # -- compiled programs -------------------------------------------------

    @_program
    def _tick_fn(self, steps):
        fam, cfg = self.math(), self.cfg

        def tick(p, blk, pool, toks, pos, pt, seeds, idxs0, temps):
            w = fam.serving_row_weights(cfg, p, blk)

            def one(carry, t):
                pool, toks, pos, _ = carry
                pool, logits = self._decode_rows(w, pool, toks, pos, pt)
                nxt, logits32 = _pick_next(logits, seeds, idxs0 + t,
                                           temps)
                return (pool, nxt, pos + 1, logits32), nxt

            logits0 = jnp.zeros((toks.shape[0], self._geom["vocab_size"]),
                                jnp.float32)
            (pool, _, _, logits32), toks_seq = jax.lax.scan(
                one, (pool, toks, pos, logits0),
                jnp.arange(steps, dtype=jnp.int32))
            return pool, toks_seq, logits32

        return tick

    @_program
    def _verify_fn(self, n_rows):
        """Speculative verification: feed ``n_rows`` tokens per slot
        (the pending token + n_rows-1 drafts) in ONE dispatch; every
        drafted position attends through the page table at its own
        offset. Returns (pool, greedy [B, n_rows], logits32
        [B, n_rows, V])."""
        fam, cfg = self.math(), self.cfg

        def verify(p, blk, pool, toks, pos, pt):
            w = fam.serving_row_weights(cfg, p, blk)
            pool, logits = self._decode_rows(w, pool, toks, pos, pt,
                                             window=n_rows)
            logits32 = logits.astype(jnp.float32)
            greedy = jnp.argmax(logits32, axis=-1).astype(jnp.int32)
            B = toks.shape[0]
            return (pool, greedy.reshape(B, n_rows),
                    logits32.reshape(B, n_rows, -1))

        return verify

    @_program
    def _prefill_fn(self, n_pages):
        from deepspeed_tpu.ops.attention import dot_product_attention
        fam, cfg = self.math(), self.cfg
        P = self.spec.page_size
        Sp = n_pages * P
        assert Sp <= self.max_prompt_len(), (
            f"prefill bucket {Sp} exceeds the {self.max_prompt_len()}"
            "-position budget")

        def prefill(p, blk, pool, ids, length, pages):
            w = fam.serving_prompt_weights(cfg, p, blk, jnp.arange(Sp))
            x, ks, vs = self._prompt_rows(
                w, ids, lambda q, k, v, l: dot_product_attention(
                    q, k, v, causal=True))
            pool = _write_prompt_pages(pool, self.cache_q8, ks, vs,
                                       pages, P)
            logits = fam.serving_prompt_head(cfg, w, x[0, length - 1])
            return pool, logits.astype(jnp.float32)

        return prefill

    @_program
    def _prefill_suffix_fn(self, n_suf_pages, n_pre_pages):
        """Suffix-only prefill for prefix-cache hits: computes (and
        writes) K/V ONLY for prompt positions >= ``start``, reading the
        shared-prefix K/V back through the slot's page table. One
        compiled program per (suffix-pages, prefix-pages) pow2 bucket
        pair."""
        from deepspeed_tpu.ops.attention import dot_product_attention
        fam, cfg, spec = self.math(), self.cfg, self.spec
        P, MAXP = spec.page_size, spec.max_pages_per_slot
        Ssuf = n_suf_pages * P

        def prefill_sfx(p, blk, pool, ids, length, start, pt_row):
            pos_q = start + jnp.arange(Ssuf, dtype=jnp.int32)
            w = fam.serving_prompt_weights(cfg, p, blk, pos_q)
            pre_ids = pt_row[:n_pre_pages]
            bias = _suffix_attn_bias(start, pos_q, n_pre_pages * P)

            def attend(q, k, v, l):
                kpre, vpre = _gather_prefix_kv(
                    pool, self.cache_q8, l, pre_ids, self._geom["dtype"])
                ka = jnp.concatenate([kpre[None], k], axis=2)
                va = jnp.concatenate([vpre[None], v], axis=2)
                return dot_product_attention(q, ka, va, bias=bias)

            x, ks, vs = self._prompt_rows(w, ids, attend)
            valid = pos_q < length
            blks = jnp.where(
                valid, pt_row[jnp.clip(pos_q // P, 0, MAXP - 1)],
                jnp.int32(0))
            pool_out = _write_suffix_rows(pool, self.cache_q8, ks, vs,
                                          blks, pos_q % P)
            logits = fam.serving_prompt_head(cfg, w,
                                             x[0, length - 1 - start])
            return pool_out, logits.astype(jnp.float32)

        return prefill_sfx

    # -- engine-facing calls -----------------------------------------------

    def tick(self, pool, toks, pos, pt, seeds, idxs, temps, steps=1):
        """Run ``steps`` decode steps in ONE dispatch. ``seeds``/
        ``idxs`` [B] drive the per-slot stateless sampling keys (global
        token index of each slot's NEXT token — greedy slots pass
        zeros). Returns (pool, tokens [steps, B], last-step logits
        [B, V])."""
        return self._tick_fn(steps)(self._p, self._blk, pool, toks, pos,
                                    pt, seeds, idxs, temps)

    def prefill(self, pool, ids, length, pages):
        return self._prefill_fn(ids.shape[1] // self.spec.page_size)(
            self._p, self._blk, pool, ids, length, pages)

    def prefill_suffix(self, pool, ids, length, start, n_pre_pages,
                       pt_row):
        """Prefix-cache-hit prefill: compute/write only positions
        [start, length), attending over the shared prefix through the
        slot's page table row."""
        return self._prefill_suffix_fn(
            ids.shape[1] // self.spec.page_size, n_pre_pages)(
            self._p, self._blk, pool, ids,
            jnp.asarray(length, jnp.int32), jnp.asarray(start, jnp.int32),
            jnp.asarray(pt_row))

    def verify(self, pool, toks, pos, pt):
        """One speculative verification dispatch over toks [B, n_rows]."""
        return self._verify_fn(toks.shape[1])(
            self._p, self._blk, pool, jnp.asarray(toks),
            jnp.asarray(pos), jnp.asarray(pt))

    def copy_block(self, pool, src, dst):
        return _copy_pool_block(pool, jnp.asarray(src, jnp.int32),
                                jnp.asarray(dst, jnp.int32))


class GPT2ServingAdapter(PagedServingAdapter):
    """The skeleton over GPT-2: ``params`` is the training
    ``GPT2LMHeadModel`` tree or the converted (optionally int8) inference
    tree `convert_gpt2_params` produces."""
    layer_math = "deepspeed_tpu.models.gpt2_inference"


class LlamaServingAdapter(PagedServingAdapter):
    """The skeleton over LLaMA: ``params`` is the PACKED serving tree
    (convert_llama_serving_params / quantize_llama_serving_params /
    random_int8_serving_params). GQA: the pool holds Hkv heads."""
    layer_math = "deepspeed_tpu.models.llama_inference"
