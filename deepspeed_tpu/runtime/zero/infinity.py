"""ZeRO-Infinity for one TPU chip: segment-streamed training of models
whose parameters + optimizer state exceed HBM by an order of magnitude.

Reference role: deepspeed/runtime/zero/stage3.py +
swap_tensor/partitioned_param_swapper.py:36 + the ZeRO-Infinity paper's
claim lattice (docs/_posts/2021-03-08-zero3-offload.md:51 — 40B params
on one 32 GB V100). The reference streams params from NVMe/DRAM through
module fetch/release hooks around every submodule and runs the optimizer
on host cores. The TPU-native realization keeps every FLOP on the chip
and expresses the tiers as XLA memory spaces:

- **fp32 master + Adam moments rest in ``pinned_host``** (device-host
  DRAM, tens of GB), never all resident in HBM — same placement as the
  r4 streamed-offload tier (zero/offload_stream.py).
- **Compute params are materialized PER SEGMENT**: the [n_layer, ...]
  scan-stacked transformer splits into K row-segments; one jitted
  fetch casts a segment's pinned fp32 rows to a bf16 stack in HBM, the
  segment's forward runs, and the stack is freed before the next
  segment fetch. Peak param HBM = one segment, not the model.
- **Backward re-fetches each segment in reverse** (boundary activations
  were kept — K+1 small [B,S,E] tensors), computes the segment vjp
  with rematerialized block bodies, streams the PER-ROW Adam update
  (donated pinned m/v/master in, updated out) and frees the segment's
  grads before touching the previous segment.
- **The compute-dtype parameters rest on client NVMe** via
  PartitionedParamSwapper files: written at init (from the host-side
  init, no d2h) and refreshed on ``park_to_nvme()``/checkpoint. Cold
  start restores the pinned masters FROM the files
  (``restore_from_nvme``), which is the disk-read path at full scale.
  A per-step disk round-trip of multi-GB params costs more than the
  step, so per-step disk parking is gated by ``park_threshold_bytes`` —
  small models keep the park-every-step behavior, large models park on
  demand — and the step streams through the pinned tier instead.

HBM peak per step ~= segment bf16 params + segment bf16 grads + one
segment's fp32 master rows + boundary activations + remat workspace —
for a 6.2B-param GPT-2 (E=4096, 30 layers) in 6 segments that is ~9 GB
on a 16 GB chip, against 12.4 GB of bf16 params and 61 GB of state.

Supports GPT2LMHeadModel configs with ``scan_layers=True`` and tied
embeddings (the flagship family). Select via the engine config::

    "zero_optimization": {"stage": 3,
        "offload_param": {"device": "nvme", "nvme_path": ...,
                          "stream_segments": 6},
        "offload_optimizer": {"device": "cpu"}}
"""

import os
from typing import List, Optional

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from deepspeed_tpu.utils.logging import logger


def gpt2_client_init(cfg, seed=0):
    """Client-side parameter init WITHOUT materializing the model on any
    device: structure from ``jax.eval_shape``, values from numpy
    (kernels ~ N(0, 1/sqrt(fan_in)), embeddings N(0, .02/.01), LN
    ones/zeros). This is how multi-GB models enter the streamed engine —
    ``model.init`` would build the whole tree through the device."""
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    model = GPT2LMHeadModel(cfg)
    shapes = jax.eval_shape(
        model.init, jax.random.PRNGKey(0),
        np.zeros((1, 8), np.int32))["params"]
    rs = np.random.RandomState(seed)

    def leaf(path, s):
        names = [str(getattr(p, "key", p)) for p in path]
        last = names[-1]
        if last == "kernel":
            a = rs.standard_normal(s.shape).astype(np.float32) \
                / np.sqrt(s.shape[-2])
        elif last == "wte":
            a = rs.standard_normal(s.shape).astype(np.float32) * 0.02
        elif last == "wpe":
            a = rs.standard_normal(s.shape).astype(np.float32) * 0.01
        elif last == "scale":
            a = np.ones(s.shape, np.float32)
        else:
            a = np.zeros(s.shape, np.float32)
        # STAY numpy (ml_dtypes handles bf16): jnp.asarray here would
        # materialize every leaf on the default device, only to be read
        # back for the NVMe files
        return a.astype(np.dtype(s.dtype))
    return jax.tree_util.tree_map_with_path(leaf, shapes)


class _Segment(nn.Module):
    """``rows`` scanned transformer blocks — the streamed unit. Param
    tree matches GPT2LMHeadModel's ``h/blk`` subtree with a [rows, ...]
    leading axis, so segment params are row-slices of the full stacks."""
    config: object
    rows: int

    @nn.compact
    def __call__(self, x):
        from deepspeed_tpu.models.gpt2 import ScanBody
        scanned = nn.scan(ScanBody,
                          variable_axes={"params": 0},
                          split_rngs={"params": True},
                          in_axes=(nn.broadcast, nn.broadcast),
                          length=self.rows)
        x, _ = scanned(self.config, name="h")(x, True, 1.0)
        return x


class InfinityEngine:
    """Segment-streamed ZeRO-Infinity trainer for scan-stacked GPT-2.

    ``train_batch({"input_ids": ..., "labels":?}) -> loss`` like the
    main engine; params/optimizer state live in pinned_host + NVMe as
    described in the module docstring.
    """

    def __init__(self, model_cfg, params, device=None, *,
                 segments: int = 4,
                 nvme_path: Optional[str] = None,
                 lr: float = 1e-4, betas=(0.9, 0.999), eps: float = 1e-8,
                 weight_decay: float = 0.0, adam_w: bool = True,
                 moment_dtype=jnp.bfloat16,
                 park_threshold_bytes: int = 256 * 1024 * 1024,
                 lr_fn=None, restore_params: bool = False):
        cfg = model_cfg
        assert cfg.scan_layers and cfg.tie_word_embeddings, \
            "InfinityEngine streams the scan-stacked tied-embedding family"
        assert cfg.n_layer % segments == 0, (cfg.n_layer, segments)
        self.cfg = cfg
        self.K = segments
        self.rows = cfg.n_layer // segments
        self.lr, self.betas, self.eps = lr, betas, eps
        self.wd, self.adam_w = weight_decay, adam_w
        self.lr_fn = lr_fn
        self._mdtype = moment_dtype
        self.step_count = 0
        self.device = device or jax.devices()[0]
        self.mesh = Mesh(np.array([self.device]), ("d",))
        kinds = {m.kind for m in self.device.addressable_memories()}
        # CPU advertises host memory kinds but cannot lower the placement
        # annotation — the tiers only separate on real accelerators
        self._host_kind = "pinned_host" \
            if "pinned_host" in kinds and self.device.platform != "cpu" \
            else None
        self._dev_sh = self._sh("device")
        self._host_sh = self._sh(self._host_kind)

        # ---- state layout: per-layer-ROW pinned fp32 master + moments
        # (the update's streaming unit; one row of a 6B model is ~800 MB
        # of fp32 master — comfortably double-bufferable)
        blk = params["h"]["blk"]
        self._blk_leaves, self._blk_def = jax.tree_util.tree_flatten(blk)
        self._blk_shapes = [tuple(l.shape) for l in self._blk_leaves]
        emb = {k: params[k] for k in ("wte", "wpe", "ln_f")}
        self._emb_leaves, self._emb_def = jax.tree_util.tree_flatten(emb)

        # host placement via in-body device_put, NOT out_shardings: the
        # AOT compile path rejects host-memory entry outputs declared
        # through out_shardings ("layout for this output is not set to
        # host memory"), while the device_put form is the r4-proven one.
        # Placement is BATCHED over ROW-CHUNKS of each stacked leaf
        # (~1 GiB of rows per jit call, split into pinned rows inside
        # the jit): per-ROW placement is 13 x n_layer dispatches, and
        # one jit per WHOLE leaf stages a multi-GB stack at once —
        # chunking bounds both.
        place_fns = {}

        def place_chunk(chunk):
            key = chunk.shape
            f = place_fns.get(key)
            if f is None:
                def body(x):
                    xf = x.astype(jnp.float32)
                    rows = tuple(jax.device_put(xf[r], self._host_sh)
                                 for r in range(x.shape[0]))
                    zm = tuple(jax.device_put(
                        jnp.zeros(x.shape[1:], self._mdtype),
                        self._host_sh) for _ in range(x.shape[0]))
                    zv = tuple(jax.device_put(
                        jnp.zeros(x.shape[1:], jnp.float32),
                        self._host_sh) for _ in range(x.shape[0]))
                    return rows, zm, zv
                f = place_fns[key] = jax.jit(body)
            return f(chunk)

        self.master: List[List] = [[None] * len(self._blk_leaves)
                                   for _ in range(cfg.n_layer)]
        self.m: List[List] = [[None] * len(self._blk_leaves)
                              for _ in range(cfg.n_layer)]
        self.v: List[List] = [[None] * len(self._blk_leaves)
                              for _ in range(cfg.n_layer)]
        for i, leaf in enumerate(self._blk_leaves):
            arr = np.asarray(leaf)
            # budget against the IN-JIT footprint (fp32 master rows +
            # fp32/bf16 zero moments ≈ 5x the bf16 source bytes), not
            # the source bytes — the AOT helper's multi-GB-per-program
            # crash is what chunking exists to avoid
            row_bytes = max(arr[0].size * 10, 1)
            step = max(1, int((1 << 30) // row_bytes))
            for s in range(0, cfg.n_layer, step):
                rows, zm, zv = place_chunk(arr[s:s + step])
                for j, r in enumerate(range(s, min(s + step,
                                                   cfg.n_layer))):
                    self.master[r][i] = rows[j]
                    self.m[r][i] = zm[j]
                    self.v[r][i] = zv[j]
        place_row = jax.jit(
            lambda *ls: tuple(
                jax.device_put(jnp.asarray(l).astype(jnp.float32),
                               self._host_sh) for l in ls))
        zeros_row = jax.jit(
            lambda *ls: tuple(
                jax.device_put(x, self._host_sh) for l in ls
                for x in (jnp.zeros(l.shape, self._mdtype),
                          jnp.zeros(l.shape, jnp.float32))))
        self.emb_master = list(place_row(*[np.asarray(l)
                                           for l in self._emb_leaves]))
        emz = zeros_row(*self.emb_master)
        self.emb_m, self.emb_v = list(emz[0::2]), list(emz[1::2])

        # ---- NVMe at-rest tier
        self._fns = {}            # jit cache (restore uses place_row)
        self._swapper = None
        self._park_threshold = park_threshold_bytes
        self.param_bytes = sum(
            int(np.prod(s)) * jnp.dtype(cfg.param_dtype).itemsize
            for s in self._blk_shapes) + sum(
            int(np.prod(l.shape)) * jnp.dtype(cfg.param_dtype).itemsize
            for l in self._emb_leaves)
        if nvme_path:
            from deepspeed_tpu.runtime.swap_tensor import (
                PartitionedParamSwapper)
            # DURABLE at-rest tier: stable sub-dir + meta sidecar, no
            # pid scoping, survives the process — a fresh engine with
            # restore_params=True cold-starts from these files.
            # CONTRACT: nvme_path identifies ONE training run's at-rest
            # state (like a checkpoint dir) — two engines sharing it
            # overwrite each other; call release() to reclaim the disk
            self._swapper = PartitionedParamSwapper(
                nvme_path, sub_dir="infinity_params", durable=True)
            if restore_params:
                self._swapper.load_meta()
                self.restore_from_nvme()
            else:
                # written host-side (numpy in, no d2h) — params rest on
                # disk from step zero
                self._swapper.write_all(
                    [np.asarray(l).astype(self._np_pdtype())
                     for l in self._emb_leaves] +
                    [np.asarray(l).astype(self._np_pdtype())
                     for l in self._blk_leaves])

        logger.info(
            f"InfinityEngine: {cfg.n_layer} layers in {segments} segments "
            f"of {self.rows}; {self.param_bytes / 2**30:.2f} GiB compute "
            f"params, master+moments in "
            f"{self._host_kind or 'device memory'}; NVMe at-rest tier "
            f"{'ON' if self._swapper else 'off'}")

    # ------------------------------------------------------------- helpers
    def _np_pdtype(self):
        return np.dtype(jnp.dtype(self.cfg.param_dtype).name) \
            if jnp.dtype(self.cfg.param_dtype) != jnp.bfloat16 \
            else jnp.bfloat16

    def _sh(self, kind):
        sh = NamedSharding(self.mesh, PartitionSpec())
        if kind and kind != "device":
            sh = sh.with_memory_kind(kind)
        return sh

    def _seg_apply(self, seg_params, x):
        mod = _Segment(self.cfg, self.rows)
        return mod.apply({"params": {"h": {"blk": jax.tree_util.
                                           tree_unflatten(self._blk_def,
                                                          seg_params)}}}, x)

    # ------------------------------------------------ jitted building blocks
    def _fn(self, name, build):
        f = self._fns.get(name)
        if f is None:
            f = self._fns[name] = build()
        return f

    def _fetch_seg(self, seg):
        """pinned fp32 rows -> one [rows, ...] bf16 stack per leaf (HBM)
        and the fp32 row list (HBM) for the update."""
        rows = list(range(seg * self.rows, (seg + 1) * self.rows))

        def build():
            nleaf = len(self._blk_leaves)
            cdt = self.cfg.param_dtype

            def fetch(*flat):
                # flat: rows-major [row0 leaves..., row1 leaves...]
                per_leaf = []
                for i in range(nleaf):
                    per_leaf.append(jnp.stack(
                        [jax.device_put(flat[r * nleaf + i], self._dev_sh)
                         for r in range(self.rows)]).astype(cdt))
                return tuple(per_leaf)
            return jax.jit(fetch)
        fetch = self._fn("fetch_seg", build)
        flat = [m for r in rows for m in self.master[r]]
        return list(fetch(*flat))

    def _embed_fwd(self):
        cfg = self.cfg

        def build():
            def f(wte, wpe, ids):
                from deepspeed_tpu.models.gpt2 import _embed_lookup
                wte_c = wte.astype(cfg.dtype)
                x = _embed_lookup(wte_c, ids) \
                    + wpe[:ids.shape[1]].astype(cfg.dtype)[None]
                return x
            return jax.jit(f)
        return self._fn("embed_fwd", build)

    def _seg_fwd(self):
        def build():
            return jax.jit(lambda ps, x: self._seg_apply(list(ps), x))
        return self._fn("seg_fwd", build)

    def _seg_grad(self):
        def build():
            def g(ps, x, dy):
                _, vjp = jax.vjp(
                    lambda p, xx: self._seg_apply(list(p), xx),
                    tuple(ps), x)
                dps, dx = vjp(dy)
                return tuple(dps), dx
            return jax.jit(g)
        return self._fn("seg_grad", build)

    def _head_grad(self):
        cfg = self.cfg

        def build():
            def loss_fn(lnf_scale, lnf_bias, wte, x, labels):
                from deepspeed_tpu.models.gpt2 import chunked_lm_loss, \
                    lm_loss
                xf = x.astype(jnp.float32)
                mu = jnp.mean(xf, axis=-1, keepdims=True)
                var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
                h = ((xf - mu) * jax.lax.rsqrt(
                    var + cfg.layer_norm_epsilon)
                    * lnf_scale.astype(jnp.float32)
                    + lnf_bias.astype(jnp.float32)).astype(cfg.dtype)
                wte_c = wte.astype(cfg.dtype)
                if cfg.loss_chunk > 0:
                    return chunked_lm_loss(h, wte_c, labels,
                                           cfg.loss_chunk)
                logits = jnp.einsum("bse,ve->bsv", h, wte_c)
                return lm_loss(logits, labels)

            def g(lnf_scale, lnf_bias, wte, x, labels):
                (loss, grads) = jax.value_and_grad(
                    loss_fn, argnums=(0, 1, 2, 3))(
                        lnf_scale, lnf_bias, wte, x, labels)
                return loss, grads
            return jax.jit(g)
        return self._fn("head_grad", build)

    def _embed_grad(self):
        def build():
            def g(wte, wpe, ids, dx):
                fwd = lambda a, b: self._embed_fwd_math(a, b, ids)
                _, vjp = jax.vjp(fwd, wte, wpe)
                return vjp(dx)
            return jax.jit(g)
        return self._fn("embed_grad", build)

    def _embed_fwd_math(self, wte, wpe, ids):
        from deepspeed_tpu.models.gpt2 import _embed_lookup
        cfg = self.cfg
        return _embed_lookup(wte.astype(cfg.dtype), ids) \
            + wpe[:ids.shape[1]].astype(cfg.dtype)[None]

    def _row_update(self):
        """One jitted Adam over a layer row: donated pinned master/m/v in,
        updated pinned master/m/v out. Grad rows are sliced on-device from
        the segment grad stacks at a traced row index."""
        beta1, beta2 = self.betas
        eps, wd, adam_w = self.eps, self.wd, self.adam_w
        mdt = self._mdtype
        nleaf = len(self._blk_leaves)

        def build():
            def upd(masters, ms, vs, grads, row, lr, count):
                cf = count.astype(jnp.float32)
                bc1 = 1.0 - beta1 ** cf
                bc2 = 1.0 - beta2 ** cf
                out_w, out_m, out_v = [], [], []
                for i in range(nleaf):
                    p32 = jax.device_put(masters[i], self._dev_sh)
                    m32 = jax.device_put(ms[i], self._dev_sh) \
                        .astype(jnp.float32)
                    v32 = jax.device_put(vs[i], self._dev_sh)
                    g32 = jax.lax.dynamic_index_in_dim(
                        grads[i], row, axis=0, keepdims=False) \
                        .astype(jnp.float32)
                    if wd and not adam_w:
                        g32 = g32 + wd * p32
                    m_new = beta1 * m32 + (1.0 - beta1) * g32
                    v_new = beta2 * v32 + (1.0 - beta2) * (g32 * g32)
                    u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
                    if wd and adam_w:
                        u = u + wd * p32
                    p_new = p32 - lr * u
                    out_w.append(jax.device_put(p_new, self._host_sh))
                    out_m.append(jax.device_put(m_new.astype(mdt),
                                                self._host_sh))
                    out_v.append(jax.device_put(v_new, self._host_sh))
                return tuple(out_w), tuple(out_m), tuple(out_v)
            return jax.jit(upd, donate_argnums=(0, 1, 2))
        return self._fn("row_update", build)

    def _emb_update(self):
        beta1, beta2 = self.betas
        eps, wd, adam_w = self.eps, self.wd, self.adam_w
        mdt = self._mdtype

        def build():
            def upd(masters, ms, vs, grads, lr, count):
                cf = count.astype(jnp.float32)
                bc1 = 1.0 - beta1 ** cf
                bc2 = 1.0 - beta2 ** cf
                out_w, out_m, out_v = [], [], []
                for p, m, v, g in zip(masters, ms, vs, grads):
                    p32 = jax.device_put(p, self._dev_sh)
                    m32 = jax.device_put(m, self._dev_sh) \
                        .astype(jnp.float32)
                    v32 = jax.device_put(v, self._dev_sh)
                    g32 = g.astype(jnp.float32)
                    if wd and not adam_w:
                        g32 = g32 + wd * p32
                    m_new = beta1 * m32 + (1.0 - beta1) * g32
                    v_new = beta2 * v32 + (1.0 - beta2) * (g32 * g32)
                    u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
                    if wd and adam_w:
                        u = u + wd * p32
                    p_new = p32 - lr * u
                    out_w.append(jax.device_put(p_new, self._host_sh))
                    out_m.append(jax.device_put(m_new.astype(mdt),
                                                self._host_sh))
                    out_v.append(jax.device_put(v_new, self._host_sh))
                return tuple(out_w), tuple(out_m), tuple(out_v)
            return jax.jit(upd, donate_argnums=(0, 1, 2))
        return self._fn("emb_update", build)

    # --------------------------------------------------------------- step
    def train_batch(self, batch):
        """One full streamed step; returns the scalar loss (host float)."""
        cfg = self.cfg
        ids = jnp.asarray(batch["input_ids"])
        labels = jnp.asarray(batch.get("labels", batch["input_ids"]))
        self.step_count += 1
        lr = jnp.float32(self.lr_fn(self.step_count)
                         if self.lr_fn else self.lr)
        count = jnp.int32(self.step_count)

        # embeddings stay resident for the whole step (wte is shared by
        # embed and the tied head)
        emb_fetch = self._fn("emb_fetch", lambda: jax.jit(
            lambda *ls: tuple(
                jax.device_put(l, self._dev_sh).astype(cfg.param_dtype)
                for l in ls)))
        # flatten order of {"ln_f": {bias, scale}, "wpe", "wte"}
        lnf_bias, lnf_scale, wpe, wte = emb_fetch(*self.emb_master)

        # ---- forward: stream segments, keep boundaries
        x = self._embed_fwd()(wte, wpe, ids)
        bounds = [x]
        seg_fwd = self._seg_fwd()
        for k in range(self.K):
            ps = self._fetch_seg(k)
            x = seg_fwd(tuple(ps), x)
            bounds.append(x)
            for p in ps:
                p.delete()

        # ---- head loss + its grads
        loss, (d_lnf_s, d_lnf_b, d_wte_head, dx) = self._head_grad()(
            lnf_scale, lnf_bias, wte, bounds[-1], labels)

        # ---- backward: re-fetch each segment, vjp, stream the row updates
        seg_grad = self._seg_grad()
        row_update = self._row_update()
        for k in reversed(range(self.K)):
            ps = self._fetch_seg(k)
            dps, dx = seg_grad(tuple(ps), bounds[k], dx)
            for p in ps:
                p.delete()
            for rloc in range(self.rows):
                r = k * self.rows + rloc
                w, m, v = row_update(
                    tuple(self.master[r]), tuple(self.m[r]),
                    tuple(self.v[r]), dps, jnp.int32(rloc), lr, count)
                self.master[r] = list(w)
                self.m[r], self.v[r] = list(m), list(v)
            for g in dps:
                g.delete()
            bounds[k + 1].delete()

        # ---- embedding grads + update
        d_wte_emb, d_wpe = self._embed_grad()(wte, wpe, ids, dx)
        add = self._fn("addcast", lambda: jax.jit(
            lambda a, b: a.astype(jnp.float32) + b.astype(jnp.float32)))
        d_wte = add(d_wte_head, d_wte_emb)
        grads = jax.tree_util.tree_leaves(
            {"wte": d_wte, "wpe": d_wpe,
             "ln_f": {"scale": d_lnf_s, "bias": d_lnf_b}})
        w, m, v = self._emb_update()(
            tuple(self.emb_master), tuple(self.emb_m), tuple(self.emb_v),
            tuple(grads), lr, count)
        self.emb_master, self.emb_m, self.emb_v = list(w), list(m), list(v)

        if self._swapper and self.param_bytes <= self._park_threshold:
            self.park_to_nvme()
        return float(jax.device_get(loss))

    # ------------------------------------------------------ NVMe residency
    def park_to_nvme(self):
        """Refresh the at-rest NVMe param files from the pinned masters
        (d2h + write — at multi-GB scale this is checkpoint-cadence work
        on disaggregated deployments; see module docstring)."""
        assert self._swapper is not None
        pdt = self._np_pdtype()
        leaves = [np.asarray(l).astype(pdt) for l in self.emb_master]
        for i in range(len(self._blk_leaves)):
            stack = np.stack([np.asarray(self.master[r][i]).astype(pdt)
                              for r in range(self.cfg.n_layer)])
            leaves.append(stack)
        self._swapper.write_all(leaves)

    def restore_from_nvme(self):
        """Cold start: rebuild the pinned fp32 masters from the NVMe
        param files (the at-scale disk-read path; moments reset)."""
        assert self._swapper is not None
        n_emb = len(self._emb_leaves)
        metas = self._swapper.meta
        place_row = self._fns.get("place_row") or jax.jit(
            lambda *ls: tuple(
                jax.device_put(jnp.asarray(l).astype(jnp.float32),
                               self._host_sh) for l in ls))
        self._fns["place_row"] = place_row
        bufs = []
        for i in range(len(metas)):
            shape, dtype = metas[i]
            arr = np.empty(int(np.prod(shape)) * dtype.itemsize, np.uint8)
            self._swapper.handle.sync_pread(arr, self._swapper._path(i))
            bufs.append(arr.view(dtype).reshape(shape))
        self.emb_master = list(place_row(*bufs[:n_emb]))
        blk = bufs[n_emb:]
        for r in range(self.cfg.n_layer):
            self.master[r] = list(place_row(*[b[r] for b in blk]))

    def params_on_disk_bytes(self):
        if not self._swapper:
            return 0
        return sum(os.path.getsize(self._swapper._path(i))
                   for i in range(len(self._swapper.meta)))

    def release(self):
        """Reclaim the durable NVMe files (they intentionally survive
        the process otherwise — see the at-rest contract in __init__)."""
        if self._swapper is not None:
            self._swapper.release()

    # ------------------------------------------------------- engine parity
    @classmethod
    def from_config(cls, model, ds_config, model_parameters=None,
                    device=None):
        """Build from a parsed DeepSpeedConfig (the ``initialize()``
        dispatch for ``offload_param.stream_segments > 0``). Large models
        should pass ``model_parameters=None`` and let the client-side
        numpy init build the tree without materializing the model."""
        cfg = model.config
        params = model_parameters if model_parameters is not None \
            else gpt2_client_init(cfg, seed=ds_config.seed)
        op = dict(ds_config.optimizer_params or {})
        adam_w = str(ds_config.optimizer_name or "adamw").lower() == "adamw"
        return cls(
            cfg, params, device=device,
            segments=ds_config.zero_config.offload_param.stream_segments,
            nvme_path=ds_config.zero_config.offload_param.nvme_path,
            lr=float(op.get("lr", 1e-4)),
            betas=tuple(op.get("betas", (0.9, 0.999))),
            eps=float(op.get("eps", 1e-8)),
            weight_decay=float(op.get("weight_decay", 0.0)),
            adam_w=adam_w)

    # the initialize() return-tuple surface
    optimizer = None
    training_dataloader = None
    lr_scheduler = None

    # ------------------------------------------------------------ export
    def params_tree(self, dtype=np.float32):
        """Full parameter pytree on the CLIENT host (d2h — checkpoint
        cadence at scale)."""
        blk_full = []
        for i, shape in enumerate(self._blk_shapes):
            blk_full.append(np.stack(
                [np.asarray(self.master[r][i]).astype(dtype)
                 for r in range(self.cfg.n_layer)]))
        tree = {"h": {"blk": jax.tree_util.tree_unflatten(
            self._blk_def, blk_full)}}
        emb = jax.tree_util.tree_unflatten(
            self._emb_def, [np.asarray(l).astype(dtype)
                            for l in self.emb_master])
        tree.update(emb)
        return tree
