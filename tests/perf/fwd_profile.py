"""Forward-pass component profile at the 774M headline shapes (bs8,
seq1024, E=1280, H=20, L=36): where do the forward milliseconds go vs
each component's roofline?

The r4 phase breakdown put forward at 167 ms against a ~72 ms matmul+
attention roofline (43% util) while backward ran at 58% — this harness
times each forward component in isolation (difference-method windows,
so the readback fence cancels) and prints a JSON line
per component with achieved TFLOP/s and % of the 197 TF v5e peak.

Run: python -m tests.perf.fwd_profile
"""

import json
import time

import numpy as np


def timed(fn, *args, iters=30, reps=3):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    # data-dependent fence: device_get of a freshly computed scalar
    leaf = jax.tree_util.tree_leaves(out)[0]
    float(jax.device_get(leaf.reshape(-1)[0]).astype(np.float32))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        leaf = jax.tree_util.tree_leaves(out)[0]
        float(jax.device_get(leaf.reshape(-1)[0]).astype(np.float32))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def main():
    import jax
    import jax.numpy as jnp
    from bench import peak_flops, _enable_compile_cache

    _enable_compile_cache()
    dev = jax.devices()[0]
    peak = peak_flops(dev)
    B, S, E, H, L = 8, 1024, 1280, 20, 36
    D = E // H
    M = B * S
    key = jax.random.PRNGKey(0)
    results = {}

    def report(name, dt, flops):
        tf = flops / dt / 1e12
        results[name] = {"ms": round(dt * 1000, 3),
                         "tflops": round(tf, 1),
                         "pct_peak": round(100 * tf * 1e12 / peak, 1)}

    x = jax.random.normal(key, (M, E), jnp.bfloat16)
    for name, n in (("matmul_qkv_3840", 3 * E), ("matmul_fc_5120", 4 * E),
                    ("matmul_proj_1280", E)):
        w32 = jax.random.normal(key, (E, n), jnp.float32) * 0.02
        wbf = w32.astype(jnp.bfloat16)
        f_bf = jax.jit(lambda a, w: a @ w)
        f_cast = jax.jit(lambda a, w: a @ w.astype(jnp.bfloat16))
        flops = 2 * M * E * n
        report(name + "_bf16w", timed(f_bf, x, wbf), flops)
        report(name + "_fp32w_cast", timed(f_cast, x, w32), flops)

    # flash attention fwd (causal): 4*S*E flops/token
    from deepspeed_tpu.ops.pallas.flash_attention import flash_attention
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i),
                                 (B, H, S, D), jnp.bfloat16) * 0.3
               for i in range(3))
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    # causal: half the S^2 work counts as "useful" in the 12LSE accounting
    report("flash_attn_fwd", timed(fa, q, k, v), 2 * 2 * B * S * S * E / 2)

    # one transformer block fwd (no remat wrapper)
    from deepspeed_tpu.models.gpt2 import GPT2Config, Block
    cfg = GPT2Config(vocab_size=50304, n_positions=S, n_embd=E, n_layer=L,
                     n_head=H, dtype=jnp.bfloat16, scan_layers=False,
                     remat=False)
    blk = Block(cfg)
    xb = jax.random.normal(key, (B, S, E), jnp.bfloat16)
    pb = jax.jit(blk.init)(key, xb)["params"]
    bf = jax.jit(lambda p, a: blk.apply({"params": p}, a))
    blk_flops = 2 * M * (12 * E * E) + 2 * 2 * B * S * S * E / 2
    report("block_fwd_fp32w", timed(bf, pb, xb), blk_flops)
    pb16 = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a, pb)
    cfg16 = GPT2Config(vocab_size=50304, n_positions=S, n_embd=E, n_layer=L,
                       n_head=H, dtype=jnp.bfloat16,
                       param_dtype=jnp.bfloat16, scan_layers=False,
                       remat=False)
    blk16 = Block(cfg16)
    bf16 = jax.jit(lambda p, a: blk16.apply({"params": p}, a))
    report("block_fwd_bf16w", timed(bf16, pb16, xb), blk_flops)

    # full-model forward + chunked loss, headline config (remat ON —
    # jax.checkpoint also runs in the primal, its policy should not
    # change pure-forward time) and OFF
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    ids = jnp.asarray(
        np.random.RandomState(0).randint(0, 50304, (B, S)), jnp.int32)
    model_flops = 2 * M * (L * 12 * E * E + 50304 * E) \
        + 2 * 2 * B * S * S * E * L / 2
    for tag, remat in (("remat_lean", True), ("noremat", False)):
        mcfg = GPT2Config(vocab_size=50304, n_positions=S, n_embd=E,
                          n_layer=L, n_head=H, dtype=jnp.bfloat16,
                          scan_layers=True, remat=remat,
                          remat_policy="dots_flash_fc_lean" if remat
                          else None, loss_chunk=1024)
        model = GPT2LMHeadModel(mcfg)
        pm = jax.jit(model.init)(key, ids[:, :8])["params"]
        lf = jax.jit(lambda p, i: model.apply({"params": p}, i, labels=i))
        report(f"model_fwd_loss_{tag}", timed(lf, pm, ids, iters=10),
               model_flops)
        del pm, lf
        jax.clear_caches()

    print(json.dumps(results, indent=1))


if __name__ == "__main__":
    main()
