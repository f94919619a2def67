"""Decode-latency ablation (b1, GPT-2 large, ctx 2048): where do the
~9 ms/token go? Times the full scan decode, then variants with pieces
removed, using the two-window difference method (the readback fence
cancels).

Run: python -m tests.perf.decode_ablate
"""

import json
import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.models.gpt2_inference import (
        generate, convert_gpt2_params, quantize_gpt2_inference_params)

    ctx = 2048
    cfg = GPT2Config(vocab_size=50304, n_positions=ctx, n_embd=1280,
                     n_layer=36, n_head=20, dtype=jnp.bfloat16,
                     param_dtype=jnp.bfloat16, scan_layers=True)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, 50304, size=(1, ctx - 200)).astype(np.int32)
    params = jax.jit(GPT2LMHeadModel(cfg).init)(
        jax.random.PRNGKey(0), prompt[:, :8])["params"]
    iparams = convert_gpt2_params(params, cfg)
    qparams = quantize_gpt2_inference_params(iparams)

    def tok_ms(**kw):
        p = qparams if kw.get("quantize_bits") else iparams

        def run(new):
            toks = generate(cfg, p, prompt, max_new_tokens=new,
                            max_out_tokens=ctx, **kw)
            return float(jax.device_get(toks[0, -1]))
        run(4)
        run(132)
        best = 1e9
        for _ in range(2):
            t0 = time.perf_counter()
            run(4)
            t_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            run(132)
            t_l = time.perf_counter() - t0
            best = min(best, (t_l - t_s) / 128)
        return best * 1000

    out = {"scan_bf16": round(tok_ms(scan_decode=True), 2),
           "steploop_bf16": round(tok_ms(scan_decode=False), 2),
           "scan_int8w": round(tok_ms(scan_decode=True, quantize_bits=8), 2),
           "scan_int8w_int8kv": round(
               tok_ms(scan_decode=True, quantize_bits=8, kv_cache_bits=8), 2)}
    out["tok_per_s_best"] = round(1000 / min(out.values()), 1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
