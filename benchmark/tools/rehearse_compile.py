"""Ask the TPU compiler about a cell's programs before chip time is spent.

    JAX_PLATFORMS=cpu python -m benchmark.tools.rehearse_compile [cell ...]

For each cell of ``BENCHMARK.json`` (default: all; a candidate cell when it
is named) the programs it runs are
compiled at real size for a described ``v5e:2x2`` that is not attached: a
training cell's step on the cell's number of chips, with the per-chip
``memory_analysis()`` and the collectives in the compiled text; a serving
cell's tick programs (every step count the engine uses) and prefill
buckets over the configured pool. The compiler refuses here what it would
refuse on the chip: a kernel it cannot tile, a program over the chip's
memory. A script and not a test (TPU-compile tests live in
``tests/test_tpu_compile.py``). Nothing runs: a compile that passes is not
a chip run and gives no time.
"""

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,  # noqa: E402
                          SingleDeviceSharding)

from benchmark import manifest  # noqa: E402
from benchmark.families import gpt2 as family_gpt2  # noqa: E402
from benchmark.trace_reduce import COLLECTIVE_OPS  # noqa: E402

SDS = jax.ShapeDtypeStruct
I32, F32 = jnp.int32, jnp.float32


def memory(compiled):
    ma = compiled.memory_analysis()
    return {"argument_gb": ma.argument_size_in_bytes / 1e9,
            "temp_gb": ma.temp_size_in_bytes / 1e9,
            "output_gb": ma.output_size_in_bytes / 1e9,
            "alias_gb": ma.alias_size_in_bytes / 1e9}


def collectives(text):
    return {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
            for op in COLLECTIVE_OPS}


def kernel_names(text):
    return sorted(set(re.findall(r'kernel_name = "([^"]+)"', text)))


def rehearse_train(config, traffic, chips, devices):
    import deepspeed_tpu as dstpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.parallel import mesh as mesh_lib
    from deepspeed_tpu.runtime import precision as prec
    from deepspeed_tpu.runtime.engine import TrainState

    cfg = family_gpt2.model_config(config, rehearse=False)
    batch = traffic["global_batch"]
    mesh = Mesh(np.asarray(devices[:chips]).reshape((1, chips, 1, 1, 1)),
                mesh_lib.AXIS_ORDER)
    engine, _, _, _ = dstpu.initialize(
        config=family_gpt2.engine_config(config, batch, 0, False),
        model=GPT2LMHeadModel(cfg), mesh=mesh)
    ids = SDS((batch, traffic["seq_len"]), I32)
    params = jax.eval_shape(lambda r, x: engine.module.init(r, x)["params"],
                            jax.random.PRNGKey(0), ids)
    state = TrainState(
        params=params, opt_state=jax.eval_shape(engine.optimizer.init, params),
        scaler=jax.eval_shape(lambda: prec.init_scaler_state(engine.precision)),
        global_step=SDS((), I32), skipped_steps=SDS((), I32))
    engine.state_shardings = engine._build_state_shardings(state)
    engine._build_jit_fns()
    state = jax.tree_util.tree_map(
        lambda s, sh: SDS(s.shape, s.dtype, sharding=sh), state,
        engine.state_shardings)
    rng = jax.random.PRNGKey(0)
    lowered = engine._jit_train_batch.lower(
        state,
        {"input_ids": SDS(ids.shape, ids.dtype,
                          sharding=mesh_lib.batch_sharding(mesh))},
        SDS(rng.shape, rng.dtype, sharding=NamedSharding(mesh, P())))
    t = time.time()
    compiled = lowered.compile()
    text = compiled.as_text()
    return {"program": f"train step, {chips} chip(s), batch {batch}",
            "compile_s": time.time() - t, "per_chip": memory(compiled),
            "kernels": kernel_names(lowered.as_text()),
            "collectives": collectives(text)}


def rehearse_serve(config, traffic, device):
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.models.gpt2_inference import convert_gpt2_params
    from deepspeed_tpu.serving import (GPT2ServingAdapter,
                                       cache_spec_from_config)
    cfg = family_gpt2.model_config(config, rehearse=False, serving=True)
    one = SingleDeviceSharding(device)

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: SDS(s.shape, s.dtype, sharding=one), tree)

    served = jnp.dtype(config["serve"]["weights_dtype"])
    ip = jax.eval_shape(
        lambda r: convert_gpt2_params(jax.tree_util.tree_map(
            lambda a: a.astype(served), GPT2LMHeadModel(cfg).init(
                r, jnp.zeros((1, 8), I32))["params"]), cfg),
        jax.random.PRNGKey(0))
    spec = cache_spec_from_config(cfg, "gpt2",
                                  {"serving": config["serve"]["serving"]})
    nb = spec.resolved_num_blocks()
    shape = (spec.n_layers, nb, spec.kv_heads, spec.page_size, spec.head_dim)
    pool = (SDS(shape, spec.dtype), SDS(shape, spec.dtype))
    adapter = GPT2ServingAdapter(cfg, ip, spec)
    B, MAXP, Pg = spec.slots, spec.max_pages_per_slot, spec.page_size
    out = [{"pool_blocks": nb,
            "pool_gb": 2 * np.prod(shape) * 2 / 1e9}]

    def vec(dt):
        return SDS((B,), dt)

    for steps in traffic.get("tick_steps", [1, 2, 4, 8, 16, 32]):
        t = time.time()
        lowered = adapter._tick_fn(steps).lower(*on_chip((
            adapter._p, adapter._blk, pool, vec(I32), vec(I32),
            SDS((B, MAXP), I32), vec(jnp.uint32), vec(I32), vec(F32))))
        compiled = lowered.compile()
        out.append({"program": f"tick x{steps}", "compile_s": time.time() - t,
                    **memory(compiled),
                    "kernels": kernel_names(lowered.as_text())})
    for pages in traffic["prefill_page_buckets"]:
        t = time.time()
        lowered = adapter._prefill_fn(pages).lower(*on_chip((
            adapter._p, adapter._blk, pool, SDS((1, pages * Pg), I32),
            SDS((), I32), SDS((pages,), I32))))
        compiled = lowered.compile()
        out.append({"program": f"prefill {pages * Pg}",
                    "compile_s": time.time() - t, **memory(compiled),
                    "kernels": kernel_names(lowered.as_text())})
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # steer the kernels to their TPU branch (they ask jax.default_backend());
    # a compile for a described chip cannot be read back from the cache
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    bench = manifest.load()
    for name in args.cells:        # a candidate is rehearsed when named
        bench = manifest.with_candidate(bench, name)
    failed = False
    for cell in bench["workloads"]:
        if args.cells and cell["name"] not in args.cells:
            continue
        config = manifest.config_of(bench, cell)
        traffic = manifest.traffic_of(cell)
        try:
            if traffic["kind"] == "train_steps":
                res = rehearse_train(config, traffic, cell["chips"],
                                     topo.devices)
            else:
                res = rehearse_serve(config, traffic, topo.devices[0])
            print(json.dumps({"cell": cell["name"], "result": res}, indent=1),
                  flush=True)
        except Exception as e:  # boundary: report the compiler's words
            failed = True
            words = [ln for ln in str(e).splitlines() if ln.strip()][:12]
            print(json.dumps({"cell": cell["name"], "refused": words},
                             indent=1), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
