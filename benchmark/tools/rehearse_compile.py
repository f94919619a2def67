"""Ask the TPU compiler about a cell's programs before chip time is spent.

    JAX_PLATFORMS=cpu python -m benchmark.tools.rehearse_compile [cell ...]

For each cell of ``BENCHMARK.json`` (default: all; a candidate cell when it
is named) the programs it runs are
compiled at real size for a described ``v5e:2x2`` that is not attached: a
training cell's step on the cell's number of chips, with the per-chip
``memory_analysis()`` and the collectives in the compiled text; a serving
cell's tick programs (every step count the engine uses) and prefill
buckets over the configured pool. The cell's FAMILY lowers the programs
over abstract weights (``lower_train_step``, ``lower_serving``: the members
``benchmark/families/__init__.py`` lists); this tool knows no model. The
compiler refuses here what it would refuse on the chip: a kernel it cannot
tile, a program over the chip's memory. A script and not a test
(TPU-compile tests live in ``tests/test_tpu_compile.py``). Nothing runs: a
compile that passes is not a chip run and gives no time.
"""

import argparse
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

from benchmark import manifest  # noqa: E402
from benchmark.trace_reduce import COLLECTIVE_OPS  # noqa: E402


def memory(compiled):
    ma = compiled.memory_analysis()
    return {"argument_gb": ma.argument_size_in_bytes / 1e9,
            "temp_gb": ma.temp_size_in_bytes / 1e9,
            "output_gb": ma.output_size_in_bytes / 1e9,
            "alias_gb": ma.alias_size_in_bytes / 1e9,
            # what the compiler holds against the chip's 15.75 GiB
            # (16.91 GB), and what ``train_program_hbm_gb`` reads in a run
            "peak_gb": ma.peak_memory_in_bytes / 1e9}


def collectives(text):
    return {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
            for op in COLLECTIVE_OPS}


def kernel_names(text):
    return sorted(set(re.findall(r'kernel_name = "([^"]+)"', text)))


def rehearse_train(family, config, traffic, devices):
    lowered = family.lower_train_step(config, traffic, devices)
    t = time.time()
    compiled = lowered.compile()
    return {"program": f"train step, {len(devices)} chip(s), "
                       f"batch {traffic['global_batch']}",
            "compile_s": time.time() - t, "per_chip": memory(compiled),
            "kernels": kernel_names(lowered.as_text()),
            "collectives": collectives(compiled.as_text())}


def rehearse_serve(family, config, traffic, device):
    facts, programs = family.lower_serving(config, traffic, device)
    out = [facts]
    for name, lowered in programs:
        t = time.time()
        compiled = lowered.compile()
        out.append({"program": name, "compile_s": time.time() - t,
                    **memory(compiled),
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
            family = manifest.family_module(config)
            if traffic["kind"] == "train_steps":
                res = rehearse_train(family, config, traffic,
                                     topo.devices[:cell["chips"]])
            else:
                res = rehearse_serve(family, config, traffic, topo.devices[0])
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
