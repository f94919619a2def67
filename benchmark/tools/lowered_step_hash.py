"""sha256 of a training cell's lowered step, for "no existing program changed".

    JAX_PLATFORMS=cpu python -m benchmark.tools.lowered_step_hash <out dir> <cell>

The cell's train step as ``tools/rehearse_compile.py`` lowers it (the
family's ``lower_train_step`` over abstract state on a described v5e), its
text with every ``loc(...)`` and ``#loc`` line stripped, written to ``<out
dir>/<cell>.txt``; one line ``<cell> <first 16 hex of its sha256> <length>``
on stdout. Two trees are compared by unpacking each IN TURN AT ONE PATH and
running this ONE PROCESS A CELL (PERF.md Findings PR 60): a Pallas kernel's
serialized module carries the file paths, lines and columns of the Python
frames that called it, and JAX caches a kernel's trace a process with the
call stack of whoever traced it first — several cells lowered in one process
inherit one another's frames. A tree that shifted lines above a calling
frame differs in those bytes alone; ``--mask-kernel-bodies`` hashes the text
with every kernel's serialized body blanked, which then has to be equal.
"""

import argparse
import hashlib
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("out")
    ap.add_argument("cell")
    ap.add_argument("--mask-kernel-bodies", action="store_true")
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from benchmark import manifest
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    bench = manifest.load()
    cell = manifest.cell_of(bench, args.cell)
    config = manifest.config_of(bench, cell)
    text = manifest.family_module(config).lower_train_step(
        config, manifest.traffic_of(cell),
        topo.devices[:cell["chips"]]).as_text()
    text = re.sub(r"loc\([^)]*\)", "", text)
    text = "\n".join(ln for ln in text.splitlines()
                     if not ln.startswith("#loc"))
    if args.mask_kernel_bodies:
        text = re.sub(r'(\\22body\\22: \\22)[^\\]*(\\22)', r"\1BODY\2", text)
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, args.cell + ".txt"), "w") as f:
        f.write(text)
    print(args.cell, hashlib.sha256(text.encode()).hexdigest()[:16],
          len(text), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
