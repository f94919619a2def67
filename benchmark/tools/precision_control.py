"""What a training cell's ``correct`` reads when the SYSTEM runs in the
nearest precision below the one its configuration states.

    python -m benchmark.tools.precision_control <cell> --seed N [--rehearse-cpu]

A limit of ``train.tolerance`` lies between two readings: the largest an
honest run gave, and what the same comparison reads when the system is worse
than it says. This tool takes both at the cell's own size and load, in one
process on the cell's chip: the engine is built as the cell builds it, the
family's ``system_step`` runs on the timed batch once with the weights as
they are (the engine's state on the device beside it, as in the cell's
set-up) and once with every weight MATRIX rounded to fp8 (e4m3's grid under
one scale a tensor; vectors and scalars — norm weights, gates' biases — as
they are; the engine's state is let go first: the second copy of the
weights takes its room), and each is judged by the family's ``compare`` +
``judge_train`` against the float32 reference on the HONEST weights. The
second must come out not correct. One JSON object a pass on stdout, the
line that starts with ``{``: every check, every reading. A script and not a test: it is
run when a tolerance is set or questioned, and its readings are written
beside the limits in the configuration file.
"""

import argparse
import json
import sys

from benchmark import manifest, traffic


def fp8_matrices(params):
    """``params`` with every matrix rounded to an e4m3 grid (4 exponent, 3
    mantissa bits) under one scale a tensor (its largest magnitude on 240,
    the largest finite value of that grid). ``lax.reduce_precision``, not a
    pair of casts: XLA removes a cast to a narrower type and back on a TPU.
    A leaf under ``layers`` (the families' ``layer_stacked_subtree``) carries
    the layer scan's leading axis: not a matrix for it."""
    import jax
    import jax.numpy as jnp

    def rounded(path, x):
        under = any(getattr(k, "key", None) == "layers" for k in path)
        if x.ndim - under < 2:
            return x
        scale = jnp.max(jnp.abs(x)) / 240.0
        return jax.lax.reduce_precision(x / scale, 4, 3) * scale

    return jax.jit(lambda p: jax.tree_util.tree_map_with_path(rounded, p))(
        params)


def main(argv=None):
    import jax
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    bench = manifest.load()
    cell = manifest.cell_of(bench, args.cell)
    config, p = manifest.config_of(bench, cell), manifest.traffic_of(cell)
    family, rehearse = manifest.family_module(config), args.rehearse_cpu
    devices = jax.devices()[:cell["chips"]]
    shapes = family.traffic_shapes(config, rehearse)
    batch = traffic.train_batches(p, args.seed, shapes["vocab_size"],
                                  shapes["seq_scale"])[0]
    engine, params = family.build_train(config, p["global_batch"], args.seed,
                                        devices, rehearse)
    all_correct = {}
    for name in ("as the cell runs", "fp8 weight matrices"):
        weights = params
        if name != "as the cell runs":
            engine.state = None
            weights = fp8_matrices(params)
        system = family.system_step(config, weights, batch, devices[0],
                                    rehearse)
        del weights
        want_loss, want_gnorm, diffs = family.compare(
            config, params, batch, devices[0], rehearse, system)
        got_loss = float(system[0])
        del system
        checks, detail = family.judge_train(
            config, got_loss, diffs["system_grad_norm"], want_loss,
            want_gnorm, diffs)
        all_correct[name] = all(checks.values())
        print(json.dumps({"system": name, "correct": all_correct[name],
                          "checks": checks, "detail": detail},
                         default=float), flush=True)
    # the honest pass correct, the lower precision not
    return 0 if list(all_correct.values()) == [True, False] else 1


if __name__ == "__main__":
    sys.exit(main())
