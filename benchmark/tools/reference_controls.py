"""What a training cell's ``correct`` reads when the REFERENCE is computed
with one deliberate fault of the mathematics.

    python -m benchmark.tools.reference_controls <cell> --seed N [--rehearse-cpu]

For a family that names its controls (``CONTROLS``: the faults its plain
reference can be asked for, ``reference/sdar.py``'s a mask that leaks the
own block, a causal mask inside a block, the 1 / t weight dropped, targets
shifted by one, one t a sequence) this runs, in one process on the cell's
chip and at the cell's own size, the family's ``system_step`` on the timed
batch ONCE and judges it against the honest reference and then against the
reference with each fault in turn (``compare(..., control=)`` +
``judge_train``, the engine's state on the device beside it as in the cell's
set-up). The honest comparison must come out correct and every faulty one
not: a tolerance under which a wrong mask or a wrong loss passes is no
tolerance. One JSON object a pass on stdout, the line that starts with
``{``: the checks that failed and every reading. A script and not a test,
beside ``precision_control.py`` (the system in a lower precision): run when
a tolerance is set or questioned, its readings written beside the limits in
the configuration file.
"""

import argparse
import json
import sys

from benchmark import manifest, traffic


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
    _, params = family.build_train(config, p["global_batch"], args.seed,
                                   devices, rehearse)
    system = family.system_step(config, params, batch, devices[0], rehearse)
    got_loss = float(system[0])
    verdicts = []
    for control in (None,) + tuple(family.CONTROLS):
        want_loss, want_gnorm, diffs = family.compare(
            config, params, batch, devices[0], rehearse, system,
            control=control)
        checks, detail = family.judge_train(
            config, got_loss, diffs["system_grad_norm"], want_loss,
            want_gnorm, diffs)
        verdicts.append(all(checks.values()))
        print(json.dumps({"reference": control or "as published",
                          "correct": verdicts[-1],
                          "failed": sorted(k for k, v in checks.items()
                                           if not v),
                          "detail": detail}, default=float), flush=True)
    # the honest reference agrees, every faulty one does not
    return 0 if verdicts[0] and not any(verdicts[1:]) else 1


if __name__ == "__main__":
    sys.exit(main())
