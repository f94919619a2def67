"""Run cells several times, as the driver does, and print medians and spreads.

    chiprun -- python -m benchmark.tools.measure --out chiprun_out/sets \
        gpt2l-train-1chip:0:1,2,3,4,5,6 gpt2l-train-1chip:0:7,8,9,10,11,12

Each argument is ``<cell>:<trace 0|1>:<seed>,<seed>,...`` — one SET of runs,
each run a new process of ``python -m benchmark.run`` (this parent never
touches JAX, so the child gets the chip). Every result line goes to
``<out>/results.jsonl`` with its set, seed, exit code and wall seconds, the
end of each run's standard error to ``<out>/<cell>-seed<n>-trace<t>.err``,
and the table printed at the end gives, per set and metric, the values,
the median and the spread (distance between the quartiles over the
median) — the number a bound is set from. Never run by the driver.
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

from benchmark import stats


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("sets", nargs="+")
    ap.add_argument("--out", default="chiprun_out/measure")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--stop-on-failure", action="store_true",
                    help="make no further run once one exits non-zero")
    ap.add_argument("--candidate", action="store_true",
                    help="the cells are candidates: pass --candidate on")
    ap.add_argument("--keep-trace", action="store_true",
                    help="copy each traced run's benchmark/out files to --out")
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)
    rows = []
    for i, spec in enumerate(args.sets):
        cell, trace, seeds = spec.split(":")
        for seed in seeds.split(","):
            cmd = [sys.executable, "-m", "benchmark.run", "--workload", cell,
                   "--seed", seed, "--trace", trace]
            if args.seconds is not None:
                cmd += ["--seconds", str(args.seconds)]
            if args.candidate:
                cmd.append("--candidate")
            t = time.time()
            proc = subprocess.run(cmd, capture_output=True, text=True)
            wall = time.time() - t
            tag = f"{cell}-seed{seed}-trace{trace}"
            with open(os.path.join(args.out, tag + ".err"), "w") as f:
                f.write(proc.stderr[-20000:])
            lines = proc.stdout.strip().splitlines()
            try:
                line = json.loads(lines[-1])
            except (IndexError, ValueError):
                line = None
            row = {"set": i, "cell": cell, "trace": int(trace),
                   "seed": int(seed), "rc": proc.returncode,
                   "wall_s": wall, "line": line}
            rows.append(row)
            with open(os.path.join(args.out, "results.jsonl"), "a") as f:
                f.write(json.dumps(row) + "\n")
            print(json.dumps({k: row[k] for k in
                              ("set", "cell", "trace", "seed", "rc",
                               "wall_s")}), flush=True)
            for k in ("attempted", "failed", "requests_finished"):
                if line and k in line:
                    print(f"  {k} {line[k]}", flush=True)
            if args.keep_trace:
                src = os.path.join("benchmark", "out", tag + ".json")
                if os.path.isfile(src):
                    shutil.copy(src, args.out)
            if args.stop_on_failure and proc.returncode != 0:
                print(proc.stderr[-3000:])
                return 1
    print("\nset cell trace metric n median spread values")
    for i, spec in enumerate(args.sets):
        mine = [r for r in rows if r["set"] == i and r["line"]]
        names = sorted({m for r in mine for m in r["line"]["metrics"]})
        for name in names:
            vals = [r["line"]["metrics"][name]["value"] for r in mine
                    if name in r["line"]["metrics"]]
            print(i, spec.split(":")[0], spec.split(":")[1], name, len(vals),
                  f"{stats.median(vals):.6g}",
                  f"{stats.spread(vals):.4%}" if len(vals) > 2 else "-",
                  " ".join(f"{v:.6g}" for v in vals))
        bad = [r for r in rows if r["set"] == i and
               (r["rc"] != 0 or not r["line"] or not r["line"]["correct"])]
        if bad:
            print(i, "NOT CORRECT / FAILED:",
                  [(r["seed"], r["rc"]) for r in bad])
    return 0


if __name__ == "__main__":
    sys.exit(main())
