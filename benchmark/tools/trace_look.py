"""Print the structure of a profiler trace, for reading one by hand.

    python -m benchmark.tools.trace_look <dir-or-.xplane.pb> [--top 25]

Planes, their lines with event counts, and per line the names that took
most time (with the stat keys of one event). This is how the names in
``benchmark/trace_reduce.py`` were chosen; run it again when a JAX or
libtpu upgrade changes how the device plane is laid out. Reads with
``jax.profiler.ProfileData`` alone and touches no device.
"""

import argparse
import collections
import glob
import json
import os
import sys


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def summarize(xplane_path, top=25):
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    out = {"file": xplane_path, "bytes": os.path.getsize(xplane_path),
           "planes": []}
    for plane in data.planes:
        p = {"name": plane.name, "lines": []}
        for line in plane.lines:
            dur = collections.Counter()
            cnt = collections.Counter()
            first, last, sample_stats = None, None, None
            n = 0
            for ev in line.events:
                n += 1
                dur[ev.name] += ev.duration_ns
                cnt[ev.name] += 1
                if first is None:
                    first = ev.start_ns
                    sample_stats = {k: str(v)[:80] for k, v in ev.stats}
                last = ev.start_ns + ev.duration_ns
            p["lines"].append({
                "name": line.name, "events": n,
                "span_ns": None if first is None else last - first,
                "sample_stats": sample_stats,
                "top": [[name[:120], cnt[name], ns]
                        for name, ns in dur.most_common(top)]})
        out["planes"].append(p)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    json.dump(summarize(find_xplane(args.path), args.top), sys.stdout,
              indent=1)
    print()


if __name__ == "__main__":
    main()
