"""Hand tool: what a profiler trace holds — planes, lines, event counts,
the first events and the heaviest names of each line — as JSON, for
reading one trace by hand before trusting the reduction.

    python3 -m benchmark.tools.trace_dump <dir or .xplane.pb> <out.json>

A traced run leaves its trace in benchmark/.work/<cell>/trace until the
cell's next run.
"""

import json
import os
import sys

import jax.profiler

from benchmark.harness import tracered


def main(argv) -> int:
    path = argv[0] if argv[0].endswith(".pb") else None
    if path is None:
        files = [os.path.join(argv[0], f) for f in os.listdir(argv[0])
                 if f.endswith(".xplane.pb")]
        path = files[0] if files else tracered.find_xplane(argv[0])
    space = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in space.planes:
        lines = []
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events]
            first = list(line.events)[:1]
            lines.append({
                "line": line.name, "events": len(events),
                "first": events[:6],
                "first_stats": [[str(k), str(v)[:80]]
                                for e in first for k, v in e.stats][:20],
                "span": [min((e[1] for e in events), default=0),
                         max((e[1] + e[2] for e in events), default=0)],
                "top": tracered.top_by_name(
                    [(n, s * 1e-9, d * 1e-9) for n, s, d in events], 15)})
        out.append({"plane": plane.name, "lines": lines})
    with open(argv[1], "w") as f:
        json.dump(out, f, indent=1)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
