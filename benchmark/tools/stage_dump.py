"""Hand tool: device seconds of a traced run's slice by the program's
named stages (benchmark/harness/scopes.py), and each operation's name
stack, as JSON.

    python3 -m benchmark.tools.stage_dump <cell> [<out.json>]

Reads the profile the cell's last `--trace 1` run left in
benchmark/.work/<cell>/trace.
"""

import json
import sys

from benchmark.harness import scopes, tracered


def main(argv) -> int:
    path = tracered.find_xplane(scopes.trace_dir(argv[0]))
    names = scopes.op_scopes(path)
    out = {"stages": scopes.seconds_by_stage(tracered.read_xplane(path),
                                             names),
           "operations": {op[:120]: scope for op, scope in names.items()}}
    if len(argv) > 1:
        with open(argv[1], "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out["stages"]))
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
