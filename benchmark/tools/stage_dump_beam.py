"""Hand tool: `tools/stage_dump.py` for a beam cell — device seconds of a
traced run's slice by the walk's named stages (`beam.seed`, `beam.gather`,
`beam.score`, `beam.merge`, `beam.finalize`: `jax.named_scope`s of
sptag_tpu/algo/engine.py), and each operation's name stack, as JSON.

    python3 -m benchmark.tools.stage_dump_beam <cell> [<out.json>]

harness/scopes.py keeps the stage names of the FLAT and dense programs in
one tuple that its functions read; this tool adds the walk's to it for
the length of its own process and changes nothing on disk.
"""

import sys

from benchmark.harness import scopes
from benchmark.tools import stage_dump

BEAM_STAGES = ("beam.seed", "beam.gather", "beam.score", "beam.merge",
               "beam.finalize")


def main(argv) -> int:
    scopes.STAGES = tuple(scopes.STAGES) + BEAM_STAGES
    return stage_dump.main(argv)


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
