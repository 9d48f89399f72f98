"""Reductions over the program's per-batch stage spans (PR 25).

A stage span (`service.parse`, `index.search`, ...) may occur more than
once a batch, or in calls no served batch made, so a stage's cost per
batch is its total over the window / the `server.execute_batch` spans of
the window.  Where the program has no such span — the parent of the PR
that added it — there is nothing to read and the answer is None.
"""

BATCH_SPAN = "server.execute_batch"


def per_batch_ms(run: dict, name: str, minus: str = None):
    """Total seconds of span `name` over the window (less those of span
    `minus`, which lies inside it) per executed batch, in ms."""
    s, b = run["spans"].get(name), run["spans"].get(BATCH_SPAN)
    if not s or not b:
        return None
    total = s["total_s"]
    if minus is not None:
        inner = run["spans"].get(minus)
        if not inner:
            return None
        total -= inner["total_s"]
    return 1e3 * total / b["count"]
