"""The rows a writing loop streams, as a function of the query texts.

No seed reaches a loop: the load generator gets the pre-encoded query
texts and the traffic mix.  So the streamed rows are made from the
queries themselves, parsed back from their text (`serving.query_text`
writes `repr(float(x))`, which reads back to the same float32), and the
check, which holds the queries as an array, makes the very same rows
again.  Imports nothing of the program.
"""

import numpy as np


def query_vector(text: str) -> np.ndarray:
    """The float32 vector of a query text (`$option:value ... v|v|v`)."""
    return np.asarray([float(v) for v in text.rsplit(" ", 1)[1].split("|")],
                      np.float32)


def index_name(text: str) -> str:
    """The `$indexname:` of a query text."""
    for word in text.split(" "):
        if word.startswith("$indexname:"):
            return word[len("$indexname:"):]
    raise ValueError("query text names no index")


def streamed_rows(queries: np.ndarray, step: int, rows: int,
                  sigma: float) -> np.ndarray:
    """The (rows, dim) float32 block of step `step`: row j is query
    `(rows * step + j) mod len(queries)` plus `sigma` x a standard normal
    vector drawn from `default_rng([step, j])`."""
    dim = queries.shape[1]
    out = np.empty((rows, dim), np.float32)
    for j in range(rows):
        noise = np.random.default_rng([step, j]).standard_normal(dim)
        out[j] = (queries[(rows * step + j) % len(queries)]
                  + sigma * noise).astype(np.float32)
    return out
