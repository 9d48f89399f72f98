"""Trips of the walk's device loop a dispatched batch: counter
`beam.trips_total` (each while loop's `live.max()`, from the count every
beam program returns with its answers; a chunked or segmented batch adds
up the loops it ran one after another, so it can read more than the
plan's T) over the batches the three walk drivers ran (counters
`beam.monolithic` + `beam.chunked` + `beam.segmented`).  A walk that
stops early reads low here.

PROCESS-CUMULATIVE, not the window's: the harness hands readers the
window's delta of spans, not of counters, so both counts run from the
start of the process — the warm-up's batches (one a bucket a pass) and the
check's are in both beside the window's.  All walk the cell's one plan
today; once walks end early on some batches this is the process's mean,
not the slice's.  None where the program counts no trips (before PR 32),
the cell walks no graph, or no batch was served."""

DRIVERS = ("beam.monolithic", "beam.chunked", "beam.segmented")


def read(run):
    from sptag_tpu.utils import metrics

    trips = metrics.counter_value("beam.trips_total")
    batches = sum(metrics.counter_value(d) for d in DRIVERS)
    if not trips or not batches or not run["spans"].get(
            "server.execute_batch"):
        return None
    return trips / batches
