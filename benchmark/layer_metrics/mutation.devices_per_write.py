"""Devices whose block a mutation's device write touched, mean over the
window's mutations: record `mesh.write_devices`
(`trace.record_sum(name, devices written, 1)` in
`ShardedFlatIndex._device_append` / `_device_mask`), total / count.
Expected 1: an add's write rung goes whole to ONE shard, and a delete's
mask write touches the shards that own its rows (this traffic deletes the
rung it added: one).  The number of chips would read where every write
re-placed or rewrote the whole mesh block.  None where the program has no
such record (before PR 43, or an index on one chip) or nothing was
mutated."""


def read(run):
    r = run["spans"].get("mesh.write_devices")
    return r["total_s"] / r["count"] if r and r["count"] else None
