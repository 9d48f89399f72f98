"""Write-ahead-log appends per mutation acknowledged in the window: spans
`index.wal_append` (the append and its fsync, `VectorIndex._wal_log`) /
spans `index.add` + `index.delete`.  Expected 1: every operation is
logged once before its reply.  None where the program has no such spans
(before PR 40) or no mutation ran."""


def read(run):
    s = run["spans"]
    ops = sum(s[n]["count"] for n in ("index.add", "index.delete") if n in s)
    appends = s.get("index.wal_append")
    return appends["count"] / ops if appends and ops else None
