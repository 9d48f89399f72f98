"""Seconds of index_builder.main for the index this run serves: built in
this run, or recorded beside the cached folder when its seed's first run
built it."""


def read(run):
    return run["build_seconds"]
