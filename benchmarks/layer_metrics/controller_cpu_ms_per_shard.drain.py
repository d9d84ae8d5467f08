"""Layer: HTTP front, queue, leases (controller/). CPU milliseconds of the
controller's own process per shard accepted in the window (user + system
time of the child, ``/proc/<pid>/stat``). Moves ``drain_rows_per_s``."""


def read(run):
    if run["kind"] != "drain" or not run["shards"]:
        return None
    return run["controller_cpu_s"] * 1e3 / run["shards"]
