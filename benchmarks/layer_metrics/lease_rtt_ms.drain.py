"""Layer: HTTP front, queue, leases (seen from the agent). Milliseconds of
one lease poll that came back with tasks, as the feeder thread pays it
(telemetry snapshot, the ``/v1/leases`` round trip, response handling): mean
of ``agent_lease_seconds{outcome="tasks"}`` over what the histogram gained
inside the window. Moves ``drain_rows_per_s``."""

from benchmarks.harness.stack import histogram_delta


def read(run):
    if run["kind"] != "drain":
        return None
    before, after = run["agent_metrics"]
    total, count = histogram_delta(before, after, "agent_lease_seconds",
                                   outcome="tasks")
    return total * 1e3 / count if count else None
