"""Device milliseconds per LPA iteration: device busy time inside the
window's ``bench/partition`` spans over the iterations they ran."""


def read(run):
    busy = run.trace.get("busy_in", {}).get("partition")
    its = sum(run.records.get("iterations", []))
    return 1e3 * busy / its if busy and its else None
