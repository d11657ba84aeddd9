"""Device milliseconds per LPA iteration of the warm adapts: device busy
time inside the window's ``bench/adapt`` spans (the delta merge included)
over the iterations they ran."""


def read(run):
    busy = run.trace.get("busy_in", {}).get("adapt")
    its = sum(run.records.get("adapt_iterations", []))
    return 1e3 * busy / its if busy and its else None
