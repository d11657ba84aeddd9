"""Share of the roofline in the warm adapts: the least time their LPA
iterations' algorithmic work needs on this chip (``work.lpa_iteration``
over the graph each adapt ran on, against ``peaks.py``) over the device
time in the ``bench/adapt`` spans."""


def read(run):
    busy = run.trace.get("busy_in", {}).get("adapt")
    r = run.records
    its, entries = r.get("adapt_iterations", []), r.get("adapt_entries", [])
    if not busy or not its or len(entries) != len(its):
        return None
    least = sum(run.work.least_seconds(
        run.work.lpa_iteration(r["num_vertices"], e, r["k"]),
        run.peaks())[0] * i for e, i in zip(entries, its))
    return 100.0 * least / busy
