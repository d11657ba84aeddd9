"""Share of the roofline in the partition calls: the least time one LPA
iteration's algorithmic work needs on this chip (``work.lpa_iteration``
against ``peaks.py``) over the device time per iteration."""


def read(run):
    busy = run.trace.get("busy_in", {}).get("partition")
    its = sum(run.records.get("iterations", []))
    if not busy or not its:
        return None
    r = run.records
    least, _ = run.work.least_seconds(
        run.work.lpa_iteration(r["num_vertices"], r["num_entries"], r["k"]),
        run.peaks())
    return 100.0 * least * its / busy
