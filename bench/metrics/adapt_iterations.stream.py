"""LPA iterations per warm adapt in the window (PartitionResult.iterations),
their mean over the adapts completed."""


def read(run):
    its = run.records.get("adapt_iterations")
    return sum(its) / len(its) if its else None
