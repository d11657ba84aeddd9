"""LPA iterations per partition in the window (PartitionResult.iterations),
their mean over the partitions completed."""


def read(run):
    its = run.records.get("iterations")
    return sum(its) / len(its) if its else None
