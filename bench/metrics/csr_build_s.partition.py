"""Seconds of the host CSR build of the cell's graph: the program's last
``graph/from_edges`` span (``repro.core.trace``) that ends before the
window's first ``session/partition`` span.  None where the program
records no spans."""


def read(run):
    try:
        from repro.core import trace
    except ImportError:
        return None
    n = len(run.records.get("iterations", []))
    calls = trace.spans("session/partition")
    if not n or len(calls) < n:
        return None
    first = calls[-n].start_ns
    builds = [s for s in trace.spans("graph/from_edges")
              if s.end_ns <= first]
    if not builds:
        return None
    return 1e-9 * max(builds, key=lambda s: s.end_ns).duration_ns
