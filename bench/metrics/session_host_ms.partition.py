"""Host milliseconds per partition call outside the device wait: the
program's ``session/partition`` span less its ``session/wait`` child
(``repro.core.trace``), averaged over the window's calls -- the last N
such spans, N the partitions the window completed.  None where the
program records no spans."""


def read(run):
    try:
        from repro.core import trace
    except ImportError:
        return None
    n = len(run.records.get("iterations", []))
    calls = trace.spans("session/partition")
    if not n or len(calls) < n:
        return None
    waits = {s.parent_id: s.duration_ns
             for s in trace.spans("session/wait")}
    host = [c.duration_ns - waits.get(c.span_id, 0) for c in calls[-n:]]
    return 1e-6 * sum(host) / n
