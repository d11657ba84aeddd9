"""Host milliseconds per warm adapt outside the device wait: the program's
``session/adapt`` span less its ``session/wait`` child
(``repro.core.trace``), averaged over the window's adapts -- the last N
such spans, N the adapts the window completed.  It holds the delta
ledger's plan, the batch upload and merge dispatch (``delta/*``) and, on
a session's first adapt, the ledger's build.  None where the program
records no spans."""


def read(run):
    try:
        from repro.core import trace
    except ImportError:
        return None
    n = len(run.records.get("adapt_iterations", []))
    calls = trace.spans("session/adapt")
    if not n or len(calls) < n:
        return None
    waits = {s.parent_id: s.duration_ns
             for s in trace.spans("session/wait")}
    host = [c.duration_ns - waits.get(c.span_id, 0) for c in calls[-n:]]
    return 1e-6 * sum(host) / n
