"""Device-idle milliseconds per segment boundary: the idle time of the
chip inside the harness's span around each ``EngineRun.step()``, over the
boundaries in the traced window.  The host's boundary work (growth,
admission bookkeeping, block tables, the segment's dispatch and sync) is
what leaves the chip idle there."""


def read(rec):
    if rec.trace is None or not rec.trace.ops.names:
        return None
    idle, n = rec.trace.idle_in("chipbench.boundary")
    return idle / n * 1e3 if n else None
