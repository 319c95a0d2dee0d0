"""Rebuild requests per rebuild started in the window (the shared
``SurfaceRebuilder``'s ``requests`` / ``builds_started`` deltas)."""


def read(run):
    rec = run.records
    if rec["builds"] == 0:
        return None
    return rec["requests"] / rec["builds"]
