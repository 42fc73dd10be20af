"""Device seconds of stage 2, the chase with tape per reduction (s): the busy
time of chip 0 under the program's ``repro.stage2`` scope in the traced
window (``scope_s`` of ``bench/scopes.py``), over the reductions the window
held.  None where the trace has no such scope, as on a program without it."""


def read(run):
    trace, count = run.get("trace"), run["readings"].get("reductions")
    seconds = (trace or {}).get("scope_s", {}).get("repro.stage2")
    if seconds is None or not count:
        return None
    return seconds / count
