"""Share of the dispatched batch slots that held a request, over the
window (%): the engine's ``served_slots / (served_slots + padded_slots)``,
taken as differences of its counters at the window's start and end."""


def read(run):
    snaps = run["readings"].get("serve_metrics")
    if not snaps:
        return None
    a, b = snaps["start"], snaps["end"]
    served = b["served_slots"] - a["served_slots"]
    slots = served + b["padded_slots"] - a["padded_slots"]
    return 100.0 * served / slots if slots else None
