"""Readers of per-layer metrics: each takes one number out of a run's
facts (counters' deltas over the window, the reduced device trace, the
window's own counts) or returns None where there is nothing to read."""


def lookup(facts: dict, path: str):
    """``a/b/c`` into nested dicts; None where any step is missing."""
    cur = facts
    for key in path.split("/"):
        if not isinstance(cur, dict) or key not in cur:
            return None
        cur = cur[key]
    return cur
