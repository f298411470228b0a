"""Pool layer, read at admission: p90 of the wait from a request's due time
to its ``RequestState.admitted_at`` stamp (``Engine._prefill_into_slot``),
over the requests due in the window; one not admitted when the window
closes counts with its wait so far.  Program stamp, host monotonic clock;
nothing when the records carry no stamps."""
from bench import stats


def read(rec):
    t0, t1 = rec["t0"], rec["t1"]
    due = [r for r in rec["reqs"].values() if t0 <= r.due < t1]
    if not any(hasattr(r, "admitted_at") for r in due):
        return None
    at = [getattr(r, "admitted_at", None) for r in due]
    waits = [(min(a, t1) if a is not None else t1) - r.due
             for a, r in zip(at, due)]
    return stats.nearest_rank(waits, 0.90)
