"""Pool layer (``serving/pool.py`` EnginePool): p90 of the wait from a
request's due time to the start of the ``Engine.step`` that admitted it,
over the requests due in the window; one not admitted when the window
closes counts with its wait so far.  Harness clock."""
from bench import stats


def read(rec):
    t0, t1 = rec["t0"], rec["t1"]
    waits = [(min(r.admit_step, t1) if r.admit_step is not None else t1)
             - r.due for r in rec["reqs"].values() if t0 <= r.due < t1]
    return stats.nearest_rank(waits, 0.90)
