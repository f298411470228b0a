"""Engine decode (``Engine.step``): median duration, in milliseconds, of the
traced window's ``engine.decode`` spans (building the decode inputs and
mapping pages, the dispatch, and the host fetch of the next tokens).
Nothing when the trace holds no such span."""
from bench import stats


def read(rec):
    tr = rec.get("engine_trace")
    if tr is None:
        return None
    return stats.median([s.seconds * 1e3 for s in tr.named("engine.decode")])
