"""Engine decode (``Engine.step``): median host wall, in milliseconds, of
the window's steps that admitted nothing and decoded.  Harness clock."""
from bench import stats


def read(rec):
    walls = [(s.end - s.start) * 1e3 for s in rec["steps"]
             if not s.admitted and s.decode_lens
             and rec["t0"] <= s.start and s.end <= rec["t1"]]
    return stats.median(walls)
