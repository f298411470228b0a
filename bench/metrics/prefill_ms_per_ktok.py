"""Engine prefill (``Engine._paged_prefill``): milliseconds per thousand
prompt tokens prefilled.  For each step of the window that admitted
requests: from the step's start to the first token of its last admission;
summed, over the prompt tokens those admissions prefilled (tokens mapped
from the prefix index are not prefilled).  Harness clock."""


def _in_window(rec, s):
    return rec["t0"] <= s.start and s.end <= rec["t1"]


def read(rec):
    steps = [s for s in rec["steps"] if s.admitted and _in_window(rec, s)]
    secs = sum(s.last_first - s.start for s in steps)
    tokens = sum(rec["reqs"][rid].prompt_len for s in steps
                 for rid in s.admitted) - sum(s.matched for s in steps)
    return secs / tokens * 1e6 if tokens > 0 else None
