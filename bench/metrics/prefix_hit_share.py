"""Prefix index (``serving/kvcache.py`` PrefixIndex): percent of the prompt
tokens admitted in the window that were mapped from the index rather than
prefilled (the change of its ``tokens_matched`` counter over each step)."""


def read(rec):
    steps = [s for s in rec["steps"]
             if s.admitted and rec["t0"] <= s.start and s.end <= rec["t1"]]
    prompt = sum(rec["reqs"][rid].prompt_len for s in steps
                 for rid in s.admitted)
    if prompt == 0:
        return None
    return 100.0 * sum(s.matched for s in steps) / prompt
