"""Prefix index (``serving/kvcache.py`` PrefixIndex): percent of the prompt
tokens of the requests admitted in the window that were mapped from the
index, by each request's ``RequestState.prefix_matched`` count.  Program
counter; nothing when the records carry no counts."""


def read(rec):
    t0, t1 = rec["t0"], rec["t1"]
    admitted = [r for r in rec["reqs"].values()
                if getattr(r, "admitted_at", None) is not None
                and t0 <= r.admitted_at < t1]
    prompt = sum(r.prompt_len for r in admitted)
    if prompt == 0:
        return None
    return 100.0 * sum(r.prefix_matched for r in admitted) / prompt
