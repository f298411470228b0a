"""Engine prefill (``Engine._prefill_into_slot``): milliseconds per thousand
prompt tokens prefilled, from the ``engine.prefill`` spans of the traced
window: their summed durations over their summed ``tokens`` args (prompt
tokens less those mapped from the prefix index).  Nothing when the trace
holds no such span."""


def read(rec):
    tr = rec.get("engine_trace")
    if tr is None:
        return None
    spans = tr.named("engine.prefill")
    tokens = sum(int(s.stats.get("tokens", 0)) for s in spans)
    if tokens <= 0:
        return None
    return sum(s.seconds for s in spans) / tokens * 1e6
