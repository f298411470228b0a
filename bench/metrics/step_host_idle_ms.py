"""Device, seen from the engine (``Engine.step``): milliseconds per
``engine.step`` span in which the device ran nothing, with device times
shifted onto the host's clock (``bench/engine_trace.py``).  Nothing when the
trace holds no such span or the clock offset's bracket is empty."""


def read(rec):
    tr = rec.get("engine_trace")
    if tr is None or not tr.aligned:
        return None
    steps = len(tr.named("engine.step"))
    if steps == 0:
        return None
    return sum(tr.idle_by_span().values()) / steps * 1e3
