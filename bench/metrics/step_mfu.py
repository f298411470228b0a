"""Model step (``models/lm.py`` paged_step): percent of the chip's bf16
peak that the useful work of the window's engine steps reaches.  Useful
work (``bench/flops.py``) is every prompt token prefilled and every token
decoded, with top-k experts only and the head only where its logits are
used; time is the summed host wall of those ``Engine.step`` calls."""
from bench import flops


def read(rec):
    if rec["peaks"] is None:
        return None
    a = rec["arch"]
    work = wall = 0.0
    for s in rec["steps"]:
        if not (rec["t0"] <= s.start and s.end <= rec["t1"]):
            continue
        for n, m in s.shares:
            work += flops.prefill_flops(a, m, n - m)
        work += sum(flops.decode_flops(a, n) for n in s.decode_lens)
        wall += s.end - s.start
    if wall == 0:
        return None
    return 100.0 * work / wall / rec["peaks"]["bf16_flops"]
