"""Kernels (``kernels/flash_decode``): percent of its roofline that the
fused paged decode kernel reaches.  The least time of each call is
``max(operations / peak bf16, bytes / HBM bandwidth)`` with the operations
and bytes of ``bench/flops.py`` (live K/V pages, queries and outputs), from
the context lengths the harness saw at each traced step: one call per layer
per decode step, and one per admission whose prefill ends in a one-token
chunk.  Its mean over calls, divided by the kernel's mean device time per
call in the trace."""
from bench import flops

# the program names no Pallas kernel, so the trace shows the fused paged
# decode as an anonymous custom call; on the one-chip served path it is the
# only Pallas kernel
KERNEL = r'custom_call_target="tpu_custom_call"'


def read(rec):
    tr = rec["trace"]
    if tr is None or rec["peaks"] is None:
        return None
    calls, secs = tr.kernel(KERNEL)
    if calls == 0 or secs <= 0:
        return None
    a, pk = rec["arch"], rec["peaks"]
    lo, hi = rec["trace_window"]
    least, n = 0.0, 0
    for s in rec["steps"]:
        if not (lo <= s.start and s.end <= hi):
            continue
        batches = [s.decode_lens] if s.decode_lens else []
        # a prefill whose remainder is odd ends in a one-token chunk
        batches += [[n] for n, m in s.shares if (n - m) % 2]
        for lens in batches:
            f, b = flops.paged_decode_call(a, lens, rec["page"], rec["slots"])
            least += a.layers * flops.least_time(f, b, pk["bf16_flops"],
                                                 pk["hbm_bytes_per_s"])
            n += a.layers
    if n == 0:
        return None
    return 100.0 * (least / n) / (secs / calls)
