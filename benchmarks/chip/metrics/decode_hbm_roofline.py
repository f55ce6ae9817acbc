"""Share of HBM bandwidth the whole decode step reaches, in %.

Bytes one decode step needs: every weight once (bf16) and the keys and
values of the live positions of the live slots only (``chipbench.flops.
decode_step_bytes``), from the harness's own record of which requests held
a slot, and how long their context was, at each call. Divided by 819 GB/s
(the chip's peak), over the mean device time of the decode program per call
in the window's trace."""
import bisect

from chipbench import flops, tracefile

PROGRAM = r"decode_step"


def read(run):
    lo, hi = run["trace_window"]
    durs = tracefile.matching(run["trace"].modules, PROGRAM, lo, hi)
    w0, w1 = run["window"]
    calls = [t for t in run["decode_calls"] if w0 <= t <= w1]
    if not durs or not calls:
        return None
    need = 0.0
    reqs = [(r.out.stamps, len(r.prompt), r.max_new) for r in run["requests"]
            if r.out.stamps]
    for t in calls:
        ctx = []
        for st, p, n in reqs:
            k = bisect.bisect_right(st, t)
            if 0 < k < n:
                ctx.append(p + k)
        need += flops.decode_step_bytes(run["config"], ctx)
    per_call_need = need / len(calls)
    per_call_time = sum(durs) * 1e-9 / len(durs)
    return 100.0 * per_call_need / run["peaks"].hbm_bw / per_call_time
