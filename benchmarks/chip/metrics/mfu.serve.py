"""Model-FLOP utilization of the traced serving window, in %.

Operations the served tokens needed: each prefill that finished in the
window (2 x matmul weights x prompt tokens plus causal attention) and each
decoded token stamped in it (2 x matmul weights plus attention over its
context; ``chipbench.flops.serve_flops``), over the window's host-clock
time, the chips and the chip's peak."""
from chipbench import flops


def read(run):
    cfg = run["config"]
    lo, hi = run["window"]
    total = 0.0
    for r in run["requests"]:
        p = len(r.prompt)
        for j, t in enumerate(r.out.stamps):
            if not lo <= t <= hi:
                continue
            if j == 0:
                total += flops.serve_flops(cfg, p, flops.causal_pairs(p))
            else:
                total += flops.serve_flops(cfg, 1, p + j)
    if total == 0:
        return None
    return 100.0 * total / ((hi - lo) * run["chips"] * run["peaks"].flops)
