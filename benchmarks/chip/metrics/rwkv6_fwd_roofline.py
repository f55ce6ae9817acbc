"""Share of its roofline the RWKV-6 WKV forward kernel reaches, in %.

The least time one call could take is the larger of its operations over the
chip's peak FLOP/s and its bytes over peak HBM bandwidth, both from the
shapes and dtypes the model passes to ``ops.rwkv6_scan`` (bf16 r, k, v;
float32 w, u and state; ``chipbench.flops``), not from the kernel's own
casts. The share is that least time, times the calls seen, over the summed
device time of the kernel's events in the window: the Pallas custom call
whose outputs are y [B*H, T, hd] and the final state [B*H, hd, hd], both
float32. A trace without the kernel gives nothing. Bound: memory (about 8x
more time on bytes than on operations at these shapes)."""
from chipbench import flops, tracefile


def pattern(bh: int, hd: int) -> str:
    return (rf"^\S+ = \(f32\[{bh},\d+,{hd}\].*?, f32\[{bh},{hd},{hd}\]\S*\) "
            rf"custom-call\(.*tpu_custom_call")


def read(run):
    cfg, tr = run["config"], run["traffic"]
    lo, hi = run["trace_window"]
    B, T = tr["batch"], tr["seq"]
    H, hd = cfg["num_attention_heads"], cfg["head_size"]
    durs = tracefile.matching(run["trace"].ops, pattern(B * H, hd), lo, hi)
    if not durs:
        return None
    pk = run["peaks"]
    least = max(flops.wkv_fwd_flops(B, T, H, hd) / pk.flops,
                flops.wkv_fwd_bytes(B, T, H, hd, 2, 4) / pk.hbm_bw)
    return 100.0 * least * len(durs) / (sum(durs) * 1e-9)
