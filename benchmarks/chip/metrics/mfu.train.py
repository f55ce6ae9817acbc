"""Model-FLOP utilization of the whole training window, in %.

The operations one step needs (6 x matmul weights x tokens plus the
attention or WKV products; remat's second forward not counted,
``chipbench.flops.train_step_flops``), times the steps the window's
``Trainer.run`` completed, over the window's host-clock time, the chips and
the chip's peak. Every host stall counts against it."""


def read(run):
    done = run["step_flops"] * run["steps"]
    return 100.0 * done / (run["wall_s"] * run["chips"] * run["peaks"].flops)
