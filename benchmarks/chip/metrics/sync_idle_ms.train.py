"""Mean device idle inside each of the program's ``train.loss_sync`` spans
(``Trainer.run``'s ``float(loss)``), in ms: the device trace's idle gaps
intersected with the spans, averaged over devices, over the window
(``chipbench.programtrace.idle_under``). Nothing where the program has no
such span."""
from chipbench import programtrace


def read(run):
    ns = programtrace.idle_under(programtrace.read(run), "train.loss_sync")
    return None if ns is None else ns * 1e-6
