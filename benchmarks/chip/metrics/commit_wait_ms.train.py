"""Mean trainer time blocked in the store per step, in ms: the store's
``vwait`` spans on the trainer's thread (txtrace, mapped onto the trace's
clock by the program's anchor) inside each ``train.commit`` annotation,
over the window (``chipbench.programtrace.commit_wait``). Nothing where
the program records no such events."""
from chipbench import programtrace


def read(run):
    ns = programtrace.commit_wait(programtrace.read(run))
    return None if ns is None else ns * 1e-6
