"""Device idle share of the traced serving window, in %: one minus the
union of device-operation intervals over the window, averaged over the
cell's devices (``chipbench.tracefile.reduce``)."""


def read(run):
    return 100.0 * run["reduced"]["idle_share"]
