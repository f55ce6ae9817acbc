"""Mean device time per step of the operations under the train step's
``optimizer`` scope, in ms: busy time split into ``loss``, ``optimizer``
and ``other`` by each operation's op name from the profile's HLO
(``chipbench.programtrace.phases``), over the steps of the window. Nothing
where no operation carries either scope; where the profile holds the train
step's HLO all the same, a printed line says so: an executable built
without the scopes was run, as one loaded from a compile cache that such
a build filled under the same program name would be."""
from chipbench import programtrace


def read(run):
    p = programtrace.read(run)
    ph = programtrace.phases(p)
    if not ph["loss"] + ph["optimizer"]:
        if any("train_step" in name for name in p.scopes.values()):
            print("[bench] optimizer_ms.train: the train step's HLO in the "
                  "profile carries neither the loss nor the optimizer "
                  "scope; no reading", flush=True)
        return None
    return ph["optimizer"] * 1e-6 / run["steps"]
