"""Share of each device's traced window spent in collective operations
while no other operation runs on that device, in %, averaged over the
devices of the trace.

A collective is a device op whose instruction name
(``programtrace.short_name``) starts with ``all-gather``, ``all-reduce``,
``reduce-scatter``, ``all-to-all``, ``collective-permute`` or
``collective-broadcast``: the synchronous op, its asynchronous halves
(``-start``, ``-done``) and the fusions named after them
(``all-reduce-scatter-fusion``). Other ops run beside it unless they are
control flow that holds it (``while``, ``conditional``, ``call``). An
asynchronous collective shows only as its two halves: the transfer in
flight between them is no op of the trace, so what it overlaps is not
counted, and what shows is the launch and the wait in ``-done``, the part
the device could not hide. The window is the run's traced window (the
``window`` annotation, or the host clock's length where the profiler drops
it). Nothing where no collective op is found.
"""
import re

from chipbench import programtrace, tracefile

COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute|collective-broadcast)([.-]|$)")
HOLDS = re.compile(r"^(while|conditional|call)([.-]|$)")


def read(run):
    lo, hi = run["trace_window"]
    ops = run["trace"].ops
    shares, found = [], {}
    for evs in ops.values():
        coll, other = [], []
        for ev in evs:
            name = programtrace.short_name(ev[0])
            if COLLECTIVE.match(name):
                coll.append(ev)
                inside = min(ev[2], hi) - max(ev[1], lo)
                if inside > 0:
                    kind = re.sub(r"\.\d+$", "", name)
                    found[kind] = found.get(kind, 0.0) + inside
            elif not HOLDS.match(name):
                other.append(ev)
        c = tracefile.union(coll, lo, hi)
        shown = sum(b - a for a, b in c) - programtrace.overlap(
            c, tracefile.union(other, lo, hi))
        shares.append(shown / (hi - lo))
    if not found:
        return None
    print("[bench] collective_exposed_share: collective ops in the window "
          "(device seconds, all devices): " + ", ".join(
              f"{k} {v * 1e-9:.4f}" for k, v in sorted(
                  found.items(), key=lambda kv: -kv[1])), flush=True)
    return 100.0 * sum(shares) / len(shares)
