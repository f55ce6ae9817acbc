"""Seconds before the window spent compiling programs or loading them
from the compile cache, from the program's compile log
(``repro.obs.compiles``, fed by JAX's compile events from the trainer's
construction on). Nothing where the program has no compile log."""
from chipbench import programtrace


def read(run):
    return programtrace.read(run).compile_s
