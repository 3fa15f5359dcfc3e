"""Kernels: device milliseconds per round of the liveness sweep (the
kernel named ``liveness``), from the trace, averaged over every round
of the traced scenarios, the rounds it skips included."""

from gossipbench import passes


def read(run):
    return passes.ms_per_round(run, ("liveness",))
