"""Kernels: device milliseconds per round of the push pass (the
kernels named ``push`` and, on the sharded overlap path,
``push_self``), from the trace, over the rounds of the traced
scenarios."""

from gossipbench import passes


def read(run):
    return passes.ms_per_round(run, ("push", "push_self"))
