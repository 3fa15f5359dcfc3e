"""Kernels: device milliseconds per round of the anti-entropy pull pass
(the kernel named ``pull``), from the trace, over the rounds of the
traced scenarios."""

from gossipbench import passes


def read(run):
    return passes.ms_per_round(run, ("pull",))
