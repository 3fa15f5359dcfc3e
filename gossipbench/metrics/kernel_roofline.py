"""Kernels: the compulsory bytes of the rounds run (gossipbench.roofline)
over the HBM peak times the kernels' device time, in percent.  On a
cell of several chips each chip moves its share of the bytes in its
own kernel time, the mean over the chips."""

from gossipbench import roofline


def read(run):
    if run.trace is None or run.trace.kernel_s <= 0:
        return None
    rounds = [s.rounds for s in run.scenarios if s.index in run.trace.scenarios]
    if not rounds:
        return None
    moved = roofline.rounds_bytes(
        rounds, n_peers=run.dep.n_peers,
        n_words=run.dep.n_words, n_slots=run.dep.n_slots,
        liveness_every=run.dep.liveness_every)
    return 100.0 * moved / (roofline.hbm_peak(run.device_kind)
                            * run.trace.kernel_s * run.dep.shards)
