"""Device time of one kernel pass, from the trace's per-chip op totals.

The round's Pallas kernels carry their pass's name on the device
(``pallas_call(name=...)``: ``push``, ``push_self``, ``pull``,
``liveness``), and the chip's trace names a kernel's op after it
(``push.8``), so a leaf op's name says which pass ran.  A program whose
kernels carry no names (``body.16``) reads as no pass at all.
"""

from __future__ import annotations

import re

_KERNEL = re.compile(r"^(?P<name>[A-Za-z_]+)(\.\d+)? \(custom-call\)$")


def ms_per_round(run, names: tuple[str, ...]) -> float | None:
    """Milliseconds per round of the ``custom-call`` leaves named one of
    ``names``, a mean over the traced chips, over the rounds of the
    traced scenarios; None when the trace holds no such leaf."""
    if run.trace is None:
        return None
    per_chip = [[s for label, s in ops.items()
                 if (m := _KERNEL.match(label)) and m.group("name") in names]
                for ops in run.trace.op_s.values()]
    rounds = sum(s.rounds for s in run.scenarios
                 if s.index in run.trace.scenarios)
    if not any(per_chip) or rounds == 0:
        return None
    return 1000.0 * (sum(map(sum, per_chip)) / len(per_chip)) / rounds
