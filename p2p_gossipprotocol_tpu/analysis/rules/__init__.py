"""Rule modules — importing one registers its checks (core.rule).

One module per contract family; the catalog with each rule's origin
PR/doc lives in docs/STATIC_ANALYSIS.md.
"""

from p2p_gossipprotocol_tpu.analysis.rules import (  # noqa: F401
    autostatics, clamps, configsurface, fingerprint, imports, locks,
    tracing, writes)
