"""Flight-recorder telemetry plane (docs/OBSERVABILITY.md).

One low-overhead, host-side-only observability layer shared by every
engine, the serving plane, and the supervisor:

* :mod:`~p2p_gossipprotocol_tpu.telemetry.recorder` — process-wide
  spans (``run`` > ``chunk``, ``init_state``, ``run_to_coverage``,
  serve ``request``), mirrored into a ``jax.profiler`` trace as
  ``gossip.<name>`` when the JAX side installs the mirror, the
  always-on typed event ledger (clamps, fallbacks, deaths), counters/
  gauges, the bounded flight-recorder ring with atomic dumps, and the
  Prometheus-style ``/metrics`` renderer;
* :mod:`~p2p_gossipprotocol_tpu.telemetry.roofline` — per-chunk
  reconciliation of the in-kernel census against ``traffic_model()``:
  a live ``roofline_frac`` and modeled-vs-achieved drift;
* :mod:`~p2p_gossipprotocol_tpu.telemetry.traceview` — the
  ``jax.profiler`` trace summarizer (top leaf ops by device time, and
  the device's idle time inside each ``gossip.`` span) behind both
  ``benchmarks/trace_top.py`` and the serve ``profile`` document.

Observational by contract: this package never imports jax, telemetry
is off by default (``telemetry=1`` / ``--telemetry`` /
``GOSSIP_TELEMETRY=1``), results are bitwise-identical on or off, and
the ``telemetry_*`` config keys never enter checkpoint fingerprints.
"""

from p2p_gossipprotocol_tpu.telemetry.recorder import (Recorder,
                                                       classify_clamp,
                                                       configure_from_config,
                                                       env_enabled,
                                                       recorder)
from p2p_gossipprotocol_tpu.telemetry.roofline import RooflineTracker

__all__ = ["Recorder", "RooflineTracker", "classify_clamp",
           "configure_from_config", "env_enabled", "recorder",
           "record_clamps", "event", "span", "counter_add", "gauge_set",
           "gauge_get", "dump"]


# module-level conveniences over the process singleton — call sites
# read ``telemetry.event(...)`` instead of threading a recorder around
def record_clamps(texts, scope=None):
    recorder().record_clamps(texts, scope=scope)


def event(kind, **fields):
    return recorder().event(kind, **fields)


def span(name, span_id=None, **attrs):
    return recorder().span(name, span_id=span_id, **attrs)


def counter_add(name, value=1.0):
    recorder().counter_add(name, value)


def gauge_set(name, value):
    recorder().gauge_set(name, value)


def gauge_get(name, default=None):
    return recorder().gauge_get(name, default)


def dump(reason, directory=None, path=None):
    return recorder().dump(reason, directory=directory, path=path)
