"""The flight-recorder telemetry plane: spans, events, counters, dumps.

One process-wide :class:`Recorder` (module singleton, :func:`recorder`)
shared by every layer — the solo/sharded chunk runner
(utils/checkpoint.run_chunked), the fleet engine, the serving loop, the
supervisor, and the CLI.  Three instruments:

* **Spans** — nested host-side timing scopes (``run`` > ``chunk``;
  ``init_state`` and ``run_to_coverage`` with their steps; serve
  ``request`` with its enqueue→admit→converge→result ledger).  Span
  ids are stable: ``<name>:<seq>`` by default, caller-chosen for
  identities that must survive a resume (a served request's span id is
  ``request:<rid>``).  Completed spans land in the bounded ring.  Every
  span is also handed to the *trace mirror* when one is installed
  (:meth:`Recorder.set_mirror`): the JAX side installs
  ``jax.profiler.TraceAnnotation``, so under a profiler session each
  span appears in the device trace as ``gossip.<name>``, on the same
  clock as the device's ops, whether or not the recorder is enabled.
* **Events** — the typed ledger that absorbs the repo's scattered
  "recorded clamp" strings (auto-select degrades, frontier/hier/
  overlap illegal combos, probe fallback, spmd fallback) into one
  queryable stream.  The ledger is ALWAYS on — events are rare,
  host-only, and a post-mortem without its degradation history is
  blind — while spans and counters are gated on ``enabled``.
* **Counters + gauges** — cumulative counters (``rounds_total``,
  ``model_bytes_total``, ...) and instantaneous gauges
  (``roofline_frac``, ``supervise_heartbeat_age_s``), rendered as a
  Prometheus-style text page by :meth:`Recorder.render_metrics` (the
  serve server's ``metrics`` document).

The **flight recorder** is the bounded ring of recent spans + the
event ledger + a counter snapshot, dumped atomically
(:meth:`Recorder.dump`) on crash (``install_crash_dump``), on SIGTERM
salvage (the CLI/serve/worker exit-75 paths), on supervisor-detected
worker death, and on demand (the serve ``flight`` document).

Telemetry is observational BY CONTRACT:

* zero device computation — this module never imports jax (the trace
  mirror is a plain callable the JAX side hands in); every
  instrument is host-side bookkeeping around already-materialized
  values, so compiled programs (``FleetBucket.trace_count``) and
  results are bit-for-bit identical with telemetry on or off
  (tests/test_telemetry.py);
* off by default — ``telemetry=1`` (config), ``--telemetry`` (CLI), or
  ``GOSSIP_TELEMETRY=1`` (env) enable it; when off, ``span()`` returns
  the mirror's marker alone (a no-op outside a profiler session) or a
  shared no-op, and counters return immediately;
* excluded from checkpoint fingerprints — the ``telemetry_*`` config
  keys never enter ``engines.config_keys``, like ``fuse_update`` and
  the other how-not-what knobs.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from collections import Counter, deque

_TRUTHY = ("1", "true", "on", "yes")

#: prefix of a span's name in the profiler's trace (the harness under
#: ``gossipbench/`` marks its own spans ``gossipbench.``)
TRACE_PREFIX = "gossip."

#: clamp-site classification: the FIRST matching substring names the
#: site, so every existing "recorded clamp" string maps to exactly one
#: typed event (tests/test_telemetry.py pins one event per named site).
_CLAMP_SITES = (
    # order matters: a clamp string may NAME another knob in its
    # explanation (the sir_fuse degrade mentions block_perm), so the
    # most specific sites come first
    ("sir_fuse", "sir_fuse"),
    ("frontier_mode", "frontier"),
    ("overlap_mode", "overlap"),
    ("hier_", "hier"),
    ("mesh_devices", "mesh_fallback"),
    ("n_messages", "msg_cap"),
    ("avg_degree", "degree_cap"),
    ("graph ", "graph_subst"),
    ("block_perm", "auto_select"),
    ("pull_window", "auto_select"),
)


def classify_clamp(text: str) -> str:
    """The clamp site a recorded-clamp string belongs to (``other``
    when no pattern matches — a new clamp site should add its pattern
    to :data:`_CLAMP_SITES` so its events stay queryable by site)."""
    for pattern, site in _CLAMP_SITES:
        if pattern in text:
            return site
    return "other"


class _NoopSpan:
    """Shared do-nothing context manager — the zero-overhead ``with``
    body when telemetry is off."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopSpan()


class _Span:
    """One live span: closes into the recorder's ring on ``__exit__``;
    ``mark`` is the trace mirror's marker (or the shared no-op)."""

    __slots__ = ("rec", "name", "sid", "parent", "attrs", "t0", "mark")

    def __init__(self, rec: "Recorder", name: str, sid: str,
                 parent: str | None, attrs: dict, mark):
        self.rec = rec
        self.name = name
        self.sid = sid
        self.parent = parent
        self.attrs = attrs
        self.t0 = 0.0
        self.mark = mark

    def __enter__(self):
        self.rec._push(self.sid)
        self.mark.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self.t0
        self.mark.__exit__(exc_type, exc, tb)
        self.rec._pop()
        self.rec._close_span(self, dur, failed=exc_type is not None)
        return False


class Recorder:
    """Process-wide telemetry state (see module docstring)."""

    def __init__(self, ring: int = 4096):
        self._lock = threading.RLock()
        self.enabled = False
        self.dump_dir: str | None = None
        self.ring = max(1, int(ring))
        self._events: deque = deque(maxlen=self.ring)
        self._spans: deque = deque(maxlen=self.ring)
        self._counters: dict[str, float] = {}
        self._gauges: dict[str, float] = {}
        self._event_kinds: Counter = Counter()
        self._span_names: Counter = Counter()
        self._seq = 0
        self._local = threading.local()
        self._t0 = time.time()
        self._crash_hook_installed = False
        self._mirror = None

    # -- configuration --------------------------------------------------
    def configure(self, enabled: bool | None = None,
                  ring: int | None = None,
                  dump_dir: str | None = None) -> "Recorder":
        with self._lock:
            if enabled is not None:
                self.enabled = bool(enabled)
            if ring is not None and int(ring) != self.ring:
                self.ring = max(1, int(ring))
                self._events = deque(self._events, maxlen=self.ring)
                self._spans = deque(self._spans, maxlen=self.ring)
            if dump_dir is not None:
                self.dump_dir = dump_dir or None
        return self

    def reset(self) -> None:
        """Drop all recorded state (tests; config survives)."""
        with self._lock:
            self._events.clear()
            self._spans.clear()
            self._counters.clear()
            self._gauges.clear()
            self._event_kinds.clear()
            self._span_names.clear()
            self._seq = 0
            self._t0 = time.time()

    def _next_seq(self) -> int:
        with self._lock:
            self._seq += 1
            return self._seq

    # -- events (always on) ---------------------------------------------
    def event(self, kind: str, **fields) -> dict:
        """Record one typed event into the ledger.  Always on — the
        ledger is what makes a dump a post-mortem, and events are rare
        host-side facts (clamps, fallbacks, deaths), never per-round
        traffic."""
        ev = {"seq": self._next_seq(), "ts": time.time(),
              "kind": kind, **fields}
        with self._lock:
            self._events.append(ev)
            self._event_kinds[kind] += 1
        return ev

    def record_clamps(self, texts, scope: str | None = None) -> None:
        """One typed ``clamp`` event per recorded-clamp string —
        the chokepoint helper ``engines.build_simulator`` and the serve
        admission path call, so every scattered clamp site emits
        through one ledger without touching the sites themselves."""
        for t in texts:
            fields = {"site": classify_clamp(t), "detail": t}
            if scope:
                fields["scope"] = scope
            self.event("clamp", **fields)

    def events(self, kind: str | None = None) -> list[dict]:
        with self._lock:
            evs = list(self._events)
        return evs if kind is None else [e for e in evs
                                         if e["kind"] == kind]

    # -- spans -----------------------------------------------------------
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, sid: str) -> None:
        self._stack().append(sid)

    def _pop(self) -> None:
        st = self._stack()
        if st:
            st.pop()

    def set_mirror(self, mirror) -> None:
        """Install the trace mirror: ``mirror(trace_name)`` returns a
        context manager that marks the span on the profiler's clock
        (``jax.profiler.TraceAnnotation``; ``device.install_trace_mirror``
        installs it).  ``None`` removes it."""
        self._mirror = mirror

    def span(self, name: str, span_id: str | None = None, **attrs):
        """Open a nested span (context manager).  With telemetry off
        the span is the trace mirror's marker alone, or a shared no-op
        constant when no mirror is installed, so the off path records
        nothing."""
        mirror = self._mirror
        mark = _NOOP if mirror is None else mirror(TRACE_PREFIX + name)
        if not self.enabled:
            return mark
        sid = span_id or f"{name}:{self._next_seq()}"
        st = self._stack()
        parent = st[-1] if st else None
        return _Span(self, name, sid, parent, attrs, mark)

    def _close_span(self, sp: _Span, dur: float, failed: bool) -> None:
        rec = {"span": sp.sid, "name": sp.name, "parent": sp.parent,
               "end_ts": time.time(), "dur_s": round(dur, 6), **sp.attrs}
        if failed:
            rec["failed"] = True
        with self._lock:
            self._spans.append(rec)
            self._span_names[sp.name] += 1

    def span_record(self, name: str, dur_s: float,
                    span_id: str | None = None, **attrs) -> None:
        """Record a span retroactively from an externally measured
        duration — for scopes whose instants were stamped elsewhere (a
        served request's enqueue→result ledger)."""
        if not self.enabled:
            return
        rec = {"span": span_id or f"{name}:{self._next_seq()}",
               "name": name, "parent": None, "end_ts": time.time(),
               "dur_s": round(float(dur_s), 6), **attrs}
        with self._lock:
            self._spans.append(rec)
            self._span_names[name] += 1

    def spans(self, name: str | None = None) -> list[dict]:
        with self._lock:
            sps = list(self._spans)
        return sps if name is None else [s for s in sps
                                         if s["name"] == name]

    # -- counters + gauges -----------------------------------------------
    def counter_add(self, name: str, value: float = 1.0) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) \
                + float(value)

    def gauge_set(self, name: str, value: float) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._gauges[name] = float(value)

    def gauge_get(self, name: str, default: float | None = None
                  ) -> float | None:
        """Read one gauge back (round 17: the autoscale tests and
        operators verify the serving loop's published signals this
        way; the control loop itself is fed the same values directly
        at the publish site, so its decisions do not change when
        telemetry is disabled and gauges go stale)."""
        with self._lock:
            return self._gauges.get(name, default)

    def counters(self) -> dict:
        """Snapshot of counters + gauges (one dict; gauges win on a
        name collision, which the catalog avoids by convention:
        ``*_total`` = counter, everything else = gauge)."""
        with self._lock:
            return {**self._counters, **self._gauges}

    # -- flight recorder --------------------------------------------------
    def snapshot(self) -> dict:
        """The flight-recorder payload: meta + counter snapshot + the
        bounded event ledger + the bounded recent-span ring."""
        with self._lock:
            return {
                "schema": 1,
                "pid": os.getpid(),
                "enabled": self.enabled,
                "started_at": self._t0,
                "dumped_at": time.time(),
                "uptime_s": round(time.time() - self._t0, 3),
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "event_kinds": dict(self._event_kinds),
                "span_names": dict(self._span_names),
                "events": list(self._events),
                "spans": list(self._spans),
            }

    def dump(self, reason: str, directory: str | None = None,
             path: str | None = None) -> str | None:
        """Atomically write the flight-recorder snapshot; returns the
        path (or None when no destination is known).  tmp+rename, so a
        reader never sees a torn dump — the checkpoint layer's
        discipline.  Never raises: a failing dump must not take down
        the salvage/crash path it decorates."""
        try:
            if path is None:
                with self._lock:    # dump_dir is written under it
                    d = directory or self.dump_dir
                if not d:
                    return None
                os.makedirs(d, exist_ok=True)
                path = os.path.join(
                    d, f"flight_{os.getpid()}_{reason}.json")
            snap = self.snapshot()
            snap["reason"] = reason
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as fp:
                json.dump(snap, fp)
                fp.flush()
                os.fsync(fp.fileno())
            os.replace(tmp, path)
            return path
        except OSError:
            return None

    def install_crash_dump(self, directory: str | None = None) -> None:
        """Chain ``sys.excepthook`` so an uncaught exception dumps the
        flight recorder before the traceback prints — every crash
        post-mortem ships its own trace.  Idempotent."""
        if self._crash_hook_installed:
            return
        self._crash_hook_installed = True
        prev = sys.excepthook

        def hook(exc_type, exc, tb):
            self.event("crash", error=f"{exc_type.__name__}: {exc}")
            self.dump("crash", directory=directory)
            prev(exc_type, exc, tb)

        sys.excepthook = hook

    # -- /metrics ----------------------------------------------------------
    def render_metrics(self) -> str:
        """Prometheus-style text page: counters/gauges as
        ``gossip_<name> <value>``, plus per-kind event totals and
        per-name span totals as labeled series.  Names are sanitized to
        the metrics charset."""
        def clean(name: str) -> str:
            return "".join(c if c.isalnum() or c == "_" else "_"
                           for c in name)

        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            kinds = dict(self._event_kinds)
            names = dict(self._span_names)
            enabled = self.enabled
            t0 = self._t0
        lines = ["# gossip telemetry (docs/OBSERVABILITY.md)",
                 "gossip_up 1",
                 f"gossip_telemetry_enabled {int(enabled)}",
                 f"gossip_uptime_s {round(time.time() - t0, 3)}"]
        for k in sorted(counters):
            lines.append(f"gossip_{clean(k)} {counters[k]:g}")
        for k in sorted(gauges):
            lines.append(f"gossip_{clean(k)} {gauges[k]:g}")
        for k in sorted(kinds):
            lines.append(
                f'gossip_events_total{{kind="{clean(k)}"}} {kinds[k]}')
        for k in sorted(names):
            lines.append(
                f'gossip_spans_total{{name="{clean(k)}"}} {names[k]}')
        return "\n".join(lines) + "\n"


# ----------------------------------------------------------------------
# The process-wide singleton and its config entry points.

_RECORDER = Recorder()


def recorder() -> Recorder:
    return _RECORDER


def env_enabled() -> bool:
    return os.environ.get("GOSSIP_TELEMETRY", "").lower() in _TRUTHY


def configure_from_config(cfg, force: bool | None = None) -> Recorder:
    """Apply a parsed NetworkConfig's ``telemetry_*`` keys to the
    process recorder (``force=True`` = the CLI's ``--telemetry`` flag;
    the env knob ``GOSSIP_TELEMETRY=1`` also wins).  Returns the
    recorder."""
    enabled = bool(getattr(cfg, "telemetry", 0)) or env_enabled()
    if force is not None:
        enabled = enabled or bool(force)
    return _RECORDER.configure(
        enabled=enabled,
        ring=getattr(cfg, "telemetry_ring", None),
        dump_dir=getattr(cfg, "telemetry_dump_dir", "") or None)
