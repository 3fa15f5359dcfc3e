"""Live roofline: per-chunk reconciliation of census vs traffic model.

A resident server and a supervised long run need ``roofline_frac``
LIVE: after every chunk, this tracker folds the chunk's
already-materialized in-kernel census (coverage, deliveries, frontier
size — metrics the engines emit anyway, so tracking adds zero device
work) and the engine's analytic per-term byte accounting
(``traffic_model()``, the Sparse-Allreduce-style comms-cost model) into
cumulative counters and two headline gauges:

* ``roofline_frac`` — achieved fraction of the HBM roof: model bytes
  moved over measured wall, divided by
  the device's published HBM peak (:data:`HBM_PEAK_GB_S`).  A CPU run
  has no roof and publishes no fraction;
* ``model_drift_frac`` — modeled-vs-achieved drift: the dense model
  prices every round at full frontier width, while the live census
  knows the actual frontier; the gauge is the relative gap between the
  dense accounting and the census-informed accounting
  (``traffic_model(frontier_fill=live fill)``), i.e. how far reality
  has drifted below the model's upper bound.  0 while the frontier is
  dense, growing as the run enters the sparse regime.
"""

from __future__ import annotations

from p2p_gossipprotocol_tpu.telemetry.recorder import recorder

#: Published HBM bandwidth of one chip in GB/s, keyed by JAX's
#: ``device_kind``.  Source: Google Cloud documentation, "TPU v5e"
#: (16 GB of HBM at 819 GB/s per chip).
HBM_PEAK_GB_S = {"TPU v5 lite": 819.0}


def hbm_peak_gb_s(platform: str, device_kind: str) -> float | None:
    """The HBM roof a roofline share divides by: None on the CPU,
    which has no roofline share; the table's entry for a chip; and an
    error for a chip the table does not know, never a default."""
    if platform == "cpu":
        return None
    try:
        return HBM_PEAK_GB_S[device_kind]
    except KeyError:
        raise KeyError(f"no published HBM peak for device_kind "
                       f"{device_kind!r} ({platform}); add it to "
                       "HBM_PEAK_GB_S with its source") from None


class RooflineTracker:
    """Per-chunk counter aggregation + live roofline for one run (see
    module docstring).  Construct via :meth:`for_sim`, which returns
    None for engines without a traffic model (the edges family) —
    callers then skip tracking entirely."""

    def __init__(self, model_fn, dense_bytes_round: float,
                 n_peers: int, roof_gb_s: float | None = None):
        self._model_fn = model_fn           # frontier_fill -> terms dict
        self.dense_bytes_round = float(dense_bytes_round)
        self.n_peers = max(1, int(n_peers))
        self.roof_gb_s = roof_gb_s          # None: no roofline share
        self.rounds = 0
        self.wall_s = 0.0
        self.model_bytes = 0.0              # dense accounting
        self.census_bytes = 0.0             # fill-informed accounting

    # ------------------------------------------------------------------
    @classmethod
    def for_sim(cls, sim, device) -> "RooflineTracker | None":
        """A tracker for ``sim`` running on ``device`` (a
        ``jax.Device``, whose kind picks the roof) when it can price
        itself (the aligned family — sharded wrappers expose the model
        through ``_inner``), else None."""
        inner = getattr(sim, "_inner", sim)
        model = getattr(inner, "traffic_model", None)
        if model is None:
            return None
        n_shards = int(getattr(sim, "n_shards", 1) or 1)

        def model_fn(fill=None):
            return model(frontier_fill=fill, n_shards=n_shards)

        try:
            dense = float(model_fn()["total"])
        except Exception:  # noqa: BLE001 — a sim that cannot price
            return None    # itself is tracked by spans alone
        topo = getattr(inner, "topo", None)
        n_peers = int(getattr(topo, "n_peers", 0) or 1)
        return cls(model_fn, dense, n_peers,
                   roof_gb_s=hbm_peak_gb_s(device.platform,
                                           device.device_kind))

    # ------------------------------------------------------------------
    def update(self, rounds: int, wall_s: float, metrics: dict) -> None:
        """Fold one chunk into the counters and refresh the gauges.
        ``metrics`` is the chunk's history dict (numpy arrays keyed
        like SimResult fields); missing keys are tolerated so the SIR
        engines ride the same tracker."""
        rec = recorder()
        if not rec.enabled:
            return
        import numpy as np

        self.rounds += int(rounds)
        self.wall_s += float(wall_s)
        chunk_model = self.dense_bytes_round * rounds
        self.model_bytes += chunk_model

        # census-informed accounting: the live frontier width caps the
        # model's per-round bytes for this chunk (the model's dense
        # answer is its upper bound, so informed <= dense always)
        fill = None
        fs = metrics.get("frontier_size")
        if fs is not None and len(fs):
            fill = min(1.0, float(np.mean(np.asarray(
                fs, dtype=np.float64))) / self.n_peers)
        try:
            informed = float(self._model_fn(fill)["total"]) * rounds
        except Exception:  # noqa: BLE001 — model without fill support
            informed = chunk_model
        informed = min(informed, chunk_model)
        self.census_bytes += informed

        rec.counter_add("rounds_total", rounds)
        rec.counter_add("wall_s_total", wall_s)
        rec.counter_add("model_bytes_total", chunk_model)
        rec.counter_add("census_bytes_total", informed)
        dl = metrics.get("deliveries")
        if dl is not None and len(dl):
            rec.counter_add("deliveries_total",
                            float(np.sum(np.asarray(dl,
                                                    dtype=np.float64))))
        cov = metrics.get("coverage")
        if cov is not None and len(cov):
            rec.gauge_set("coverage", float(np.asarray(cov)[-1]))
        ni = metrics.get("new_infections")
        if ni is not None and len(ni):
            rec.counter_add("new_infections_total",
                            float(np.sum(np.asarray(ni,
                                                    dtype=np.float64))))
        if fill is not None:
            rec.gauge_set("frontier_fill", round(fill, 6))

        # the two headline gauges, recomputed from cumulative totals
        if self.wall_s > 0:
            gbs = self.model_bytes / self.wall_s / 1e9
            rec.gauge_set("achieved_gb_s", round(gbs, 4))
            if self.roof_gb_s is not None:
                # 10 places, not 6: on a loaded host a real-but-tiny
                # fraction must not round to an impossible exact 0.0
                # (a positive achieved_gb_s implies a positive fraction)
                rec.gauge_set("roofline_frac",
                              round(gbs / self.roof_gb_s, 10))
        if self.model_bytes > 0:
            drift = 1.0 - self.census_bytes / self.model_bytes
            rec.gauge_set("model_drift_frac", round(drift, 6))
