"""Seed-deterministic multi-tenant load shapes for the serving-plane
harnesses (round 18), for the served benchmark cell to drive.

The round-12/17 sweeps drove homogeneous Poisson arrivals — the right
null model for a latency knee, but not the shape that stresses a
FEDERATION: fleet-kill recovery cares about how much work is in flight
when the axe lands (bursts), and tenant fairness only means anything
when one tenant's offered load looks nothing like another's (a flood
against a trickle).  Two generators, both pure functions of their
(seed, parameters):

* :func:`diurnal_gaps` — a non-homogeneous Poisson stream whose rate
  follows a raised-cosine day curve (peak/trough ratio ``swing``),
  generated by Lewis-Shedler thinning against the peak rate, so the
  arrival instants are exact for the target intensity, not a binned
  approximation;
* :func:`bursty_gaps` — a two-state Markov-modulated Poisson process
  (ON at ``rate * burst_factor``, OFF near-idle), the classic
  flash-crowd shape: most arrivals land inside bursts, which is
  exactly when a fleet kill hurts most.

:func:`tenant_stream` deals tenants over an arrival stream by weight,
deterministically.  :func:`synth` composes the three into the
``(overrides, gaps)`` pair a driver feeds its wire loop — every
request dict carries its ``tenant`` tag (the round-18
SLO field) and cycles the same six program-signature families the
round-17 sweep served, so bucket lifecycle stays in play.

Everything here uses ``random.Random(seed)`` only — same seed, same
workload, byte for byte, on any host (the replay property the chaos
harness leans on: an A/B pair must offer IDENTICAL load to both arms).
"""
import math
import random

#: the six compiled-program signature families of the round-17 sweep
#: (mode x fanout x stagger x message width) — reused so round-18 rows
#: are comparable against the round-17 baseline at equal diversity
FAMILIES = (
    {},
    {"mode": "pull"},
    {"mode": "pushpull"},
    {"fanout": 2},
    {"message_stagger": 4},
    {"n_messages": 8},
)


def diurnal_gaps(rate_rps: float, n: int, *, seed: int,
                 period_s: float = 60.0,
                 swing: float = 4.0) -> list:
    """``n`` inter-arrival gaps (seconds) from a non-homogeneous
    Poisson process whose intensity traces one "day" of ``period_s``
    seconds: a raised cosine between ``rate_rps`` (trough) and
    ``rate_rps * swing`` (peak), via Lewis-Shedler thinning (candidate
    arrivals at the peak rate, each kept with probability
    intensity(t)/peak — exact, not binned)."""
    if rate_rps <= 0 or n <= 0:
        return []
    swing = max(1.0, float(swing))
    peak = rate_rps * swing
    rng = random.Random(seed)
    gaps, t, last = [], 0.0, 0.0
    while len(gaps) < n:
        t += rng.expovariate(peak)
        phase = (t % period_s) / period_s
        lam = rate_rps * (1.0 + (swing - 1.0)
                          * 0.5 * (1.0 - math.cos(2 * math.pi * phase)))
        if rng.random() <= lam / peak:
            gaps.append(t - last)
            last = t
    return gaps


def bursty_gaps(rate_rps: float, n: int, *, seed: int,
                burst_len: int = 8, burst_factor: float = 8.0,
                idle_factor: float = 0.125) -> list:
    """``n`` gaps from a two-state MMPP: bursts of ``burst_len``
    arrivals at ``rate_rps * burst_factor`` separated by idle spells
    at ``rate_rps * idle_factor`` — mean rate stays near ``rate_rps``
    while most arrivals land shoulder to shoulder (the flash-crowd
    shape the fleet-kill chaos drives, so the kill lands on a plane
    with real in-flight depth)."""
    if rate_rps <= 0 or n <= 0:
        return []
    rng = random.Random(seed)
    gaps = []
    while len(gaps) < n:
        # one idle gap opens the burst, then the burst drains hot
        gaps.append(rng.expovariate(rate_rps * idle_factor))
        for _ in range(min(burst_len - 1, n - len(gaps))):
            gaps.append(rng.expovariate(rate_rps * burst_factor))
    return gaps[:n]


def tenant_stream(n: int, weights: dict, *, seed: int) -> list:
    """``n`` tenant names dealt by weight (an independent weighted
    draw per arrival — ``random.Random(seed)``, so the deal replays).
    Empty/None weights yield the anonymous default tenant throughout."""
    if not weights:
        return [""] * n
    names = sorted(weights)
    cum, total = [], 0.0
    for name in names:
        total += float(weights[name])
        cum.append(total)
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        x = rng.random() * total
        for name, edge in zip(names, cum):
            if x <= edge:
                out.append(name)
                break
    return out


def synth(shape: str, rate_rps: float, n: int, *, seed: int,
          tenants: dict | None = None, **kw) -> tuple:
    """One workload: ``(overrides, gaps)`` where ``overrides[i]`` is a
    submit-ready request dict (distinct ``prng_seed``, one of the six
    signature families, its ``tenant`` tag when ``tenants`` is given)
    and ``gaps[i]`` is the inter-arrival sleep before submitting it.
    ``shape`` is ``"poisson"`` (the round-12/17 null), ``"diurnal"``,
    or ``"bursty"``; extra keywords pass through to the generator."""
    if shape == "poisson":
        rng = random.Random(seed)
        gaps = [rng.expovariate(rate_rps) for _ in range(n)]
    elif shape == "diurnal":
        gaps = diurnal_gaps(rate_rps, n, seed=seed, **kw)
    elif shape == "bursty":
        gaps = bursty_gaps(rate_rps, n, seed=seed, **kw)
    else:
        raise ValueError(f"unknown load shape {shape!r} "
                         "(poisson | diurnal | bursty)")
    labels = tenant_stream(n, tenants or {}, seed=seed ^ 0x5EED)
    overrides = []
    for i in range(n):
        ov = {"prng_seed": i, **FAMILIES[i % len(FAMILIES)]}
        if labels[i]:
            ov["tenant"] = labels[i]
        overrides.append(ov)
    return overrides, gaps
