"""Plain reference of one gossip scenario under a fault plan.

``aligned_gossip.py``'s scenario (overlay, churn, liveness sweep and
rewiring, staggered generation, flood push, windowed anti-entropy pull,
census), with the fault plane written from its documented rules
(``docs/ROBUSTNESS.md``; the program's ``faults.py``), importing nothing
of the program:

* every fault draw is keyed on the plan's own key for the round,
  ``fold_in(PRNGKey(seed & 0x7FFFFFFF), round)``, never on the scenario's
  key, so the churn and pull draws are those of the unfaulted scenario;
* crash and recover, after churn: the i-th crash entry ``(round, f)``
  kills each live peer whose per-row uniform under tag ``101 + i`` is
  below ``f`` in that round, the i-th recover entry revives each dead
  valid peer under tag ``211 + i`` (padding never revives); deaths are
  real, so the liveness sweep sees them;
* relay delay: a live peer whose per-row uniform under tag 7 is below
  ``delay`` sends nothing in this round's push, still serves the pull,
  and keeps its frontier bits in its frontier for the next round;
* link drop: slot ``d`` of receiver ``p`` (flat id) drops its transfer in
  pass ``k`` (push 0, pull 1) iff a 31-bit hash of (p, d, round,
  ``seed ^ (k * 0x632BE5AB & 0x7FFFFFFF)``) is below
  ``int(drop * 2^31)``;
* partition: while ``start <= round < heal``, a transfer crosses the
  slot only if the receiver's lane and the sender's lane agree under
  ``groups - 1`` (for a power-of-two group count up to 128 that is
  ``peer_id % groups``); the liveness sweep is not gated.

Departures: none known.  ``fault_duplicate`` acts on the socket wire
only (delivery is an OR, so a second copy changes no state) and is not
modelled; ``fault_byzantine`` is refused, as the comparison counts every
byzantine mark as a mismatch.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from gossipbench.references import aligned_gossip as base

LANES = base.LANES
Outcome = base.Outcome
TAG_DEFER = 7
TAG_CRASH = 101
TAG_RECOVER = 211
PASS_SEED = 0x632BE5AB

_FAULT_KEYS = ("fault_link_drop", "fault_delay", "fault_duplicate",
               "fault_partition", "fault_partition_groups", "fault_crash",
               "fault_recover", "fault_seed")


def _pairs(text: str, cast) -> tuple:
    """``"a:b+c:d"`` -> ((a, b), (c, d))."""
    out = []
    for part in text.split("+"):
        if part.strip():
            left, _, right = part.partition(":")
            out.append((int(left), cast(right)))
    return tuple(out)


@dataclasses.dataclass(frozen=True)
class Deployment(base.Deployment):
    drop: float = 0.0
    delay: float = 0.0
    partitions: tuple = ()
    groups: int = 2
    crash: tuple = ()
    recover: tuple = ()
    fault_seed: int = 0

    @property
    def threshold(self) -> int:
        return min(int(self.drop * (1 << 31)), (1 << 31) - 1)

    @property
    def gated(self) -> bool:
        """Whether transfers pass the per-link gate at all."""
        return self.drop > 0.0 or bool(self.partitions)


def deployment(kv: dict) -> Deployment:
    """Resolve a configuration's keys, its fault plan among them."""
    plain = {k: v for k, v in kv.items() if k not in _FAULT_KEYS}
    groups = int(kv.get("fault_partition_groups", "2"))
    parts = _pairs(kv.get("fault_partition", ""), int)
    if parts and (groups < 2 or groups > LANES or groups & (groups - 1)):
        raise ValueError(f"fault_partition_groups={groups}: a power of two "
                         "in [2, 128]")
    return Deployment(
        **dataclasses.asdict(base.deployment(plain)),
        drop=float(kv.get("fault_link_drop", "0")),
        delay=float(kv.get("fault_delay", "0")),
        partitions=parts, groups=groups,
        crash=_pairs(kv.get("fault_crash", ""), float),
        recover=_pairs(kv.get("fault_recover", ""), float),
        fault_seed=int(kv.get("fault_seed", "0")))


def keep_hash(flat_id, d, round_idx, seed):
    """31-bit hash of link (slot ``d`` of peer ``flat_id``) in a round."""
    h = flat_id ^ (round_idx * jnp.int32(0x2545F491))
    h = h ^ (d * jnp.int32(0x19660D1F))
    h = h ^ (seed * jnp.int32(0x7FEB352D))
    return base._mix32(h) & jnp.int32(0x7FFFFFFF)


class Scenario(base.Scenario):
    """``aligned_gossip.Scenario`` with the fault plane in its round."""

    def _round(self, live: bool):
        dep, ov = self.dep, self.ov
        R, D, W = ov.rows, dep.n_slots, dep.n_words
        Dw = ov.pull_slots() if dep.pull_window else D
        hmask = jnp.asarray(base.honest_mask(dep.n_msgs, W))[None, :, None]
        rows = jnp.arange(R, dtype=jnp.int32)
        pid = rows[:, None] * LANES + jnp.arange(LANES, dtype=jnp.int32)[None]
        chunk, parts, whole = self.chunk, self.parts, self._sharding(None)
        fseed = dep.fault_seed & 0x7FFFFFFF
        seeds = [fseed ^ (k * PASS_SEED & 0x7FFFFFFF) for k in (0, 1)]
        gmask = dep.groups - 1

        def row_u(key):
            return jax.vmap(lambda g: jax.random.uniform(
                jax.random.fold_in(key, g), (LANES,)))(rows)

        def nbr_words(x, srow_d, lanes_d):
            return base.lane_gather(jnp.take(x, srow_d, axis=0),
                                    lanes_d.astype(jnp.int32), chunk, parts)

        def link_ok(d, lane_d, r, split, k):
            """Whether slot d's transfer reaches each receiver in pass k."""
            ok = keep_hash(pid, d, r, jnp.int32(seeds[k])) >= dep.threshold
            same = (pid & gmask) == (lane_d.astype(jnp.int32) & gmask)
            return ok & (same | ~split)

        def rnd(st, c, r, m, inject):
            key, k_churn, _, k_pull, _ = jax.random.split(st["key"], 5)
            u = jax.vmap(lambda g: jax.random.uniform(
                jax.random.fold_in(k_churn, g), (2, LANES)))(rows)
            alive = st["alive"] & ~(u[:, 0] < dep.churn_rate) & c["valid"]
            fkey = jax.random.fold_in(jax.random.PRNGKey(fseed), r)
            for i, (at, frac) in enumerate(dep.crash):
                uc = row_u(jax.random.fold_in(fkey, TAG_CRASH + i))
                alive = alive & ~((r == at) & (uc < frac))
            for i, (at, frac) in enumerate(dep.recover):
                ur = row_u(jax.random.fold_in(fkey, TAG_RECOVER + i))
                alive = alive | ((r == at) & (ur < frac) & c["valid"] & ~alive)
            defer = jnp.zeros_like(alive)
            if dep.delay > 0.0:
                defer = (row_u(jax.random.fold_in(fkey, TAG_DEFER))
                         < dep.delay) & alive
            split = jnp.bool_(False)
            for start, heal in dep.partitions:
                split = split | ((r >= start) & (r < heal))
            alive_w = jnp.where(alive, jnp.int32(-1), jnp.int32(0))
            lanes, strikes = st["lanes"], st["strikes"]
            if live:
                def slot(d, carry):
                    lanes, strikes = carry
                    y = jnp.take(alive_w[:, None], c["srow"][d], axis=0)
                    lane = lanes[d].astype(jnp.int32)
                    nbr_alive = base.lane_gather(y, lane, chunk, parts)[:, 0] != 0
                    s = strikes[d].astype(jnp.int32)
                    dead = (d < c["deg"]) & ~nbr_alive
                    s_new = jnp.where(dead, jnp.minimum(s + 1, dep.max_strikes + 1), 0)
                    cand = base.rewire_lane(pid, d, r, jnp.int32(dep.seed))
                    take = ((s_new >= dep.max_strikes)
                            & (base.lane_gather(y, cand, chunk, parts)[:, 0] != 0))
                    lanes = lanes.at[d].set(jnp.where(take, cand, lane).astype(jnp.int8))
                    strikes = strikes.at[d].set(jnp.where(take, 0, s_new).astype(jnp.int8))
                    return lanes, strikes

                lanes, strikes = jax.lax.fori_loop(0, D, slot, (lanes, strikes))
            seen0 = st["seen"]
            seen, frontier = seen0, st["frontier"]
            if dep.stagger > 0:
                src = c["src"][jnp.clip(m, 0, dep.n_msgs - 1)]
                row, lane, w = src // LANES, src % LANES, m // base.WORD_BITS
                bit = jnp.where(inject & alive[row, lane],
                                jnp.left_shift(jnp.int32(1), m % base.WORD_BITS), 0)
                seen = seen.at[row, w, lane].set(seen[row, w, lane] | bit)
                frontier = frontier.at[row, w, lane].set(frontier[row, w, lane] | bit)
            held_w = alive_w & jnp.where(defer, jnp.int32(-1), jnp.int32(0))
            send = frontier & (alive_w & ~held_w)[:, None]

            def push(d, acc):
                z = nbr_words(send, c["srow"][d], lanes[d])
                gate = d < c["deg"]
                if dep.gated:
                    gate = gate & link_ok(d, lanes[d], r, split, 0)
                return acc | jnp.where(gate[:, None], z, 0)

            recv = jax.lax.fori_loop(0, D, push, jnp.zeros_like(seen))
            ys = seen0 & alive_w[:, None]
            pu = jax.vmap(lambda g: jax.random.randint(
                jax.random.fold_in(k_pull, g), (LANES,), 0, 1 << 30,
                jnp.int32))(rows)
            degw = jnp.minimum(c["deg"], Dw)
            delta = jnp.where(degw > 0, pu % jnp.maximum(degw, 1), Dw)

            def pull(d, acc):
                z = nbr_words(ys, c["srow"][d], lanes[d])
                gate = delta == d
                if dep.gated:
                    gate = gate & link_ok(d, lanes[d], r, split, 1)
                return acc | jnp.where(gate[:, None], z, 0)

            recv = jax.lax.fori_loop(0, Dw, pull, recv)
            ok_w = alive_w & jnp.where(c["valid"], -1, 0)
            new = recv & ok_w[:, None] & ~seen
            seen = seen | new
            pc = jax.lax.population_count
            per_row = lambda x: jnp.sum(x.reshape(R, -1), axis=1, dtype=jnp.int32)
            orw = base.or_words(seen, whole)
            stats = {"hits": per_row(jnp.where((ok_w != 0)[:, None],
                                               pc(seen & hmask), 0)),
                     "n_ok": jnp.sum(ok_w != 0, dtype=jnp.int32),
                     "n_cols": jnp.sum(pc(orw & hmask[0, :, 0]), dtype=jnp.int32)}
            st = {"seen": seen, "frontier": new | (frontier & held_w[:, None]),
                  "alive": alive, "strikes": strikes, "lanes": lanes, "key": key}
            return st, stats

        return rnd

