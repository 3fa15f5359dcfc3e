"""Degree-bucketed vertex-block packing for the realgraph SpMV.

The delivery SpMV gathers each vertex's in-edges; on a real power-law
graph the in-degrees span five orders of magnitude, so one padded
``[n, max_deg]`` table would be almost entirely padding.  Instead the
fleet packer's discipline is applied to VERTEX blocks: vertices bucket
by degree class into power-of-two-width padded blocks (a degree-37
vertex rides the width-64 block; a hub wider than the cap splits into
multiple rows of the cap-width block — boolean OR accumulates across
its rows, so splitting is semantics-free), and the resulting
:func:`pack_signature` is a STATIC shape tuple: two graphs with the
same degree histogram compile to the same program, and the fleet/serve
bucket signature embeds it so admission reuse stays provable.

All packing is host-side numpy over the STRUCTURAL edge list (the
initial ``edge_mask``) — runtime mask mutations (per-link faults,
liveness eviction) are read live through ``gate[eid]`` inside the
round, so the tables never go stale.

:func:`shard_partition` is the 1-D vertex shard partition over chips:
contiguous vertex ranges balanced by in-degree (edge work), the
sharded seam's placement rule.  The engine itself runs single-device
today — the pack tables ride the jit as closure constants, and the
repo's remote-compile body-limit precedent (aligned SIR at 32M) is
exactly why the sharded path must pass them as arguments instead;
that seam is documented, not built, in this round.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

#: pack schema tag — rides the signature so a packing-rule change can
#: never silently collide with cached programs from an older rule
PACK_VERSION = "rgpack-v1"

#: default cap on a block's padded width (the realgraph_pack_width
#: auto value — ONE spelling, owned by autostatics.py with its rule):
#: wide enough that >99% of a power-law graph's vertices fit one row,
#: narrow enough that one hub cannot force a megabyte-wide lane on
#: everyone
from p2p_gossipprotocol_tpu.autostatics import \
    REALGRAPH_PACK_WIDTH_DEFAULT as PACK_WIDTH_DEFAULT  # noqa: E402


def next_pow2(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


@dataclass(frozen=True)
class PackedBlock:
    """One degree-class block: ``nrows`` padded rows of ``width``
    in-edge slots.  ``eid[r, j]`` indexes the topology's edge arrays
    (gate reads ride it), ``src`` is its pre-gathered source vertex,
    ``vtx[r]`` the destination vertex the row ORs into, ``valid`` the
    padding mask.  Padding rows/slots point at edge 0 / vertex 0 with
    ``valid=False`` — inert under the masked OR."""

    width: int
    eid: object        # int32[nrows, width]  (jnp)
    src: object        # int32[nrows, width]  (jnp)
    vtx: object        # int32[nrows]         (jnp)
    valid: object      # bool [nrows, width]  (jnp)


@dataclass(frozen=True)
class PackedGraph:
    """The packed CSR: blocks in ascending width order + the static
    signature tuple the bucket signature embeds."""

    blocks: tuple
    width_cap: int
    n_peers: int
    n_edges: int
    signature: tuple


def pack_topology(topo, width_cap: int = PACK_WIDTH_DEFAULT
                  ) -> PackedGraph:
    """Pack ``topo``'s structural in-edges into degree-class blocks.

    Deterministic by construction: edge ids arrive in the canonical
    ``_pad_and_build`` order, the dst grouping is a stable sort, and
    rows are emitted in ascending vertex order within ascending width —
    the same topology packs to bit-identical tables every time (the
    pack-determinism test pins this)."""
    if width_cap < 1 or (width_cap & (width_cap - 1)):
        raise ValueError(
            f"realgraph pack width must be a power of two >= 1, got "
            f"{width_cap}")
    n = int(topo.n_peers)
    dst = np.asarray(topo.dst)
    src = np.asarray(topo.src)
    mask = np.asarray(topo.edge_mask)
    eids = np.nonzero(mask)[0]
    e = int(eids.shape[0])
    order = np.argsort(dst[eids], kind="stable")
    eids = eids[order].astype(np.int64)
    deg = np.bincount(dst[eids], minlength=n)
    offsets = np.concatenate([[0], np.cumsum(deg)])

    # rows[w] = (vtx_list, eid_rows) per width class
    rows: dict[int, tuple[list, list]] = {}
    for v in np.nonzero(deg)[0].tolist():
        lo, hi = int(offsets[v]), int(offsets[v + 1])
        for start in range(lo, hi, width_cap):
            seg = eids[start:min(start + width_cap, hi)]
            w = next_pow2(len(seg))
            vlist, elist = rows.setdefault(w, ([], []))
            vlist.append(v)
            elist.append(seg)

    import jax.numpy as jnp

    blocks = []
    sig_rows = []
    for w in sorted(rows):
        vlist, elist = rows[w]
        nrows = len(vlist)
        eid = np.zeros((nrows, w), np.int64)
        valid = np.zeros((nrows, w), bool)
        for r, seg in enumerate(elist):
            eid[r, :len(seg)] = seg
            valid[r, :len(seg)] = True
        blocks.append(PackedBlock(
            width=w,
            eid=jnp.asarray(eid, jnp.int32),
            src=jnp.asarray(src[eid], jnp.int32),
            vtx=jnp.asarray(np.asarray(vlist), jnp.int32),
            valid=jnp.asarray(valid)))
        sig_rows.append((w, nrows))
    signature = (PACK_VERSION, int(width_cap), tuple(sig_rows))
    return PackedGraph(blocks=tuple(blocks), width_cap=int(width_cap),
                       n_peers=n, n_edges=e, signature=signature)


def pack_signature(packed: PackedGraph) -> tuple:
    """The pack's STATIC shape identity: schema, width cap, and the
    ``(width, nrows)`` histogram.  Everything the compiled SpMV's
    shapes depend on and nothing data-dependent beyond them — the
    compile-reuse key, embedded verbatim in the fleet bucket
    signature."""
    return packed.signature


def shard_partition(deg_in: np.ndarray, n_shards: int) -> np.ndarray:
    """1-D contiguous vertex partition over ``n_shards`` chips,
    balanced by in-degree (gather work is edge work): returns bounds
    ``b[int32, n_shards+1]`` with shard k owning vertices
    ``[b[k], b[k+1])``.  The frontier delta exchange between these
    ranges is the PR 5/14 machinery's job; this function is the
    placement half of that sharded seam (single-device runs use the
    trivial ``[0, n]`` partition)."""
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    deg = np.asarray(deg_in, np.int64)
    n = deg.shape[0]
    cum = np.concatenate([[0], np.cumsum(deg)])
    total = int(cum[-1])
    targets = (np.arange(1, n_shards) * total) // n_shards
    cuts = np.searchsorted(cum, targets, side="left")
    bounds = np.concatenate([[0], cuts, [n]]).astype(np.int32)
    return np.maximum.accumulate(bounds)
