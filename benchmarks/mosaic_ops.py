"""Count the operations of one grid step of the main gossip kernels.

Compiles the kernels for a described TPU v5e (no chip needed: run with
``JAX_PLATFORMS=cpu``), has Mosaic dump each kernel's lowering stages
into a directory, and prints, per kernel, how many of each ``llo.`` op
the final stage (``post-finalize-llo``) holds.  Kernel time on the chip
follows these counts where the step is bound by compute, as the
sublane roll's selects were (PERF.md section 5).

    JAX_PLATFORMS=cpu python benchmarks/mosaic_ops.py [dump_dir]

Shapes are those of the 16M-peer cells (16 slots): the W=8 fused push at
512-row blocks with the prefetch ring, the W=1 push at 2048-row blocks
with the skip tables, and the liveness sweep at 512-row blocks.  Each
kernel's line is JSON: its name and its op counts, largest first.
"""
from __future__ import annotations

import collections
import glob
import json
import os
import re
import sys
import tempfile

ROWS = (1 << 24) // 128
SLOTS = 16


def compile_kernels(dump_dir: str) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # read once, when the TPU library loads: set before JAX starts it
    os.environ["LIBTPU_INIT_ARGS"] = (
        os.environ.get("LIBTPU_INIT_ARGS", "")
        + f" --xla_mosaic_dump_to={dump_dir}").strip()
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from p2p_gossipprotocol_tpu.ops.aligned_kernel import (gossip_pass,
                                                           liveness_pass)

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    sh = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    def tables(blk):
        return (sds((SLOTS, ROWS, 128), jnp.int8), sds((ROWS, 128), jnp.int8),
                sds((SLOTS,), jnp.int32), sds((SLOTS,), jnp.int32),
                sds((SLOTS, ROWS // blk), jnp.int32))

    def build(fn, *args):
        jax.jit(fn).lower(*args).compile()

    col, gate, rolls, sub, tab = tables(512)
    plane = sds((ROWS, 128), jnp.int32)
    build(lambda y, c, g, k, s, yt, ok, yi, ya: gossip_pass(
        y, c, g, k, s, ytab=yt, src_ok=ok, yidx=yi, yact=ya,
        prefetch_depth=2, rowblk=512, name="push_w8_fused"),
        sds((8, ROWS, 128), jnp.int32), col, gate, rolls, sub, tab, plane,
        tab, tab)
    build(lambda a, c, st, g, k, s, yt, gb: liveness_pass(
        a, c, st, g, k, s, gbase=gb, round_idx=3, hash_seed=7, ytab=yt,
        rowblk=512), plane, col, col, gate, rolls, sub, tab,
        sds((ROWS // 512,), jnp.int32))
    col, gate, rolls, sub, tab = tables(2048)
    build(lambda y, c, g, k, s, yi, ya: gossip_pass(
        y, c, g, k, s, yidx=yi, yact=ya, prefetch_depth=2, rowblk=2048,
        name="push_w1"),
        sds((1, ROWS, 128), jnp.int32), col, gate, rolls, sub, tab, tab)


def op_counts(dump_dir: str) -> dict[str, collections.Counter]:
    counts = {}
    for path in sorted(glob.glob(os.path.join(
            dump_dir, "*-mosaic-dump-*-post-finalize-llo.txt"))):
        name = re.search(r"-mosaic-dump-(.+)-post-finalize-llo",
                         os.path.basename(path)).group(1)
        with open(path) as f:
            counts[name] = collections.Counter(
                re.findall(r"\b(llo\.[a-z_.0-9]+)", f.read()))
    return counts


def main(argv: list[str]) -> int:
    dump_dir = argv[0] if argv else tempfile.mkdtemp(prefix="mosaic-ops-")
    compile_kernels(dump_dir)
    for name, ops in op_counts(dump_dir).items():
        print(json.dumps({"kernel": name, "ops": dict(ops.most_common())}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
