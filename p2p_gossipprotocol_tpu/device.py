"""The device a run executes on, and where its compiled programs are kept.

Entry points (``cli.main``, ``bench.main``, ``chip_smoke.py``) call
:func:`enable_compile_cache` before their first compile;
``engines.build_simulator`` calls :func:`install_trace_mirror`.
Importing this module does nothing.
"""

from __future__ import annotations

import os

#: the checkout's own compile cache.  The path is fixed relative to the
#: package — never built from a temp name, pid or time — because a cache
#: that moves between runs is never hit.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache() -> str | None:
    """Give JAX's persistent compilation cache its directory and return
    it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
    and nothing is set here.  A process pinned to the CPU
    (``JAX_PLATFORMS=cpu``, as every test is) keeps no cache: its
    compiles are cheap, and a test must never write one.  Otherwise the
    cache is :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR


def install_trace_mirror() -> None:
    """Mirror every ``telemetry.span`` into the profiler's trace: under
    a ``jax.profiler`` session each span is a host event named
    ``gossip.<name>``, on the device ops' clock; outside one the marker
    costs a no-op TraceMe.  The telemetry package never imports jax, so
    the JAX side hands it the marker here.  Idempotent."""
    import jax

    from p2p_gossipprotocol_tpu import telemetry

    telemetry.recorder().set_mirror(jax.profiler.TraceAnnotation)


def device_label() -> str:
    """``platform=<p>, device_kind=<k>`` of the first device, as JAX
    reports it — what every run header names, so a CPU run cannot be
    read as a chip run."""
    import jax

    d = jax.devices()[0]
    return f"platform={d.platform}, device_kind={d.device_kind}"
