"""Test harness config: force an 8-device virtual CPU mesh before JAX init.

The reference has no tests at all (SURVEY.md §4); our strategy is seeded,
deterministic single-process simulation — the multi-node-without-a-cluster
fixture the reference lacks. Multi-chip sharding is exercised on 8 virtual
CPU devices (driver separately dry-runs the real multi-chip path).
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# JAX's persistent compile cache stays off for the suite and every
# subprocess that inherits this environment, even where the shell sets
# JAX_COMPILATION_CACHE_DIR: a test must never write one (and the TPU
# compiles of test_tpu_compile could not be read back here anyway).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402  (import after env setup)

# Backend init is lazy, so pinning the platform here — before any
# jax.devices() call — wins over an inherited JAX_PLATFORMS, and the XLA
# flag above gives us the 8-device virtual CPU mesh.
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_compilation_cache", False)

import pytest  # noqa: E402

# Best-effort native build: the .so is a gitignored build artifact, so a
# fresh checkout would silently skip the 13 native tests even on a
# machine with a full toolchain.  One quiet make at collection time
# keeps those tests live; failure (no g++, no make) falls back to the
# skipif guards exactly as before.  Gated on the .so being absent or
# older than the native sources — single-test runs on an up-to-date
# tree must not pay the 120 s-timeout subprocess at every collection.


def _native_stale(native_dir: str) -> bool:
    so = os.path.join(native_dir, "libgossip_native.so")
    if not os.path.exists(so):
        return True
    built = os.path.getmtime(so)
    for src in ("gossip_native.cpp", "Makefile"):
        p = os.path.join(native_dir, src)
        if os.path.exists(p) and os.path.getmtime(p) > built:
            return True
    return False


try:
    import subprocess
    import warnings

    _native_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")
    if _native_stale(_native_dir):
        _mk = subprocess.run(
            ["make", "-C", _native_dir],
            capture_output=True, timeout=120, check=False, text=True)
        if _mk.returncode != 0:
            # A toolchain exists but the build BROKE — that must be
            # loud, not a green suite with 13 silent skips.
            warnings.warn("native build failed (tests will skip): "
                          + _mk.stderr.strip()[-500:], stacklevel=1)
except Exception:  # noqa: BLE001 — no toolchain: tests skip gracefully
    pass


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


# -- per-test timeout guard for the socket + subprocess suites ---------
# The socket tests drive real TCP nodes with daemon threads; a wedged
# accept/recv used to hang the WHOLE tier-1 run until the outer
# 870-second kill (observed: the seed suite died at the timeout with the
# tail of the run never executed).  SIGALRM interrupts the blocking
# syscall in the main thread and fails ONE test with a readable error
# instead.  Scoped by module name, so any suite touching real sockets
# (test_socket_*, test_transport, ...) is covered automatically — and
# the preemption suite (test_preemption drives kill/resume CLI
# subprocesses, which can wedge the same way) rides the same guard, as
# does the supervisor suite (test_supervisor drives stub-worker and
# chaos subprocesses whose whole point is wedging on cue — this guard
# keeps a supervision bug from wedging tier-1 itself).  The slow chaos
# tests run multi-attempt supervised jobs (compile x attempts + a
# reference run), bounded — but not by the 120 s leash, so the
# supervisor module gets its own budget.

SOCKET_TEST_TIMEOUT_S = 120
SUPERVISOR_TEST_TIMEOUT_S = 420


@pytest.fixture(autouse=True)
def _socket_suite_timeout(request):
    import signal

    mod = getattr(request.module, "__name__", "")
    guarded = "socket" in mod or "preemption" in mod \
        or "supervisor" in mod or "serve" in mod \
        or "telemetry" in mod or "federation" in mod
    if not guarded or not hasattr(signal, "SIGALRM"):
        yield
        return
    budget = (SUPERVISOR_TEST_TIMEOUT_S
              if "supervisor" in mod or "serve" in mod
              or "telemetry" in mod or "federation" in mod
              else SOCKET_TEST_TIMEOUT_S)

    def _fire(signum, frame):
        raise TimeoutError(
            f"guarded-suite test exceeded {budget}s "
            "(per-test guard; a blocking accept/recv or subprocess "
            "wedged)")

    old = signal.signal(signal.SIGALRM, _fire)
    signal.alarm(budget)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
