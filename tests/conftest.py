"""Test configuration: force JAX onto a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on a virtual CPU mesh (the driver separately dry-run-compiles the
multi-chip path via __graft_entry__.dryrun_multichip).

The platform is forced through jax.config.update, not only through
JAX_PLATFORMS: the suite must stay on the CPU even where something imported
jax before this file ran, and must never claim a chip the host may have
(a chip belongs to one process at a time).  XLA_FLAGS is set before the
first backend init.  The Pallas kernels run here through the interpreter
only; the chip is driven by chip_smoke.py.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import tendermint_tpu  # noqa: E402  (places the compile cache)
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _locksan(request):
    """tmlint lockset monitor (docs/adr/adr-014-tmlint.md): armed for
    EVERY test under TM_TPU_LOCKSAN=1, or per-test via the `locksan`
    marker.  Locks created by tendermint_tpu modules during the test
    are wrapped; an acquisition that takes a lower-ranked lock while
    holding a higher-ranked one (devtools/lockorder.py) fails the test
    with the offending edge.  Pre-existing singletons keep their raw
    locks — scheduler/degrade/comb tests build fresh runtimes, which is
    exactly where the ordering matters."""
    armed = os.environ.get("TM_TPU_LOCKSAN") == "1" or \
        request.node.get_closest_marker("locksan") is not None
    if not armed:
        yield None
        return
    from tendermint_tpu.devtools.tmlint.runtime import LockSanitizer
    san = LockSanitizer()
    san.install()
    try:
        yield san
    finally:
        san.uninstall()
    assert not san.violations, (
        "lockset monitor: lock-order inversion(s) against "
        "devtools/lockorder.py:\n  " + "\n  ".join(san.violations))


@pytest.fixture
def compile_sentinel():
    """tmlint compile sentinel (opt-in): snapshots the launch-bucket
    set and watched jit-entry cache sizes; at teardown fails the test
    if a launch landed outside the known padded-lane shapes.  Tests
    that must not compile anything new assert on the returned report or
    construct their own CompileSentinel(max_new_compiles=0)."""
    from tendermint_tpu.devtools.tmlint.runtime import CompileSentinel
    s = CompileSentinel().start()
    yield s
    s.check()


@pytest.fixture(autouse=True)
def _no_thread_leaks():
    """Every worker thread in this codebase must either be a daemon
    (service.spawn, the degrade lane worker) or be joined by the test
    that started it.  A NON-daemon thread that survives a test is a
    leak: it blocks interpreter shutdown behind whatever it is wedged
    on and accumulates across the tier-1 run (the VerifyScheduler /
    degradation-runtime workers in particular must stop cleanly)."""
    before = set(threading.enumerate())
    yield

    def leaked():
        return [t for t in threading.enumerate()
                if t.is_alive() and not t.daemon
                and t is not threading.main_thread() and t not in before]

    # grace for executors/servers that are mid-shutdown at teardown
    deadline = time.monotonic() + 5.0
    while leaked() and time.monotonic() < deadline:
        time.sleep(0.05)
    rest = leaked()
    assert not rest, (
        f"non-daemon threads leaked by this test: "
        f"{[t.name for t in rest]}")
