"""Test harness config.

Mirrors the reference's distributed-without-a-cluster test trick
(``TEST/optim/DistriOptimizerSpec.scala:139`` uses ``local[1]`` Spark): we
run every test on a virtual 8-device CPU mesh via
``--xla_force_host_platform_device_count=8`` so sharding/collective paths
are exercised without TPU hardware.  MUST be set before jax import.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
)

# Lockdep opt-in (BIGDL_TPU_LOCKDEP=1): install the lock-order
# sanitizer BEFORE any product module constructs a lock, so every
# tier-1 run doubles as a deadlock hunt.  The module is loaded
# standalone by file path (registered under its canonical name) —
# importing it through the bigdl_tpu package would drag in the whole
# tree and create product locks ahead of the patch.
_LOCKDEP_MOD = None
if os.environ.get("BIGDL_TPU_LOCKDEP", "").lower() in (
        "1", "true", "yes", "on"):
    import importlib.util
    import sys as _sys
    _ld_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "bigdl_tpu", "utils", "lockdep.py")
    _spec = importlib.util.spec_from_file_location(
        "bigdl_tpu.utils.lockdep", _ld_path)
    _LOCKDEP_MOD = importlib.util.module_from_spec(_spec)
    _sys.modules["bigdl_tpu.utils.lockdep"] = _LOCKDEP_MOD
    _spec.loader.exec_module(_LOCKDEP_MOD)
    _LOCKDEP_MOD.install(hold_ms=float(
        os.environ.get("BIGDL_TPU_LOCKDEP_HOLD_MS", "200")))

# Spmdcheck opt-in (BIGDL_TPU_SPMDCHECK=1): the collective-schedule
# sanitizer (runtime twin of graftlint GL4xx).  Unlike lockdep it
# patches nothing — the driver's note sites gate on the recorder — so
# a plain import before jax is enough.  Loaded standalone by file path
# for the same reason as lockdep: importing through the bigdl_tpu
# package would drag in the whole tree here.
_SPMDCHECK_MOD = None
if os.environ.get("BIGDL_TPU_SPMDCHECK", "").lower() in (
        "1", "true", "yes", "on"):
    import importlib.util
    import sys as _sys2
    _sc_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "..", "bigdl_tpu", "utils", "spmdcheck.py")
    if "bigdl_tpu.utils.spmdcheck" in _sys2.modules:
        _SPMDCHECK_MOD = _sys2.modules["bigdl_tpu.utils.spmdcheck"]
    else:
        _sc_spec = importlib.util.spec_from_file_location(
            "bigdl_tpu.utils.spmdcheck", _sc_path)
        _SPMDCHECK_MOD = importlib.util.module_from_spec(_sc_spec)
        _sys2.modules["bigdl_tpu.utils.spmdcheck"] = _SPMDCHECK_MOD
        _sc_spec.loader.exec_module(_SPMDCHECK_MOD)
    _SPMDCHECK_MOD.install()

import jax  # noqa: E402

# tests run on an 8-device virtual CPU mesh (must precede device use)
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual devices, got {len(devs)}"
    return devs


@pytest.fixture(autouse=True)
def _reset_engine_mesh():
    """Isolate tests from any globally-set Engine mesh."""
    from bigdl_tpu.engine import Engine
    prev = Engine._state.mesh
    yield
    Engine._state.mesh = prev


def pytest_report_header(config):
    # additive: each sanitizer contributes its own line, so running
    # both (the composition smoke test) reports both
    lines = []
    if _LOCKDEP_MOD is not None:
        lines.append("lockdep: lock-order sanitizer INSTALLED "
                     "(BIGDL_TPU_LOCKDEP) — cycles fail the session")
    if _SPMDCHECK_MOD is not None:
        lines.append("spmdcheck: collective-schedule sanitizer "
                     "INSTALLED (BIGDL_TPU_SPMDCHECK) — divergences "
                     "fail the session")
    return lines


def pytest_sessionfinish(session, exitstatus):
    """The sanitizer gates: a run under BIGDL_TPU_LOCKDEP=1 fails when
    any lock-order cycle was recorded; a run under
    BIGDL_TPU_SPMDCHECK=1 fails when any collective-schedule
    divergence was recorded.  Each gate reports independently — they
    must not clobber one another when both are live."""
    if _LOCKDEP_MOD is not None:
        cycles = _LOCKDEP_MOD.cycles()
        edges = len(_LOCKDEP_MOD.graph_edges())
        slow = len(_LOCKDEP_MOD.slow_holds())
        print(f"\nlockdep: {_LOCKDEP_MOD.proxies_allocated()} locks "
              f"instrumented, {edges} order edges, {len(cycles)} cycles, "
              f"{slow} slow holds")
        if cycles:
            for c in cycles:
                print(c.render())
            session.exitstatus = 1
    if _SPMDCHECK_MOD is not None:
        # intra-run index mismatches only: emulated participants from
        # different tests legitimately record different-LENGTH
        # schedules, so the length finalizer stays off at session scope
        divs = _SPMDCHECK_MOD.divergences()
        print(f"\nspmdcheck: {_SPMDCHECK_MOD.notes_recorded()} "
              f"collective notes, "
              f"{len(_SPMDCHECK_MOD.schedules())} participants, "
              f"{len(divs)} divergences")
        if divs:
            for d in divs:
                print(d.render())
            session.exitstatus = 1
