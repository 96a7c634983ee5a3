"""Toolchain-drift guard + the CPU rehearsal of ``chip_smoke.py``.

- the jax version floor and the shard_map API shape this repo depends
  on (``from jax import shard_map`` + ``check_vma=``) are asserted, so
  the next upgrade fails CI instead of silently changing semantics;
- ``chip_smoke.py`` — the one proof that the main path runs on the chip
  — is run in-process at its tiny size (one device, and its four-chip
  path on four of the suite's virtual devices), so the script cannot
  rot between chip runs; and it must refuse a full-size run off-TPU;
- the compile-cache helper keeps its placement contract.

Nothing here starts a process that could take the accelerator: the
only child is pinned to the CPU and asks one question (where would the
compile cache go).
"""

import json
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def test_jax_version_floor():
    import jaxlib
    ver = tuple(int(p) for p in jax.__version__.split(".")[:2])
    assert ver >= (0, 9), (
        f"jax {jax.__version__} < 0.9: the shard_map API contract and "
        f"the kernels' compile gates were settled under 0.9")
    # same-series skew (jax 0.9.1 / jaxlib 0.9.0) is allowed by jax's
    # own policy; hold jaxlib to what the installed jax says it needs
    from jax.version import _minimum_jaxlib_version as floor
    have = tuple(int(p) for p in jaxlib.__version__.split(".")[:3])
    need = tuple(int(p) for p in floor.split(".")[:3])
    assert have >= need, (
        f"jaxlib {jaxlib.__version__} is older than the minimum "
        f"({floor}) jax {jax.__version__} supports")


def test_shard_map_api_shape():
    # the repo-wide import path and kwarg (parallel/grad_sync.py,
    # parallel/pipeline.py, parallel/ring_attention.py)
    from jax import shard_map
    import inspect
    params = inspect.signature(shard_map).parameters
    assert "check_vma" in params, list(params)
    assert "mesh" in params and "in_specs" in params \
        and "out_specs" in params


# ------------------------------------------------- chip_smoke rehearsal
@pytest.fixture
def smoke(monkeypatch):
    """``chip_smoke`` with the persistent compile cache left off (the
    suite never turns it on) and the Engine restored afterwards."""
    import chip_smoke
    from bigdl_tpu.engine import Engine
    monkeypatch.setattr(Engine, "enable_compile_cache",
                        staticmethod(lambda: "<off under pytest>"))
    yield chip_smoke
    Engine.reset()


def _last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_chip_smoke_refuses_full_size_off_tpu(smoke, capsys):
    """No accelerator, no ``--tiny``: fail, and print no result."""
    with pytest.raises(smoke.SmokeFailure, match="no TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_rehearsal_one_device(smoke, capsys):
    """train → kernels → serve, the chip run's code at toy sizes with
    the kernels under the Pallas interpreter."""
    assert smoke.main(["--tiny"]) == 0
    last = _last_line(capsys.readouterr().out)
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["phases_passed"] == ["train", "kernels", "serve"]
    assert last["device"]["platform"] == "cpu"


def test_chip_smoke_rehearsal_four_devices(smoke, capsys, devices):
    """The ``--chips 4`` path on four of the suite's virtual devices:
    DP vs one device, then a replica per device — and nothing else."""
    assert smoke.main(["--tiny", "--chips", "4"]) == 0
    out = capsys.readouterr().out
    assert "4 distinct devices" in out and "per replica" in out
    last = _last_line(out)
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["phases_passed"] == ["dp", "replicas"]


# ---------------------------------------------------------- compile cache
@pytest.fixture
def cache_config():
    """Restore the jax.config values the helper may touch."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in saved.items():
        jax.config.update(n, v)


def test_compile_cache_env_placement_is_left_alone(monkeypatch,
                                                   cache_config, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets no directory
    of its own: jax.config keeps what the environment gave it."""
    from bigdl_tpu.engine import Engine
    placed = str(tmp_path / "placed_from_outside")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    # what JAX itself does with the variable at import
    jax.config.update("jax_compilation_cache_dir", placed)
    assert Engine.enable_compile_cache() == placed
    assert jax.config.jax_compilation_cache_dir == placed
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_default_is_one_fixed_checkout_path(monkeypatch,
                                                          cache_config):
    """Unset, the cache is <checkout>/.jax_cache — the same in two calls
    and in another process; no tempdir, pid or time in the path."""
    from bigdl_tpu.engine import Engine
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".jax_cache")
    assert Engine.enable_compile_cache() == want
    assert Engine.enable_compile_cache() == want
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"  # the child never asks for the chip
    r = subprocess.run(
        [sys.executable, "-c",
         "from bigdl_tpu.engine import Engine; "
         "print(Engine.enable_compile_cache())"],
        capture_output=True, text=True, timeout=120, env=env, cwd=ROOT)
    assert r.returncode == 0, r.stderr[-1000:]
    assert r.stdout.strip().splitlines()[-1] == want
