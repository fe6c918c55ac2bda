"""Backend initialisation and the compile-cache placement
(dynamo_tpu/utils/platform.py): one strict in-process init — the CPU only
when JAX_PLATFORMS=cpu asks for it, otherwise a TPU or a non-zero exit —
and one fixed, in-checkout home for everything the program builds."""

import logging
import os
import subprocess
import sys

import pytest

import dynamo_tpu.utils.platform as plat

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_explicit_cpu_returns_cpu_and_says_so(monkeypatch, caplog):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    with caplog.at_level(logging.WARNING, logger="dynamo_tpu.platform"):
        assert plat.init_backend() == "cpu"
    assert any(r.levelno == logging.WARNING and "CPU" in r.getMessage()
               for r in caplog.records)


def test_non_tpu_backend_without_explicit_cpu_exits(monkeypatch):
    """JAX quietly picks the CPU when it finds no accelerator and
    JAX_PLATFORMS is unset; the program must not follow it there."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit) as e:
        plat.init_backend()
    assert e.value.code not in (0, None)
    assert "no TPU" in str(e.value.code) and "'cpu'" in str(e.value.code)


def test_backend_that_fails_to_initialise_exits_naming_the_error(
        monkeypatch):
    import jax

    def boom():
        raise RuntimeError("Unable to initialize backend 'tpu': no device")

    monkeypatch.setenv("JAX_PLATFORMS", "cpu,")  # a list, not "cpu" alone
    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(SystemExit) as e:
        plat.init_backend()
    assert "Unable to initialize backend 'tpu'" in str(e.value.code)


def test_init_spawns_no_process_and_no_thread(monkeypatch):
    """A chip belongs to one process: a probe child that initialises it
    first takes it from its own parent."""
    import threading

    def forbidden(*a, **k):
        raise AssertionError("init_backend must not start a process")

    monkeypatch.setattr(subprocess, "Popen", forbidden)
    monkeypatch.setattr(subprocess, "run", forbidden)
    monkeypatch.setattr(os, "fork", forbidden)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    before = threading.active_count()
    assert plat.init_backend() == "cpu"
    assert threading.active_count() == before


def test_compile_cache_env_var_is_left_alone(monkeypatch, tmp_path):
    import jax

    placed = str(tmp_path / "placed-from-outside")
    monkeypatch.setenv(plat.COMPILE_CACHE_ENV, placed)
    before = jax.config.jax_compilation_cache_dir
    assert plat.enable_compile_cache() == placed
    assert os.environ[plat.COMPILE_CACHE_ENV] == placed
    # nothing set in code: JAX reads the variable itself
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout_and_ignored(monkeypatch):
    import jax

    monkeypatch.delenv(plat.COMPILE_CACHE_ENV, raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = plat.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert plat.enable_compile_cache() == first  # same across calls
    finally:
        # keep the rest of the suite off the persistent cache
        jax.config.update("jax_compilation_cache_dir", before)
    # ... and across processes: no pid, clock or temp name in the path
    code = ("from dynamo_tpu.utils.platform import build_home; "
            "print(build_home())")
    env = {k: v for k, v in os.environ.items()
           if k != plat.COMPILE_CACHE_ENV}
    homes = {subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                            capture_output=True, text=True,
                            check=True).stdout.strip() for _ in range(2)}
    assert homes == {plat.build_home()}
    assert first == os.path.join(plat.build_home(), "jax-comp-cache")
    # inside the checkout, never under ~, and git-ignored
    assert os.path.commonpath([first, REPO]) == REPO
    assert not first.startswith(os.path.expanduser("~") + os.sep + ".cache")
    rel = os.path.relpath(plat.build_home(), REPO)
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert rel + "/" in f.read().split()


def test_native_build_dir_shares_the_build_home(monkeypatch):
    from dynamo_tpu.runtime import native

    monkeypatch.delenv("DYNAMO_TPU_BUILD_DIR", raising=False)
    assert native._build_dir() == os.path.join(plat.build_home(), "native")


def test_a_phase_past_its_limit_fails_and_does_not_hang(request, monkeypatch):
    """tests/conftest.py arms an alarm around every test's setup and call:
    a sleep inside the armed hook ends at the limit with pytest's failure,
    and the alarm this test itself runs under is armed again afterwards."""
    import signal
    import time

    import conftest

    monkeypatch.setattr(conftest, "PHASE_LIMIT_S", 0.2)
    armed = conftest.pytest_runtest_call(request.node)
    next(armed)
    try:
        with pytest.raises(pytest.fail.Exception, match="call ran past 0.2 s"):
            time.sleep(30)
    finally:
        armed.close()
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0.2
