"""Test fixtures: force an 8-device virtual CPU platform BEFORE jax imports.

This is the multi-device simulation strategy from SURVEY.md §4 — the
reference has no test suite at all (verification is operational only), so the
fake-device mesh is how we exceed it: TP/DP/EP sharding and disagg KV transfer
are all testable on CPU.
"""

import collections
import gc
import os
import shutil
import signal
import tempfile
import threading

os.environ["JAX_PLATFORMS"] = "cpu"
# the CPU loader's two ERROR lines about +prefer-no-scatter / +prefer-no-gather
# at every load from the session's compile cache are noise on the machine that
# compiled the entry; the driver reads its dots from this log
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Tests run on the CPU on purpose: 8 virtual devices stand in for a slice.
# The config update repeats the env var for a process in which jax was
# imported (and read JAX_PLATFORMS) before this file set it.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# --- test tiers -------------------------------------------------------------
# Nearly every engine-level test pays XLA CPU compiles. The driver runs tier-1
# (`-m "not slow"`) under six xdist workers (`-n 6 --dist loadfile`) against a
# limit of 1,470 s: the command is in /root/TESTS_LAST_RUN.json, the totals in
# ROADMAP.md D22. tests/compile_heavy.txt lists what `make test`'s fast tier
# (`-m "not slow and not compile_heavy"`) leaves out; `make test-full` runs
# everything.
_HEAVY_FILE = os.path.join(os.path.dirname(__file__), "compile_heavy.txt")
# tier-1's slowest, demoted to `slow` (D22: demoting hides, it does not shrink)
_SLOW_TIER_FILE = os.path.join(os.path.dirname(__file__), "slow_tier.txt")

# One phase (setup or call) of one test may take this long: four times and
# more the longest phase of a whole run under six busy workers (ROADMAP D22),
# so a hang costs one failure and not the run.
PHASE_LIMIT_S = 400.0


def _load_ids(path):
    try:
        with open(path) as f:
            return {ln.split(" #")[0].strip() for ln in f
                    if ln.strip() and not ln.startswith("#")}
    except OSError:
        return set()


def pytest_collection_modifyitems(config, items):
    tiers = [(_load_ids(_HEAVY_FILE), pytest.mark.compile_heavy,
              "tests/compile_heavy.txt"),
             (_load_ids(_SLOW_TIER_FILE), pytest.mark.slow,
              "tests/slow_tier.txt")]
    for ids, marker, label in tiers:
        matched = set()
        for item in items:
            if item.nodeid in ids:
                matched.add(item.nodeid)
                item.add_marker(marker)
        # staleness guard: a renamed/re-parametrized test silently dropping
        # out of the tier would regress the fast `make test` target (or
        # re-bloat tier-1) with no signal. Only meaningful on full-suite
        # collections — a path-scoped run (e.g. `pytest tests/test_ops.py`)
        # legitimately collects none of the others.
        stale = ids - matched
        if stale and len(items) > 200:
            import warnings

            warnings.warn(
                f"{label} has {len(stale)} entr(y/ies) matching "
                f"no collected test (renamed or removed?): "
                f"{sorted(stale)[:5]}", stacklevel=1)


def pytest_configure(config):
    """A program of a tiny model is compiled once a session: one persistent
    compilation cache, in a directory this run makes and removes, shared by
    the xdist workers. Never one that outlives the run: the suite's time, and
    every test that looks at compiling, would depend on what ran before."""
    shared = getattr(config, "workerinput", {}).get("jax_cache_dir")
    if shared is None:  # the controller, or a run without xdist, owns it
        shared = config._jax_cache_dir = tempfile.mkdtemp(prefix="jaxcc-")
    jax.config.update("jax_compilation_cache_dir", shared)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    node.workerinput["jax_cache_dir"] = node.config._jax_cache_dir


def pytest_unconfigure(config):
    if hasattr(config, "_jax_cache_dir"):
        shutil.rmtree(config._jax_cache_dir, ignore_errors=True)


def _limited(item, phase):
    """Arm the alarm around one phase of one test (main thread only; a test
    that installs a SIGALRM handler or timer of its own keeps it meanwhile)."""
    if threading.current_thread() is not threading.main_thread():
        return (yield)

    def past(signum, frame):
        pytest.fail(f"{item.nodeid}: {phase} ran past {PHASE_LIMIT_S} s")

    handler = signal.signal(signal.SIGALRM, past)
    armed = signal.setitimer(signal.ITIMER_REAL, PHASE_LIMIT_S)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, *armed)
        signal.signal(signal.SIGALRM, handler)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_setup(item):
    return (yield from _limited(item, "setup"))


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    return (yield from _limited(item, "call"))


def pytest_terminal_summary(terminalreporter):
    """Where the run's time went (ROADMAP D22's table), from the reports the
    controller already has."""
    busy, files, cases = (collections.Counter() for _ in range(3))
    for rep in (r for reps in terminalreporter.stats.values() for r in reps
                if hasattr(r, "when") and hasattr(r, "duration")):
        gateway = getattr(getattr(rep, "node", None), "gateway", None)
        busy[gateway.id if gateway else "main"] += rep.duration
        path = rep.nodeid.split("::")[0]
        files[path] += rep.duration
        cases[path] += rep.when == "call"
    terminalreporter.write_line(
        f"test-seconds {sum(busy.values()):.0f}; busy: " + ", ".join(
            f"{w} {s:.0f}" for w, s in sorted(busy.items())))
    for path, s in files.most_common(15):
        terminalreporter.write_line(f"{s:7.1f} s {cases[path]:4d} cases  {path}")


@pytest.fixture(scope="module", autouse=True)
def _programs_end_with_their_module():
    """A live CPU executable holds some 21 memory mappings, and a process may
    hold `vm.max_map_count` (65,530) of them: a worker that kept every
    module's programs ended a whole run at 62,359 (ROADMAP D22), and one past
    the limit dies in whatever allocates next. What a later module needs
    again it loads from the session's compile cache."""
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="session")
def eight_devices():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs
