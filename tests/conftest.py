"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; all sharding tests run against
``--xla_force_host_platform_device_count=8`` on CPU (SURVEY.md §4).
This must run before jax is imported anywhere in the test process.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

# Runtime lock sanitizer (RSDL_LOCKSAN=1): must be live before any
# package module allocates its locks, and importing the package here
# would defeat that (runtime/__init__ eagerly pulls the threaded
# modules). Load locksan.py standalone, pre-seeded under its canonical
# name so the later package import reuses this module — and its
# recorded state — instead of a fresh, unpatched copy.
_LOCKSAN = None
if os.environ.get("RSDL_LOCKSAN") == "1":
    import importlib.util

    _repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    _locksan_name = "ray_shuffling_data_loader_tpu.runtime.locksan"
    _locksan_spec = importlib.util.spec_from_file_location(
        _locksan_name,
        os.path.join(_repo_root, "ray_shuffling_data_loader_tpu",
                     "runtime", "locksan.py"))
    _LOCKSAN = importlib.util.module_from_spec(_locksan_spec)
    sys.modules[_locksan_name] = _LOCKSAN
    _locksan_spec.loader.exec_module(_LOCKSAN)
    _LOCKSAN.install(root=_repo_root)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def tmp_parquet_dir(tmp_path):
    return str(tmp_path / "parquet")


@pytest.fixture(autouse=True, scope="session")
def _chipbench_scratch_of_this_worker(tmp_path_factory):
    """The benchmark's rehearsals in this process keep their run
    directories out of the checkout's ``.chipbench_scratch``:
    ``test_measurement_path_fails_without_a_tpu`` asserts that one empty
    after its own subprocess, and under several workers another worker's
    rehearsal stood in it (ROADMAP.md B9's flake; PR 40's added test files
    moved the schedule onto it in every whole run)."""
    from chipbench import harness
    checkout_scratch = harness.SCRATCH_ROOT
    harness.SCRATCH_ROOT = str(tmp_path_factory.mktemp("chipbench_scratch"))
    yield
    harness.SCRATCH_ROOT = checkout_scratch


@pytest.fixture
def placings():
    """``placings()``: the q and k projections the decoder has traced
    since the test began, by what places their heads
    (``rsdl_lm_place_total``'s kinds: ops/rope.py's kernels, ``vmem``, or
    XLA's passes, ``xla``). A test that wants the kernels off the chip
    sets ``ops.rope.on_tpu`` true: ``models/mellum.py`` asks its own
    ``on_tpu`` whether to interpret them, and that one stays false."""
    from ray_shuffling_data_loader_tpu.runtime import metrics

    def count(kind):
        metric = metrics.get("rsdl_lm_place_total", {"kind": kind})
        return 0 if metric is None else metric.value

    before = {kind: count(kind) for kind in ("vmem", "xla")}
    return lambda: {kind: count(kind) - was for kind, was in before.items()}


def pytest_sessionfinish(session, exitstatus):
    if _LOCKSAN is not None and _LOCKSAN.installed():
        out = _LOCKSAN.dump()
        g = _LOCKSAN.graph()
        cyc = _LOCKSAN.cycles(g)
        sys.stderr.write(
            f"\n[locksan] order graph -> {out}: {len(g['nodes'])} lock "
            f"site(s), {len(g['edges'])} edge(s), {len(g['events'])} "
            f"event(s), {len(cyc)} cycle(s)\n")
