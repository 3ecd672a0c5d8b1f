"""chip_smoke.py and the bring-up contracts around it, checked on the CPU.

The chip run itself is the driver's; here the same body runs at a tiny
size on the 8-device CPU mesh with kernels interpreted, and the pieces
that decide whether a chip run can be trusted — the platform refusal,
the compile-cache placement, the native library's rebuild key — are
pinned one by one.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)
import chip_smoke  # noqa: E402


def test_body_runs_at_tiny_size_on_the_cpu_mesh(capsys):
    chip_smoke.run(chip_smoke.tiny_size(), interpret=True)
    out = capsys.readouterr().out
    assert "bit-equal to take (interpreted)" in out
    assert '"step_compiles": 1' in out and '"devices": 8' in out


def test_rows_digest_sees_a_lost_and_a_doubled_row():
    import numpy as np
    cols = [np.arange(6), np.arange(6) * 7]
    labels = np.linspace(0, 1, 6, dtype=np.float32)
    whole = chip_smoke._rows_digest(cols, labels)
    order = np.array([3, 0, 5, 1, 4, 2])
    assert chip_smoke._rows_digest([c[order] for c in cols],
                                   labels[order]) == whole
    doubled = np.array([0, 1, 2, 3, 4, 4])  # row 5 lost, row 4 twice
    assert chip_smoke._rows_digest([c[doubled] for c in cols],
                                   labels[doubled]) != whole


def test_main_refuses_to_run_off_the_chip(capsys):
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    assert captured.out == "", "no result may be printed off the chip"
    assert "refusing to run" in captured.err


def test_compile_cache_helper_places_the_cache(monkeypatch):
    import jax

    from ray_shuffling_data_loader_tpu.utils import compile_cache
    option = compile_cache.CACHE_DIR_OPTION
    threshold = "jax_persistent_cache_min_compile_time_secs"
    before = (getattr(jax.config, option), getattr(jax.config, threshold))
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.enable_compile_cache() == "/some/dir"
        assert getattr(jax.config, option) == before[0], (
            "a cache placed from outside is JAX's to read, not ours to set")
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO_ROOT, ".jax_cache")
        assert compile_cache.enable_compile_cache() == fixed
        assert getattr(jax.config, option) == fixed
    finally:
        jax.config.update(option, before[0])
        jax.config.update(threshold, before[1])


def test_native_library_is_rebuilt_when_its_source_changes(tmp_path):
    from ray_shuffling_data_loader_tpu import native
    src = str(tmp_path / "probe.cpp")
    with open(src, "w") as f:
        f.write('extern "C" int probe() { return 1; }\n')
    flags = ("-O1",)
    first = native.compiled_library(src, flags)
    assert os.path.dirname(first) == str(tmp_path)
    assert native.compiled_library(src, flags) == first  # reused as is
    # A library under the current name is trusted; one left by other
    # source is not — changing the source changes the name it is looked
    # up by, the library is built again, and the stale file is removed.
    shutil.copy(first, str(tmp_path / "libprobe-0123456789abcdef.so"))
    with open(src, "a") as f:
        f.write('extern "C" int probe2() { return 2; }\n')
    second = native.compiled_library(src, flags)
    assert second != first
    assert sorted(os.listdir(tmp_path)) == sorted(
        ["probe.cpp", os.path.basename(second)])
    import ctypes
    assert ctypes.CDLL(second).probe2() == 2


def test_native_build_failure_is_an_error(tmp_path):
    from ray_shuffling_data_loader_tpu import native
    src = str(tmp_path / "broken.cpp")
    with open(src, "w") as f:
        f.write("this is not C++\n")
    with pytest.raises(native.NativeBuildError):
        native.compiled_library(src, ("-O1",))
    assert os.listdir(tmp_path) == ["broken.cpp"]
