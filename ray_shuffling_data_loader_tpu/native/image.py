"""ctypes loader for the native batch image decoder (src/image_decode.cpp).

Built on first use (g++, linked against the system libjpeg/libpng) by the
same hash-named, atomically written build as the shuffle kernels
(``native.compiled_library``). :func:`available` is False only under
``RSDL_TPU_DISABLE_NATIVE`` — workloads/imagenet.py then decodes with PIL;
a build or load that fails without that switch raises.
"""

from __future__ import annotations

import ctypes
import os
import threading
from typing import List, Optional

import numpy as np

from ray_shuffling_data_loader_tpu.native import (NativeBuildError,
                                                  compiled_library)

_SRC = os.path.join(os.path.dirname(__file__), "src", "image_decode.cpp")
# Libraries after the source: the linker resolves left to right.
_FLAGS = ("-O2", "-std=c++17", "-pthread")
_LIBS = ("-ljpeg", "-lpng")

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False
_load_lock = threading.Lock()

_DEFAULT_THREADS = max(1, min(8, (os.cpu_count() or 1)))


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _load_attempted
    if _load_attempted:
        return _lib
    with _load_lock:
        if _load_attempted:
            return _lib
        if not os.environ.get("RSDL_TPU_DISABLE_NATIVE"):
            lib_path = compiled_library(_SRC, _FLAGS, _LIBS)
            try:
                lib = ctypes.CDLL(lib_path)
                lib.rsdl_decode_images.argtypes = [
                    ctypes.POINTER(ctypes.c_char_p),
                    ctypes.POINTER(ctypes.c_int64), ctypes.c_int64,
                    ctypes.c_int64, ctypes.c_int64,
                    ctypes.POINTER(ctypes.c_uint8), ctypes.c_int
                ]
                lib.rsdl_decode_images.restype = ctypes.c_int64
            except (OSError, AttributeError) as e:
                raise NativeBuildError(
                    f"cannot load {lib_path}: {e}") from e
            _lib = lib
        _load_attempted = True
        return _lib


def available() -> bool:
    """True if the native decoder is loaded; False only when
    ``RSDL_TPU_DISABLE_NATIVE`` turned it off."""
    return _load() is not None


def decode_batch(payloads: List[bytes], height: int, width: int,
                 nthreads: Optional[int] = None) -> np.ndarray:
    """Decode JPEG/PNG payloads to one ``(n, height*width*3)`` uint8 array.

    Raises ValueError naming the first payload that failed to decode or
    had the wrong dimensions.
    """
    lib = _load()
    assert lib is not None
    n = len(payloads)
    out = np.empty((n, height * width * 3), dtype=np.uint8)
    if n == 0:
        return out
    srcs = (ctypes.c_char_p * n)(*payloads)
    sizes = np.fromiter((len(p) for p in payloads), dtype=np.int64, count=n)
    rc = lib.rsdl_decode_images(
        srcs, sizes.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
        height, width, out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        nthreads or _DEFAULT_THREADS)
    if rc != 0:
        raise ValueError(
            f"image {rc - 1} failed to decode to ({height}, {width}, 3) — "
            "unsupported format or wrong dimensions; the TPU pipeline "
            "requires fixed shapes")
    return out
