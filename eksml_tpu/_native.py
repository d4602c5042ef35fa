"""Shared build-and-load machinery for the C++ native bridges.

Three subsystems ship a g++-built shared library with a ctypes C ABI
(pybind11 isn't available in the image): the comm-layer topology shim
(parallel/), the mask/RLE eval ops (evalcoco/), and the input-pipeline
image ops (data/).  Each bridge keeps only its symbol declarations;
the build-on-first-use / stale-source / graceful-fallback logic lives
here once.

Thread-safe: DetectionLoader worker threads can race into the first
load — a per-library lock makes sure exactly one `make` runs and the
library is mapped only after the build completed.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Callable, Optional

log = logging.getLogger(__name__)


class NativeLib:
    """Lazy builder/loader for one shared library.

    ``declare``: callback receiving the loaded CDLL to set
    argtypes/restype; a raised AttributeError (symbol mismatch from a
    stale binary) downgrades to the python fallback.
    """

    def __init__(self, lib_path: str, src_dir: str, src_name: str,
                 declare: Callable[[ctypes.CDLL], None]):
        self._lib_path = lib_path
        self._src_dir = src_dir
        self._src = os.path.join(src_dir, src_name)
        self._declare = declare
        self._lock = threading.Lock()
        self._lib: Optional[ctypes.CDLL] = None
        self._attempted = False

    def _stale(self) -> bool:
        try:
            return (os.path.getmtime(self._src)
                    > os.path.getmtime(self._lib_path))
        except OSError:
            return False

    def get(self) -> Optional[ctypes.CDLL]:
        if self._attempted:  # fast path, no lock once resolved
            return self._lib
        with self._lock:
            if self._attempted:
                return self._lib
            lib = self._load()
            self._lib = lib
            self._attempted = True
            return lib

    def _load(self) -> Optional[ctypes.CDLL]:
        name = os.path.basename(self._lib_path)
        if not os.path.exists(self._lib_path) or self._stale():
            try:
                subprocess.run(["make", "-C", self._src_dir], check=True,
                               capture_output=True, timeout=120)
            except Exception as e:  # noqa: BLE001 — build is optional
                log.debug("%s build failed: %s", name, e)
            if not os.path.exists(self._lib_path):
                log.info("%s unavailable; using python fallback", name)
                return None
            if self._stale():
                log.warning("%s source changed but rebuild failed; NOT "
                            "loading the stale binary — using python "
                            "fallback", name)
                return None
        try:
            lib = ctypes.CDLL(self._lib_path)
            self._declare(lib)
            return lib
        except (OSError, AttributeError) as e:
            # AttributeError: symbol mismatch (old binary / changed ABI)
            log.warning("failed to load %s: %s", self._lib_path, e)
            return None


def bridge_report() -> dict:
    """Resolve all three bridges (building from the tracked
    ``native_src/`` where the untracked ``.so`` is missing or stale)
    and say how each loaded: ``"native"`` or ``"python"`` (fallback)."""
    from eksml_tpu.data import native as data_native
    from eksml_tpu.evalcoco import native as eval_native
    from eksml_tpu.parallel import native as parallel_native

    return {name: "native" if mod.get_lib() is not None else "python"
            for name, mod in (("imageops", data_native),
                              ("maskops", eval_native),
                              ("topology", parallel_native))}
