"""Build the native loader shared library (g++, no pybind11 — plain C ABI for ctypes).

Invoked lazily on first import of ``data.native`` and cached by source mtime; also runnable
directly: ``python -m csed_514_project_distributed_training_using_pytorch_tpu.data._native.build``.
"""

from __future__ import annotations

import os
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
SOURCE = os.path.join(_DIR, "loader.cc")
LIBRARY = os.path.join(_DIR, "libnativeloader.so")


class BuildFailed(RuntimeError):
    """g++ was found and ran, and did not produce the library (compile error or
    timeout) — distinct from a machine with no toolchain, which is not an error."""


def build(force: bool = False) -> str | None:
    """Compile loader.cc → libnativeloader.so if stale/missing. Returns the library path,
    or None when there is no ``g++`` to run (callers fall back to numpy). A compiler that
    ran and failed raises :class:`BuildFailed` with its stderr.
    """
    if not force and os.path.exists(LIBRARY):
        try:
            if os.path.getmtime(LIBRARY) >= os.path.getmtime(SOURCE):
                return LIBRARY
        except OSError:
            return LIBRARY  # source missing (e.g. binary-only install): use the built .so
    # Compile to a per-process temp path, then atomically os.replace into place: every
    # process runs this same module (the framework's launch contract), so concurrent
    # builders must never interleave writes into the .so another process may be dlopening.
    tmp = f"{LIBRARY}.tmp.{os.getpid()}"
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           SOURCE, "-o", tmp, "-lz"]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=300)
        if proc.returncode != 0:
            raise BuildFailed(f"native loader build failed:\n{proc.stderr}")
        os.replace(tmp, LIBRARY)
    except FileNotFoundError:
        return None                       # no g++ on this machine
    except subprocess.TimeoutExpired as e:
        raise BuildFailed(f"native loader build timed out after {e.timeout}s") from e
    except OSError as e:                  # e.g. a read-only install directory
        raise BuildFailed(f"native loader build failed: {e}") from e
    finally:
        if os.path.exists(tmp):
            try:
                os.remove(tmp)
            except OSError:
                pass
    return LIBRARY


if __name__ == "__main__":
    path = build(force=True)
    print(f"built {path}")
