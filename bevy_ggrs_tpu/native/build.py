"""Build the native shared libraries (g++, no pybind11).

Each ``.cpp`` in this directory compiles to a sibling ``.so`` whose file
name carries a hash of the source text, lazily on first import of its
binding module. A library is reused only when its name matches the source
at hand — a copied tree (which keeps no useful mtimes) or an edited source
can never load a library built from other code. The data plane falls back
to pure Python only when asked to (``GGRS_NO_NATIVE=1``, see ``core.py``);
a failed build is an error.
"""

from __future__ import annotations

import contextlib
import glob
import hashlib
import os
import subprocess

_DIR = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(_DIR, "udp_poller.cpp")
CORE_SRC = os.path.join(_DIR, "session_core.cpp")


def _lib_prefix(src: str) -> str:
    stem = os.path.splitext(os.path.basename(src))[0]
    return os.path.join(os.path.dirname(src), f"_{stem}-")


def lib_path(src: str) -> str:
    """The ``.so`` path for ``src`` as it reads now (content-addressed)."""
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:12]
    return f"{_lib_prefix(src)}{digest}.so"


def build_lib(src: str, force: bool = False) -> str:
    """Compile ``src`` unless the library for its current content exists;
    returns the .so path. Libraries built from other content are removed.
    Raises on failure."""
    lib = lib_path(src)
    if not force and os.path.exists(lib):
        return lib
    tmp = f"{lib}.{os.getpid()}.tmp"  # unique per process: concurrent first
    # runs (two peers on one machine) must not clobber each other's output
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src, "-o", tmp]
    try:
        subprocess.run(cmd, check=True, capture_output=True, text=True)
        os.replace(tmp, lib)
    except subprocess.CalledProcessError as exc:
        raise RuntimeError(
            f"native build failed: {' '.join(cmd)}\n{exc.stderr}"
        ) from exc
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    for stale in glob.glob(f"{glob.escape(_lib_prefix(src))}*.so"):
        if stale != lib:
            with contextlib.suppress(FileNotFoundError):  # a racing peer
                os.remove(stale)
    return lib


def ensure_built(force: bool = False) -> str:
    """The UDP poller library."""
    return build_lib(SRC, force)


def ensure_core_built(force: bool = False) -> str:
    """The session data-plane core library."""
    return build_lib(CORE_SRC, force)


if __name__ == "__main__":
    print(ensure_built(force=True))
    print(ensure_core_built(force=True))
