"""Native (C++) runtime components and their build driver.

Reference: the reference's native layer is TFPlus C++/CUDA ops and
ATorch csrc built by a JIT op builder (``atorch/ops/op_builder/
builder.py``; SURVEY.md §2.7).  Here: C++ sources compiled on demand
with g++ into shared libraries cached next to the package, loaded via
ctypes.
"""

import hashlib
import os
import platform
import subprocess
import threading
from typing import List, Optional

from dlrover_tpu.common.log import default_logger as logger

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_LOCK = threading.Lock()
_FLAGS = [
    "-O3", "-shared", "-fPIC", "-std=c++17", "-march=native",
    "-pthread",
]


def _machine_id() -> bytes:
    """What ``-march=native`` resolves against: the architecture and
    the first CPU's model and feature flags."""
    lines = [platform.machine().encode()]
    try:
        with open("/proc/cpuinfo", "rb") as f:
            for line in f:
                if line.startswith((b"model name", b"flags")):
                    lines.append(line.strip())
                elif not line.strip():
                    break  # end of the first CPU's block
    except OSError:
        pass
    return b"\n".join(lines)


def build_library(
    name: str, sources: Optional[List[str]] = None,
    extra_flags: Optional[List[str]] = None,
) -> str:
    """Compile ``sources`` (default ``<name>.cc``) into
    ``lib<name>.so`` if missing or stale; returns the .so path.

    The reference's op builder drives nvcc the same way
    (op_builder/builder.py:681); here the toolchain is plain g++ -O3.
    """
    sources = sources or [os.path.join(_SRC_DIR, f"{name}.cc")]
    build_dir = os.path.join(_SRC_DIR, "_build")
    os.makedirs(build_dir, exist_ok=True)

    # -march=native ties the binary to this CPU: flags and machine
    # are part of the key, so a _build/ carried to another machine by
    # a copy of the tree is rebuilt there, never loaded
    digest = hashlib.sha256(
        " ".join(_FLAGS + (extra_flags or [])).encode()
        + _machine_id()
    )
    for src in sources:
        with open(src, "rb") as f:
            digest.update(f.read())
    tag = digest.hexdigest()[:16]
    lib_path = os.path.join(build_dir, f"lib{name}-{tag}.so")
    if os.path.exists(lib_path):
        return lib_path

    with _BUILD_LOCK:
        if os.path.exists(lib_path):
            return lib_path
        cmd = ["g++", *_FLAGS, *sources, "-o", lib_path + ".tmp"] + (
            extra_flags or []
        )
        logger.info("building native lib: %s", " ".join(cmd))
        result = subprocess.run(  # noqa: S603
            cmd, capture_output=True, text=True
        )
        if result.returncode != 0:
            raise RuntimeError(
                f"native build of {name} failed:\n{result.stderr}"
            )
        os.replace(lib_path + ".tmp", lib_path)
    return lib_path
