"""GIL-free bulk copies for the checkpoint hot path.

ctypes foreign calls release the GIL, so routing the flat
array->shm memcpy through the tiny native helper keeps the trainer's
other threads (heartbeats, IPC replies, monitors) responsive while a
multi-GB snapshot streams — the reference gets this for free from
torch's C++ copy (ckpt_saver.py:174); numpy's ``copyto`` holds the
GIL the whole time.  Without the native library (no ``g++``) the
copies still work through numpy, and say so with a warning: callers
that need the GIL-free path check :func:`native_available`.
"""

import ctypes
import os
from typing import Optional

import numpy as np

from dlrover_tpu.common.log import default_logger as logger


def save_workers() -> int:
    """Thread count for the save-side chunked parallel memcpy
    (``DLROVER_SAVE_WORKERS``; the twin of the restore pipeline's
    ``DLROVER_RESTORE_WORKERS``).  1 means exact serial copies.
    Default sizes like the restore pool: half the cores, capped."""
    env = os.environ.get("DLROVER_SAVE_WORKERS", "").strip()
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return min(8, max(2, (os.cpu_count() or 2) // 2))

_lib: Optional[ctypes.CDLL] = None
_tried = False


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    try:
        from dlrover_tpu.native import build_library

        lib = ctypes.CDLL(build_library("fastcopy"))
        lib.dlrover_fastcopy.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
        ]
        lib.dlrover_fastcopy.restype = ctypes.c_size_t
        lib.dlrover_fastcopy_strided.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64),
            ctypes.c_size_t, ctypes.c_int,
        ]
        lib.dlrover_fastcopy_strided.restype = ctypes.c_size_t
        _lib = lib
    except (OSError, RuntimeError) as e:  # no g++ / build failed
        logger.warning(
            "native fastcopy is MISSING (%s): checkpoint copies hold "
            "the GIL through numpy", e,
        )
        _lib = None
    return _lib


def native_available() -> bool:
    """Whether the GIL-free native copy is loaded (builds it on first
    use)."""
    return _load() is not None


def copy_into(dst: np.ndarray, src: np.ndarray) -> bool:
    """dst[...] = src with the GIL released during the transfer.

    ``dst`` is C-contiguous with ``src``'s dtype and shape (the
    checkpoint path guarantees this).  A C-contiguous ``src`` is one
    native ``memcpy``.  Any other ``src`` (``jax.device_get`` hands a
    leaf back in the device buffer's dimension order, so a weight the
    TPU keeps column-major arrives as a transposed view) goes through
    the native strided pass, row-major into ``dst`` in one go, over
    :func:`save_workers` threads; returns True for that case alone.
    Without the library, or on a dtype / shape mismatch, falls back
    to ``np.copyto``.
    """
    lib = _load()
    if (
        lib is None
        or not dst.flags["C_CONTIGUOUS"]
        or dst.dtype != src.dtype
        or dst.size != src.size
    ):
        np.copyto(dst, src)
        return False
    if src.flags["C_CONTIGUOUS"]:
        lib.dlrover_fastcopy(
            dst.ctypes.data, src.ctypes.data, dst.nbytes
        )
        return False
    if dst.shape == src.shape and dst.size:
        dims = ctypes.c_int64 * src.ndim
        # 0 bytes written: an item size or a rank it does not take
        if lib.dlrover_fastcopy_strided(
            dst.ctypes.data, src.ctypes.data, src.ndim,
            dims(*src.shape), dims(*src.strides),
            src.itemsize, save_workers(),
        ):
            return True
    np.copyto(dst, src)
    return False


def copy_into_chunked(
    dst: np.ndarray,
    src: np.ndarray,
    submit=None,
    chunk_bytes: int = 64 * 2**20,
):
    """``dst[...] = src`` split into ~``chunk_bytes`` contiguous
    pieces.  Each piece is dispatched through ``submit(fn, *args)``
    (a thread-pool submit — the GIL-released :func:`copy_into` makes
    the pieces genuinely concurrent, page faults included) or run
    inline when ``submit`` is None; returns whatever ``submit``
    returned per piece so the caller can drain.  The restore pipeline
    uses this to parallelize the detach of one large leaf, where a
    single serial memcpy against a cold shm mapping is fault-bound.
    """
    if not (
        dst.flags["C_CONTIGUOUS"] and src.flags["C_CONTIGUOUS"]
    ):
        # reshape(-1) of a non-contiguous array is a COPY — chunk
        # writes would land in a temporary and dst stay untouched
        np.copyto(dst, src)
        return []
    d1, s1 = dst.reshape(-1), src.reshape(-1)
    if d1.size == 0:
        return []
    step = max(1, chunk_bytes // max(1, d1.dtype.itemsize))
    out = []
    for lo in range(0, d1.size, step):
        if submit is None:
            copy_into(d1[lo:lo + step], s1[lo:lo + step])
        else:
            out.append(submit(copy_into, d1[lo:lo + step], s1[lo:lo + step]))
    return out
