"""``ops/fastcopy.copy_into``: the native copy into a row-major
destination from a source of any strides (``jax.device_get`` hands a
leaf back in the device buffer's dimension order), against numpy's
``ascontiguousarray`` bit for bit; and the flash save that relies on
it, round trip on the CPU backend."""

import itertools

import ml_dtypes
import numpy as np
import pytest

from dlrover_tpu.ops import fastcopy

DTYPES = {
    "u1": np.uint8,
    "f2": np.float16,
    "bf16": ml_dtypes.bfloat16,
    "f4": np.float32,
    "f8": np.float64,
}


def _random(shape, dtype):
    """Random BITS of ``dtype`` (NaN patterns included)."""
    dtype = np.dtype(dtype)
    n = int(np.prod(shape, dtype=np.int64))
    return np.random.default_rng(n + dtype.itemsize).integers(
        0, 256, size=n * dtype.itemsize, dtype=np.uint8
    ).view(dtype).reshape(shape)


def _permuted(shape, perm):
    """A view of logical shape ``shape`` whose dense buffer is laid
    out in the axis order ``perm`` (major to minor): what
    ``device_get`` returns for a device layout ``major_to_minor=perm``."""
    def build(dtype):
        base = _random([shape[a] for a in perm], dtype)
        return base.transpose(np.argsort(perm))
    return build


def _sliced(cut):
    def build(dtype):
        return cut(_random((41, 70), dtype))
    return build


SOURCES = {
    # 2-D, the fetched weights' case; neither side a tile multiple
    "fc_out_T": _permuted((640, 160), (1, 0)),
    "wte_T_cut": _permuted((393, 100), (1, 0)),
    "tile_plus_1": _permuted((65, 129), (1, 0)),
    "row": _permuted((1, 77), (1, 0)),
    "column": _permuted((77, 1), (1, 0)),
    "empty": _permuted((0, 5), (1, 0)),
    **{
        "3d_" + "".join(map(str, p)): _permuted((7, 66, 33), p)
        for p in itertools.permutations(range(3)) if p != (0, 1, 2)
    },
    **{
        "4d_" + "".join(map(str, p)): _permuted((3, 5, 70, 9), p)
        for p in [(3, 2, 1, 0), (0, 1, 3, 2), (2, 0, 1, 3), (1, 3, 0, 2)]
    },
    # not a permutation of a dense buffer: the plain loop
    "every_other": _sliced(lambda a: a[::2, ::3]),
    "window": _sliced(lambda a: a[5:30, 10:60]),
    "window_T": _sliced(lambda a: a[5:30, 10:60].T),
    "reversed": _sliced(lambda a: a[::-1, ::-1]),
    "broadcast": _sliced(lambda a: np.broadcast_to(a[3], (6, 70))),
}


def _bits(a):
    return np.ascontiguousarray(a).tobytes()


@pytest.mark.parametrize("threads", [1, 3])
@pytest.mark.parametrize("source", sorted(SOURCES))
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_strided_source_is_copied_like_ascontiguousarray(
    dtype, source, threads, monkeypatch
):
    monkeypatch.setenv("DLROVER_SAVE_WORKERS", str(threads))
    src = SOURCES[source](DTYPES[dtype])
    dst = np.full(src.shape, 0xAB, np.uint8).astype(src.dtype)
    native = fastcopy.copy_into(dst, src)
    assert dst.tobytes() == _bits(src)
    if fastcopy.native_available():
        # the strided pass ran wherever there was something strided
        assert native == (
            src.size > 0 and not src.flags["C_CONTIGUOUS"]
        )


@pytest.mark.parametrize("threads", [1, 3, 8])
@pytest.mark.parametrize("source", ["2d", "3d", "window"])
@pytest.mark.parametrize("dtype", ["bf16", "f4"])
def test_a_leaf_of_several_mib_is_split_over_threads(
    dtype, source, threads, monkeypatch
):
    """Above a MiB a leaf is split over ``save_workers()`` threads
    (work units that do not divide evenly among them included)."""
    monkeypatch.setenv("DLROVER_SAVE_WORKERS", str(threads))
    build = {
        "2d": _permuted((1601, 1403), (1, 0)),
        "3d": _permuted((7, 301, 1027), (2, 0, 1)),
        "window": lambda dt: _random((1500, 1700), dt)[3:, 5:1500],
    }[source]
    src = build(DTYPES[dtype])
    assert src.nbytes > 3 * 2**20
    dst = np.empty(src.shape, src.dtype)
    fastcopy.copy_into(dst, src)
    assert dst.tobytes() == _bits(src)


def test_strided_copy_leaves_the_neighbours_alone():
    """The destination is a slice of a larger buffer (as in the shm
    segment): not a byte before or after it is written."""
    src = _permuted((130, 67), (1, 0))(np.uint16)
    buf = np.full(src.size + 64, 0xFFFF, np.uint16)
    dst = buf[32:32 + src.size].reshape(src.shape)
    fastcopy.copy_into(dst, src)
    assert dst.tobytes() == _bits(src)
    assert (buf[:32] == 0xFFFF).all() and (buf[-32:] == 0xFFFF).all()


class _Spy:
    """The loaded library with its calls counted."""

    def __init__(self, lib):
        self.calls = []
        self._lib = lib

    def dlrover_fastcopy(self, *args):
        self.calls.append(("dlrover_fastcopy", args))
        return self._lib.dlrover_fastcopy(*args)

    def dlrover_fastcopy_strided(self, *args):
        self.calls.append(("dlrover_fastcopy_strided", args))
        return self._lib.dlrover_fastcopy_strided(*args)


@pytest.fixture()
def spy(monkeypatch):
    if not fastcopy.native_available():
        pytest.skip("no native toolchain")
    spy = _Spy(fastcopy._lib)
    monkeypatch.setattr(fastcopy, "_lib", spy)
    return spy


def test_c_contiguous_source_is_the_plain_native_memcpy(spy):
    src = _random((33, 65), np.float32)
    dst = np.empty_like(src)
    assert fastcopy.copy_into(dst, src) is False
    assert spy.calls == [(
        "dlrover_fastcopy",
        (dst.ctypes.data, src.ctypes.data, src.nbytes),
    )]
    assert dst.tobytes() == src.tobytes()


def test_chunked_copy_stays_on_the_plain_native_memcpy(spy):
    """The restore pipeline passes C-contiguous pieces."""
    src = _random((64, 1024), np.float32)
    dst = np.empty_like(src)
    fastcopy.copy_into_chunked(dst, src, chunk_bytes=64 * 1024)
    assert {name for name, _ in spy.calls} == {"dlrover_fastcopy"}
    assert sum(args[2] for _, args in spy.calls) == src.nbytes
    assert dst.tobytes() == src.tobytes()


def test_strided_source_takes_the_strided_entry_point_once(spy):
    src = _permuted((70, 130), (1, 0))(ml_dtypes.bfloat16)
    dst = np.empty(src.shape, src.dtype)
    assert fastcopy.copy_into(dst, src) is True
    assert [name for name, _ in spy.calls] == ["dlrover_fastcopy_strided"]


@pytest.mark.parametrize("source", ["fc_out_T", "3d_201", "every_other"])
def test_without_the_library_numpy_copies(source, monkeypatch):
    monkeypatch.setattr(fastcopy, "_tried", True)
    monkeypatch.setattr(fastcopy, "_lib", None)
    src = SOURCES[source](ml_dtypes.bfloat16)
    dst = np.empty(src.shape, src.dtype)
    assert fastcopy.copy_into(dst, src) is False
    assert dst.tobytes() == _bits(src)


@pytest.mark.parametrize("case", ["item_size_16", "other_dtype", "shape"])
def test_what_the_native_pass_does_not_take_goes_through_numpy(case):
    if case == "item_size_16":
        src = _random((9, 20), np.complex128).T
        dst = np.empty(src.shape, src.dtype)
    elif case == "other_dtype":
        src = np.arange(60, dtype=np.int32).reshape(6, 10).T
        dst = np.empty(src.shape, np.float32)
    else:  # same size, another shape: numpy decides (it raises)
        src = np.arange(60, dtype=np.int32).reshape(6, 10).T
        dst = np.empty((60,), np.int32)
        with pytest.raises(ValueError):
            fastcopy.copy_into(dst, src)
        return
    assert fastcopy.copy_into(dst, src) is False
    np.testing.assert_array_equal(dst, src)


def test_flash_save_of_a_column_major_device_leaf_round_trips(
    tmp_path, monkeypatch
):
    """A leaf the device keeps column-major comes back F-contiguous
    from ``device_get``; the save writes it row-major in one native
    pass and says so on the ``ckpt.save.memcpy`` span."""
    import jax
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    from dlrover_tpu.checkpoint.shm_handler import (
        CheckpointConfig,
        SharedMemoryHandler,
    )
    from dlrover_tpu.telemetry.events import read_events

    if not fastcopy.native_available():
        pytest.skip("no native toolchain")
    log = str(tmp_path / "events.jsonl")
    monkeypatch.setenv("DLROVER_EVENT_LOG", log)
    one = SingleDeviceSharding(jax.devices()[0])
    w = _random((130, 70), ml_dtypes.bfloat16)
    column_major = jax.device_put(
        w, Format(Layout(major_to_minor=(1, 0)), one)
    )
    fetched = jax.device_get(column_major)
    assert fetched.flags["F_CONTIGUOUS"]
    assert not fetched.flags["C_CONTIGUOUS"]
    state = {
        "w": column_major,
        "b": jax.device_put(_random((70,), np.float32), one),
        "host": _random((5, 6), np.float32),
        "step": 3,
    }
    handler = SharedMemoryHandler(
        0, host=True, job_name=f"fastcopy_{tmp_path.name}"
    )
    try:
        handler.save_state_dict(state, CheckpointConfig(step=3))
        config, back = handler.load_state_dict()
        assert config.step == 3 and back["step"] == 3
        for key in ("w", "b", "host"):
            want = np.asarray(jax.device_get(state[key]))
            assert back[key].shape == want.shape
            assert back[key].dtype == want.dtype
            assert back[key].tobytes() == _bits(want)
    finally:
        handler.unlink()
    (memcpy,) = [
        e for e in read_events(log)
        if e["type"] == "span" and e["name"] == "ckpt.save.memcpy"
    ]
    attrs = memcpy["attributes"]
    assert attrs["strided_leaves"] == 1
    assert attrs["strided_bytes"] == w.nbytes
    assert attrs["contiguous_s"] == 0.0
    assert 0.0 <= attrs["strided_s"] <= attrs["copy_s"]
