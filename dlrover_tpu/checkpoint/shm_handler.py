"""Pytree <-> shared-memory serialization.

Reference: ``SharedMemoryHandler`` / ``TensorMeta``
(``dlrover/python/elastic_agent/torch/ckpt_saver.py:65,209``): a state
dict is traversed into one flat shared-memory buffer plus a meta dict
(shape/dtype/offset per leaf) published through a ``SharedDict``; the
agent process re-materializes tensors zero-copy with ``frombuffer``.

The JAX version traverses a pytree with ``jax.tree_util`` key paths.
Array leaves (jax/numpy) are device_get into the shm buffer — for a
sharded ``jax.Array`` only this host's addressable shards would be
copied by the sharded engine; this handler takes whatever ``np.asarray``
of the leaf yields.  Non-array leaves (step counters, strings, opt
hyperparams) are pickled into a trailing blob.
"""

import os
import pickle
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

try:  # registers bfloat16/fp8 dtypes with numpy for np.dtype(str)
    import ml_dtypes  # noqa: F401
except ImportError:  # pragma: no cover
    pass

from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.common.multi_process import (
    PersistentSharedMemory,
    SharedDict,
    get_or_create_shm,
)
from dlrover_tpu.telemetry.tracing import span as _span


@dataclass
class TensorMeta:
    """Placement of one array leaf inside the flat buffer
    (reference: ckpt_saver.py:65).  For a shard of a global sharded
    ``jax.Array`` (key suffixed ``@shardN``), ``global_shape`` and
    ``index`` carry the reassembly metadata (reference shard-aware
    analog: fsdp_engine.py:568)."""

    shape: Tuple[int, ...] = ()
    dtype: str = "float32"
    offset: int = 0
    nbytes: int = 0
    global_shape: Optional[Tuple[int, ...]] = None
    index: Optional[Tuple[Tuple[int, int], ...]] = None


@dataclass
class CheckpointConfig:
    """Per-snapshot metadata carried with the shm segment
    (reference: ckpt_saver.py:74)."""

    step: int = 0
    path: str = ""
    rank: int = 0
    world_size: int = 1
    # shards expected globally for the commit protocol
    global_shard_num: int = 1
    writing: bool = False
    extra: Dict[str, Any] = field(default_factory=dict)


def _flatten_state_dict(state_dict) -> Dict[str, Any]:
    """Pytree -> {"a/b/0": leaf} using jax key paths."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(state_dict)
    out = {}
    for path, leaf in flat:
        key = "/".join(_path_str(p) for p in path)
        out[key] = leaf
    return out


def _path_str(entry) -> str:
    import jax

    if isinstance(entry, jax.tree_util.DictKey):
        return str(entry.key)
    if isinstance(entry, jax.tree_util.SequenceKey):
        return str(entry.idx)
    if isinstance(entry, jax.tree_util.GetAttrKey):
        return str(entry.name)
    if isinstance(entry, jax.tree_util.FlattenedIndexKey):
        return str(entry.key)
    return str(entry)


def _unflatten_to_nested(flat: Dict[str, Any]) -> Dict[str, Any]:
    """{"a/b": v} -> {"a": {"b": v}}; integer-keyed dicts stay dicts
    (exact container types are the engine caller's concern — the state
    dict contract is string/index-keyed nesting, like the reference's
    torch state dicts)."""
    root: Dict[str, Any] = {}
    for key, value in flat.items():
        parts = key.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = value
    return root


def _extract_entries(state_dict):
    """Split a pytree into shm-layout entries: ``(entries, scalars,
    shard_info)`` where entries is ``[(key, leaf)]`` in layout order
    (numpy leaves materialized contiguous, device leaves left for the
    batched fetch), scalars the non-array leaves, and shard_info the
    reassembly metadata of ``@shardN`` entries."""
    from dlrover_tpu.checkpoint.sharded import (
        SHARD_SEP,
        is_sharded_leaf,
        local_shards,
    )

    flat = _flatten_state_dict(state_dict)
    entries = []  # (key, leaf) in shm layout order
    scalars: Dict[str, Any] = {}
    shard_info: Dict[str, Tuple[Tuple[int, ...], Tuple]] = {}
    for key, leaf in flat.items():
        if isinstance(leaf, (np.ndarray, np.generic)):
            entries.append((key, np.ascontiguousarray(leaf)))
        elif is_sharded_leaf(leaf):
            # global sharded array: only this process's addressable
            # shards go to shm, with reassembly metadata
            gshape = tuple(leaf.shape)
            for i, (ranges, data) in enumerate(local_shards(leaf)):
                skey = f"{key}{SHARD_SEP}{i}"
                entries.append((skey, data))
                shard_info[skey] = (gshape, ranges)
        elif type(leaf).__module__.startswith(("jaxlib", "jax")):
            entries.append((key, leaf))
        else:
            scalars[key] = leaf
    return entries, scalars, shard_info


def default_job_suffix() -> str:
    """Namespace shm segments per job so two jobs (or a test run next
    to a live job) on one host never collide: DLROVER_JOB_NAME if set,
    else a hash of the job's IPC socket dir (which agent and trainers
    already share)."""
    import hashlib

    from dlrover_tpu.common.multi_process import socket_dir

    name = os.getenv("DLROVER_JOB_NAME")
    if name:
        return name
    return hashlib.md5(socket_dir().encode()).hexdigest()[:8]


# -- paged base+delta shm layout (hot-save tier) ------------------------
#
# Segment anatomy (DLROVER_SHM_PAGED):
#
#   [ 0: 8]  magic  b"DLRVPG01"
#   [ 8: 9]  active directory slot (0/1) — the ATOMIC publish: a
#            single byte flips after everything the new generation
#            references is in place, so a reader or a SIGKILL
#            mid-write always lands on the previous consistent
#            snapshot
#   [12:16]  dir_cap (u32) — capacity of each directory slot
#   [16            : 16+dir_cap  ]  directory slot 0
#   [16+dir_cap    : 16+2*dir_cap]  directory slot 1
#   [data_off ...]  per-leaf ping-pong extents (A/B copy-on-write: a
#            delta save writes changed leaves to the INACTIVE side
#            and flips per-leaf `active` in the new directory), then
#            two kv arenas (base + delta blob pages bump-allocated;
#            a re-base targets the arena the live directory does NOT
#            reference)
#
# Each directory slot is [len u32 | crc32 u32 | pickled directory];
# the directory carries generation, config, per-leaf {offset, len,
# crc, gen} placement, the pickled scalar blob, and the kv page
# chain — so the segment stands alone even if the meta SharedDict
# host died with the trainer.

PAGED_MAGIC = b"DLRVPG01"
_PAGED_HDR = 16
_PAGED_ALIGN = 64


class PagedNeedBase(Exception):
    """The paged segment cannot accept a delta save (no valid epoch,
    leaf layout changed, kv arena or directory slot overflow) — the
    caller must re-export a full kv base and retry."""


def paged_enabled() -> bool:
    """``DLROVER_SHM_PAGED`` opt-in for the paged hot-save tier
    (default off: memory saves write the flat full segment)."""
    return os.environ.get(
        "DLROVER_SHM_PAGED", ""
    ).strip().lower() in ("1", "true", "yes", "on")


def shm_full_every() -> int:
    """Full-base cadence of the paged kv chain: every Nth paged save
    re-bases even without a poison, bounding both the delta replay a
    restore pays and the page directory's growth.  0 = no cadence
    (re-base only on poison/overflow).  ``DLROVER_SHM_FULL_EVERY``."""
    try:
        return max(
            0, int(os.environ.get("DLROVER_SHM_FULL_EVERY", "32"))
        )
    except ValueError:
        return 32


def save_chunk_bytes() -> int:
    """Chunk size of the save-side parallel memcpy
    (``DLROVER_SAVE_CHUNK_BYTES``; default 64 MB — the restore
    pipeline's twin)."""
    env = os.environ.get("DLROVER_SAVE_CHUNK_BYTES", "").strip()
    if env:
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return 64 * 2**20


def _align_up(n: int, a: int = _PAGED_ALIGN) -> int:
    return (n + a - 1) // a * a


def _crc(buf) -> int:
    import zlib

    return zlib.crc32(buf) & 0xFFFFFFFF


def _as_bytes_1d(arr: np.ndarray) -> np.ndarray:
    """A contiguous array reinterpreted as flat uint8 — the compare
    unit for bit-unchanged copy-skip (float equality would miscall
    NaN-bearing leaves as changed every save)."""
    return arr.reshape(-1).view(np.uint8)


class SharedMemoryHandler:
    """Owns one shm segment + meta SharedDict for one local rank."""

    SHM_PREFIX = "dlrover_tpu_ckpt_shm"
    META_PREFIX = "ckpt_meta"

    def __init__(self, local_rank: int, host: bool = False,
                 job_name: str = ""):
        self._rank = local_rank
        job_name = job_name or default_job_suffix()
        suffix = f"{job_name}_{local_rank}" if job_name else str(local_rank)
        self._shm_name = f"{self.SHM_PREFIX}_{suffix}"
        self._meta = SharedDict(
            f"{self.META_PREFIX}_{suffix}", create=host
        )
        self._shm: Optional[PersistentSharedMemory] = None
        self._write_lock = threading.Lock()
        # writer-side copy of the last published page directory (paged
        # mode); None = unknown — the next paged save tries to adopt
        # the in-segment directory before starting a fresh epoch
        self._paged_dir: Optional[Dict[str, Any]] = None
        # phase timings of the last save (seconds): the engine logs
        # them and the bench reports them — the dominant term of a
        # flash save must be measurable, not buried (VERDICT r2)
        self.last_save_phases: Dict[str, float] = {}

    # -- write (trainer side) ---------------------------------------------

    def save_state_dict(self, state_dict, config: CheckpointConfig):
        """Serialize the pytree into shm and publish the meta dict.

        Layout (metas) is computed from array avals BEFORE any
        transfer, then device leaves are fetched in ~256 MB batched
        chunks (``jax.device_get`` issues a chunk's transfers
        concurrently — per-leaf waits pay a transfer round trip per
        leaf)
        and memcpy'd chunk-by-chunk into shm, bounding extra host RAM
        to one chunk instead of a full second state copy.  The engine
        issues ``copy_to_host_async`` on the snapshot up front as a
        best-effort head start.  Note jax caches the host copy on
        each ``jax.Array`` (``_npy_value``): the async engine path
        drops its device snapshot right after this call, bounding
        that overhead to the save window.
        Reference hot path: _traverse_copy_to_shm, ckpt_saver.py:174.

        Phase timings land in ``last_save_phases`` (fetch_s = waiting
        on device->host transfers — the dominant term when the device
        is reached through a slow link; memcpy_s = shm writes).
        """
        import time as _time

        step = config.step
        with _span("ckpt.save.layout", step=step):
            entries, scalars, shard_info = _extract_entries(state_dict)
            scalar_blob = pickle.dumps(scalars)
            # a flat write clobbers any paged epoch in this segment;
            # the next paged save must start a fresh one
            self._paged_dir = None

            # layout from shapes/dtypes only — no transfer needed yet
            metas: Dict[str, TensorMeta] = {}
            offset = 0
            for key, arr in entries:
                gshape, ranges = shard_info.get(key, (None, None))
                dt = np.dtype(arr.dtype)
                count = int(np.prod(arr.shape, dtype=np.int64)) if (
                    arr.shape
                ) else 1
                nbytes = count * dt.itemsize
                metas[key] = TensorMeta(
                    shape=tuple(arr.shape),
                    dtype=str(dt),
                    offset=offset,
                    nbytes=nbytes,
                    global_shape=gshape,
                    index=ranges,
                )
                offset += nbytes
            total = offset + len(scalar_blob)

        t_fetch = 0.0
        t_memcpy = 0.0
        with self._write_lock:
            if self._shm is None or self._shm.size < total:
                with _span("ckpt.save.segment", step=step, bytes=total):
                    if self._shm is not None:
                        self._shm.close()
                        self._shm.unlink()
                        self._shm = None
                    self._shm = get_or_create_shm(self._shm_name, total)
            config.writing = True
            with _span("ckpt.save.publish_meta", step=step):
                self._publish_meta(
                    metas, config, offset, len(scalar_blob)
                )
            import jax

            from dlrover_tpu.ops.fastcopy import copy_into

            buf = self._shm.buf
            # leaves are fetched in BATCHED chunks: ``jax.device_get``
            # on a group issues all transfers concurrently (per-leaf
            # waits would pay one transfer round trip per leaf; a
            # host array passes through it untouched), while ~256 MB
            # chunks bound the extra host RAM.  A chunk is fetched,
            # then copied, then the next chunk is fetched: nothing
            # overlaps.  One ``fetch`` and one ``memcpy`` span a chunk.
            CHUNK = 256 * 2**20
            chunk: list = []
            chunk_bytes = 0

            def flush(chunk, chunk_bytes):
                nonlocal t_fetch, t_memcpy
                if not chunk:
                    return
                with _span(
                    "ckpt.save.fetch", step=step, bytes=chunk_bytes,
                    leaves=len(chunk),
                ) as sp:
                    fetched = jax.device_get([a for _, a in chunk])
                t_fetch += sp.duration
                with _span(
                    "ckpt.save.memcpy", step=step, bytes=chunk_bytes,
                    leaves=len(chunk),
                ) as sp:
                    # a fetched leaf comes in the device buffer's
                    # dimension order, not always row-major;
                    # ``copy_into`` writes either kind row-major into
                    # the segment in one native pass, GIL released: a
                    # multi-GB snapshot must not starve heartbeat/IPC
                    # threads.  ``strided_*`` count the leaves that
                    # were not row-major.
                    t_copy = t_strided = 0.0
                    strided_leaves = strided_bytes = 0
                    for (key, _), host in zip(chunk, fetched):
                        m = metas[key]
                        dst = np.frombuffer(
                            buf, dtype=np.dtype(m.dtype),
                            count=host.size, offset=m.offset,
                        ).reshape(m.shape)
                        t0 = _time.perf_counter()
                        strided = copy_into(dst, host)
                        dt = _time.perf_counter() - t0
                        t_copy += dt
                        if strided:
                            t_strided += dt
                            strided_leaves += 1
                            strided_bytes += m.nbytes
                    sp.set_attribute("copy_s", round(t_copy, 6))
                    # seconds in numpy making an array contiguous:
                    # none since the native pass takes strides
                    sp.set_attribute("contiguous_s", 0.0)
                    sp.set_attribute("strided_s", round(t_strided, 6))
                    sp.set_attribute("strided_leaves", strided_leaves)
                    sp.set_attribute("strided_bytes", strided_bytes)
                t_memcpy += t_copy
                chunk.clear()

            for i, (key, arr) in enumerate(entries):
                chunk.append((key, arr))
                chunk_bytes += metas[key].nbytes
                entries[i] = (key, None)  # free eagerly
                if chunk_bytes >= CHUNK:
                    flush(chunk, chunk_bytes)
                    chunk_bytes = 0
            flush(chunk, chunk_bytes)
            with _span("ckpt.save.scalars", step=step):
                buf[offset:offset + len(scalar_blob)] = scalar_blob
            config.writing = False
            with _span("ckpt.save.publish_meta", step=step):
                self._publish_meta(
                    metas, config, offset, len(scalar_blob)
                )
        self.last_save_phases = {
            "fetch_s": round(t_fetch, 3),
            "memcpy_s": round(t_memcpy, 3),
            "bytes": total,
        }
        # chaos hook: a corrupt_shm rule flips bytes of (or tears) the
        # snapshot that was just published, so restore/persist paths
        # must prove they reject or survive a damaged segment
        from dlrover_tpu import chaos as _chaos

        _chaos.fire("ckpt.shm_save", step=config.step, handler=self)
        logger.debug(
            "rank %s wrote %.1f MB checkpoint step %s to shm "
            "(fetch %.2fs, memcpy %.2fs)",
            self._rank, total / 2**20, config.step, t_fetch, t_memcpy,
        )

    def _publish_meta(
        self, metas: Dict[str, TensorMeta], config: CheckpointConfig,
        scalar_offset: int, scalar_nbytes: int,
    ):
        self._meta.set(
            {
                "tensors": metas,
                "config": config,
                "scalar_offset": scalar_offset,
                "scalar_nbytes": scalar_nbytes,
            }
        )

    # -- paged write (trainer side) ----------------------------------------

    def save_state_dict_paged(
        self, state_dict, config: CheckpointConfig,
        kv_payload: Optional[Tuple[str, Dict[str, Any]]] = None,
        workers: Optional[int] = None,
        chunk_bytes: Optional[int] = None,
    ) -> Dict[str, Any]:
        """Paged hot save: write only what changed, publish with an
        atomic directory swap.

        Dense leaves are compared bit-for-bit against their active
        extent and copy-skipped when unchanged; changed leaves go to
        the leaf's INACTIVE extent (per-leaf ping-pong copy-on-write)
        through a GIL-released chunked parallel copy
        (``DLROVER_SAVE_WORKERS``).  ``kv_payload`` is the sparse
        adapter's ``("base"|"delta", state)`` — the blob lands in a
        bump-allocated kv page (a base targets the arena the live
        directory does NOT reference).  Raises :class:`PagedNeedBase`
        when a delta cannot land (no valid epoch, layout changed,
        arena/directory overflow): the caller re-exports a full base
        and retries.  Returns the phase/byte accounting dict (also
        stored in ``last_save_phases``)."""
        import struct
        import time as _time

        from dlrover_tpu.ops import fastcopy

        entries, scalars, shard_info = _extract_entries(state_dict)
        scalars_blob = pickle.dumps(scalars)
        kv_kind = kv_payload[0] if kv_payload else None
        kv_blob = (
            pickle.dumps(kv_payload[1]) if kv_payload else b""
        )
        config.writing = False  # paged publishes are atomic, never torn

        metas: Dict[str, Dict[str, Any]] = {}
        order = []
        for key, arr in entries:
            gshape, ranges = shard_info.get(key, (None, None))
            dt = np.dtype(arr.dtype)
            count = int(np.prod(arr.shape, dtype=np.int64)) if (
                arr.shape
            ) else 1
            metas[key] = {
                "shape": tuple(arr.shape), "dtype": str(dt),
                "nbytes": count * dt.itemsize,
                "global_shape": gshape, "index": ranges,
            }
            order.append(key)

        if workers is None:
            workers = fastcopy.save_workers()
        if chunk_bytes is None:
            chunk_bytes = save_chunk_bytes()

        with self._write_lock:
            d = self._paged_dir
            if d is None:
                # a respawned writer adopts the in-segment epoch so
                # its first save stays O(touched) and never clobbers
                # the snapshot a concurrent restore may still need
                d = self._read_paged_directory(verify_pages=False)
            epoch_ok = self._paged_epoch_matches(d, order, metas)
            if epoch_ok and kv_kind == "base":
                other = 1 - int(d["kv_active"])
                cap = int(d["kv_arena"][other][1])
                epoch_ok = _align_up(len(kv_blob)) <= cap
            if epoch_ok and kv_kind is None and d.get("kv_pages"):
                # the sparse plane disappeared — pages would go stale
                epoch_ok = False
            if not epoch_ok:
                if kv_kind == "delta":
                    raise PagedNeedBase(
                        "no valid paged epoch for a delta save"
                    )
                prev_gen = int(d.get("generation", 0)) if (
                    isinstance(d, dict)
                ) else 0
                d = self._paged_new_epoch(
                    order, metas, len(kv_blob), len(scalars_blob),
                    prev_gen=prev_gen,
                )
                fresh = True
            else:
                fresh = False
            buf = self._shm.buf
            gen = int(d["generation"]) + (0 if fresh else 1)
            new_leaves = {k: dict(v) for k, v in d["leaves"].items()}

            t_fetch = t_compare = t_memcpy = t_kv = 0.0
            copied = skipped = pages = 0
            futures: list = []
            pool = None
            if workers > 1:
                from concurrent.futures import ThreadPoolExecutor

                pool = ThreadPoolExecutor(
                    max_workers=workers,
                    thread_name_prefix="shm-save",
                )
            submit = pool.submit if pool is not None else None

            def handle(key, host):
                nonlocal t_compare, t_memcpy, copied, skipped, pages
                host = np.ascontiguousarray(host)
                slot = new_leaves[key]
                nbytes = slot["nbytes"]
                host_b = _as_bytes_1d(host) if nbytes else host
                if not fresh and nbytes:
                    cur_off = (
                        slot["off_a"] if slot["active"] == 0
                        else slot["off_b"]
                    )
                    t0 = _time.perf_counter()
                    cur = np.frombuffer(
                        buf, dtype=np.uint8, count=nbytes,
                        offset=cur_off,
                    )
                    same = np.array_equal(cur, host_b)
                    t_compare += _time.perf_counter() - t0
                    if same:
                        skipped += nbytes
                        return
                    side = 1 - int(slot["active"])
                else:
                    side = 0
                dst_off = slot["off_a"] if side == 0 else slot["off_b"]
                dst = np.frombuffer(
                    buf, dtype=np.uint8,
                    count=max(1, nbytes), offset=dst_off,
                )[:nbytes]
                t0 = _time.perf_counter()
                futures.extend(
                    fastcopy.copy_into_chunked(
                        dst, host_b, submit=submit,
                        chunk_bytes=chunk_bytes,
                    )
                    or []
                )
                t_memcpy += _time.perf_counter() - t0
                slot["active"] = side
                slot["gen"] = gen
                slot["crc"] = _crc(host_b)
                copied += nbytes
                pages += 1

            try:
                CHUNK = 256 * 2**20
                chunk: list = []
                pending = 0

                def flush(chunk):
                    nonlocal t_fetch
                    if not chunk:
                        return
                    t0 = _time.perf_counter()
                    import jax

                    fetched = jax.device_get([a for _, a in chunk])
                    t_fetch += _time.perf_counter() - t0
                    for (key, _), host in zip(chunk, fetched):
                        handle(key, host)

                for i, (key, arr) in enumerate(entries):
                    if isinstance(arr, np.ndarray):
                        handle(key, arr)
                    else:
                        chunk.append((key, arr))
                        pending += metas[key]["nbytes"]
                        if pending >= CHUNK:
                            flush(chunk)
                            chunk, pending = [], 0
                    entries[i] = (key, None)  # free eagerly
                flush(chunk)
                t0 = _time.perf_counter()
                for f in futures:
                    f.result()
                t_memcpy += _time.perf_counter() - t0
            finally:
                if pool is not None:
                    pool.shutdown(wait=True)

            # kv blob page (base -> the other arena; delta -> bump)
            kv_pages = list(d.get("kv_pages") or ())
            kv_active = int(d.get("kv_active", 0))
            kv_tail = int(d.get("kv_tail", 0))
            if kv_kind is not None:
                t0 = _time.perf_counter()
                if kv_kind == "base":
                    kv_active = 0 if fresh else 1 - kv_active
                    arena_off, arena_cap = d["kv_arena"][kv_active]
                    page_off = int(arena_off)
                    kv_pages = []
                else:
                    arena_off, arena_cap = d["kv_arena"][kv_active]
                    page_off = kv_tail
                    if (
                        page_off + len(kv_blob)
                        > int(arena_off) + int(arena_cap)
                    ):
                        raise PagedNeedBase(
                            "kv delta arena overflow "
                            f"({page_off - int(arena_off)}"
                            f"+{len(kv_blob)} > {arena_cap})"
                        )
                buf[page_off:page_off + len(kv_blob)] = kv_blob
                kv_pages.append({
                    "kind": kv_kind, "step": int(config.step),
                    "off": page_off, "len": len(kv_blob),
                    "crc": _crc(kv_blob), "gen": gen,
                })
                kv_tail = _align_up(page_off + len(kv_blob))
                copied += len(kv_blob)
                pages += 1
                t_kv = _time.perf_counter() - t0

            new_dir = {
                "generation": gen,
                "config": config,
                "order": order,
                "leaves": new_leaves,
                "scalars_blob": scalars_blob,
                "kv_pages": kv_pages,
                "kv_arena": d["kv_arena"],
                "kv_active": kv_active,
                "kv_tail": kv_tail,
                "data_end": d["data_end"],
                "dir_cap": d["dir_cap"],
            }
            payload = pickle.dumps(new_dir)
            if len(payload) + 8 > int(d["dir_cap"]):
                if kv_kind == "delta":
                    raise PagedNeedBase("page directory slot overflow")
                raise RuntimeError(
                    "paged directory exceeds its slot even on a "
                    f"fresh epoch ({len(payload)} > {d['dir_cap']})"
                )
            # chaos hook: a kill here lands BETWEEN the data/page
            # writes and the directory publish — the crash-consistency
            # tests prove readers still see the previous generation
            from dlrover_tpu import chaos as _chaos

            _chaos.fire(
                "ckpt.paged_write", step=config.step, handler=self,
                generation=gen, kind="base" if fresh else "delta",
            )
            t0 = _time.perf_counter()
            dir_cap = int(d["dir_cap"])
            prev_slot = None if fresh else self._paged_active_slot()
            new_header = fresh or prev_slot is None
            slot_idx = 0 if new_header else 1 - prev_slot
            slot_off = _PAGED_HDR + slot_idx * dir_cap
            buf[slot_off + 8:slot_off + 8 + len(payload)] = payload
            struct.pack_into(
                "<II", buf, slot_off, len(payload), _crc(payload)
            )
            if new_header:
                # invalidate the other slot BEFORE the magic goes in:
                # a reader must never parse pre-epoch garbage
                other_off = _PAGED_HDR + (1 - slot_idx) * dir_cap
                struct.pack_into("<II", buf, other_off, 0, 0)
                struct.pack_into("<I", buf, 12, dir_cap)
                buf[0:8] = PAGED_MAGIC
            buf[8] = slot_idx  # THE atomic publish
            self._paged_dir = new_dir
            self._meta.set({
                "paged": True,
                "tensors": {},
                "config": config,
                "generation": gen,
                "scalar_offset": int(d["data_end"]),
                "scalar_nbytes": 0,
            })
            t_publish = _time.perf_counter() - t0

        total = sum(m["nbytes"] for m in metas.values()) + len(kv_blob)
        self.last_save_phases = {
            "fetch_s": round(t_fetch, 4),
            "compare_s": round(t_compare, 4),
            "memcpy_s": round(t_memcpy, 4),
            "kv_s": round(t_kv, 4),
            "publish_s": round(t_publish, 4),
            "paged": True,
            "kind": "base" if fresh else "delta",
            "generation": gen,
            "pages_written": pages,
            "bytes": int(copied),
            "bytes_skipped": int(skipped),
            "bytes_total": int(total),
            "kv_bytes": len(kv_blob),
        }
        _chaos.fire("ckpt.shm_save", step=config.step, handler=self)
        logger.debug(
            "rank %s paged save step %s gen %s: %s, wrote %d pages "
            "%.1f MB (skipped %.1f MB of %.1f MB)",
            self._rank, config.step, gen,
            "base" if fresh else "delta", pages, copied / 2**20,
            skipped / 2**20, total / 2**20,
        )
        return dict(self.last_save_phases)

    def _paged_epoch_matches(
        self, d: Optional[Dict[str, Any]], order, metas,
    ) -> bool:
        """A directory can absorb a delta save only if the dense leaf
        layout is unchanged — same keys in the same order with the
        same shapes/dtypes (their extents are preallocated)."""
        if not isinstance(d, dict) or d.get("order") != order:
            return False
        leaves = d.get("leaves") or {}
        for key in order:
            e = leaves.get(key)
            m = metas[key]
            if (
                e is None
                or tuple(e["shape"]) != tuple(m["shape"])
                or e["dtype"] != m["dtype"]
                or int(e["nbytes"]) != int(m["nbytes"])
            ):
                return False
        return self._attach(min_size=int(d.get("data_end", 0))) is not None

    def _paged_new_epoch(
        self, order, metas, kv_len: int, scalars_len: int,
        prev_gen: int = 0,
    ) -> Dict[str, Any]:
        """Lay out a fresh epoch: directory slots, per-leaf ping-pong
        extents, two kv arenas — and size/(re)create the segment.
        Returns the epoch skeleton (generation = next to publish)."""
        leaves: Dict[str, Dict[str, Any]] = {}
        # directory capacity: a prototype pickle of the fully
        # populated directory, doubled, plus headroom for the kv page
        # chain the epoch will accumulate
        proto = {
            k: {**m, "off_a": 0, "off_b": 0, "active": 0,
                "gen": 0, "crc": 0}
            for k, m in metas.items()
        }
        proto_len = len(pickle.dumps({
            "generation": 0, "config": CheckpointConfig(),
            "order": order, "leaves": proto,
            "scalars_blob": b"\0" * scalars_len,
            "kv_pages": [], "kv_arena": ((0, 0), (0, 0)),
            "kv_active": 0, "kv_tail": 0, "data_end": 0,
            "dir_cap": 0,
        }))
        dir_cap = _align_up(2 * proto_len + 65536)
        off = _align_up(_PAGED_HDR + 2 * dir_cap)
        for key in order:
            m = metas[key]
            ext = _align_up(int(m["nbytes"]))
            leaves[key] = {
                **m, "off_a": off, "off_b": off + ext,
                "active": 0, "gen": 0, "crc": 0,
            }
            off += 2 * ext
        kv_cap = 0
        arenas = ((0, 0), (0, 0))
        if kv_len:
            kv_cap = _align_up(kv_len + max(kv_len // 2, 1 << 20))
            arenas = ((off, kv_cap), (off + kv_cap, kv_cap))
            off += 2 * kv_cap
        total = off
        if self._shm is None or self._shm.size < total:
            if self._shm is not None:
                logger.warning(
                    "paged epoch needs %d bytes > segment %d: "
                    "recreating (previous snapshot discarded)",
                    total, self._shm.size,
                )
                self._shm.close()
                self._shm.unlink()
                self._shm = None
            self._shm = get_or_create_shm(self._shm_name, total)
        return {
            "generation": prev_gen + 1,
            "config": None,
            "order": order,
            "leaves": leaves,
            "scalars_blob": b"",
            "kv_pages": [],
            "kv_arena": arenas,
            "kv_active": 0,
            "kv_tail": int(arenas[0][0]),
            "data_end": total,
            "dir_cap": dir_cap,
        }

    def _paged_active_slot(self) -> Optional[int]:
        shm = self._attach(min_size=_PAGED_HDR)
        if shm is None or bytes(shm.buf[0:8]) != PAGED_MAGIC:
            return None
        slot = shm.buf[8]
        return int(slot) if slot in (0, 1) else None

    # -- paged read --------------------------------------------------------

    def _read_paged_directory(
        self, verify_pages: bool = True,
    ) -> Optional[Dict[str, Any]]:
        """Parse the in-segment page directory.  Tries the active
        slot first; a torn slot (bad length/CRC/pickle, or pages that
        fail their CRC) falls back to the other slot — the previous
        generation.  Returns None when neither slot verifies."""
        import struct

        shm = self._attach(min_size=_PAGED_HDR)
        if shm is None or shm.size < _PAGED_HDR:
            return None
        if bytes(shm.buf[0:8]) != PAGED_MAGIC:
            return None
        active = int(shm.buf[8])
        (dir_cap,) = struct.unpack_from("<I", shm.buf, 12)
        if active not in (0, 1) or dir_cap <= 8:
            return None
        if shm.size < _PAGED_HDR + 2 * dir_cap:
            shm = self._attach(min_size=_PAGED_HDR + 2 * dir_cap)
            if shm is None or bytes(shm.buf[0:8]) != PAGED_MAGIC:
                return None
        for slot in (active, 1 - active):
            off = _PAGED_HDR + slot * dir_cap
            ln, crc = struct.unpack_from("<II", shm.buf, off)
            if not 0 < ln <= dir_cap - 8:
                continue
            payload = bytes(shm.buf[off + 8:off + 8 + ln])
            if _crc(payload) != crc:
                logger.warning(
                    "paged directory slot %d torn (crc mismatch)%s",
                    slot,
                    "; falling back to the previous generation"
                    if slot == active else "",
                )
                continue
            try:
                d = pickle.loads(payload)
            except Exception:
                continue
            if not isinstance(d, dict) or "generation" not in d:
                continue
            data_end = int(d.get("data_end", 0))
            if data_end > shm.size:
                shm = self._attach(min_size=data_end)
                if shm is None:
                    continue
            if verify_pages and not self._paged_verify(d, shm.buf):
                logger.warning(
                    "paged generation %s fails page CRC; %s",
                    d.get("generation"),
                    "falling back to the previous generation"
                    if slot == active else "refusing the snapshot",
                )
                continue
            if slot != active:
                logger.warning(
                    "paged restore fell back to previous generation "
                    "%s", d.get("generation"),
                )
            return d
        return None

    def _paged_verify(self, d: Dict[str, Any], buf) -> bool:
        """Every extent/page the directory references must match its
        recorded CRC — a half-written or clobbered generation (e.g. a
        re-epoch that overwrote pages before dying) must not restore."""
        try:
            for key in d["order"]:
                e = d["leaves"][key]
                nbytes = int(e["nbytes"])
                if not nbytes:
                    continue
                off = e["off_a"] if int(e["active"]) == 0 else e["off_b"]
                got = _crc(np.frombuffer(
                    buf, dtype=np.uint8, count=nbytes, offset=int(off)
                ))
                if got != int(e["crc"]):
                    return False
            for p in d.get("kv_pages") or ():
                blob = np.frombuffer(
                    buf, dtype=np.uint8, count=int(p["len"]),
                    offset=int(p["off"]),
                )
                if _crc(blob) != int(p["crc"]):
                    return False
        except (KeyError, TypeError, ValueError, IndexError):
            return False
        return True

    def _paged_views(
        self, d: Dict[str, Any], buf,
    ) -> Tuple[Dict[str, np.ndarray], Dict[str, TensorMeta]]:
        """Views over each leaf's ACTIVE extent, plus flat-compatible
        TensorMetas (offset = extent offset) so every downstream
        consumer of (views, metas) works unchanged."""
        views: Dict[str, np.ndarray] = {}
        metas: Dict[str, TensorMeta] = {}
        for key in d["order"]:
            e = d["leaves"][key]
            off = int(
                e["off_a"] if int(e["active"]) == 0 else e["off_b"]
            )
            m = TensorMeta(
                shape=tuple(e["shape"]), dtype=e["dtype"],
                offset=off, nbytes=int(e["nbytes"]),
                global_shape=e.get("global_shape"),
                index=e.get("index"),
            )
            metas[key] = m
            views[key] = np.frombuffer(
                buf, dtype=np.dtype(m.dtype),
                count=int(np.prod(m.shape, dtype=np.int64))
                if m.shape else 1,
                offset=off,
            ).reshape(m.shape)
        return views, metas

    def _paged_kv_state(
        self, d: Dict[str, Any], buf,
    ) -> Optional[Dict[str, Any]]:
        """Replay the kv page chain (base + deltas) back to one full
        kv export — bit-identical to what a flat full save would have
        carried."""
        pages = d.get("kv_pages") or []
        if not pages:
            return None
        from dlrover_tpu.checkpoint.sparse import merge_kv_states

        blobs = [
            pickle.loads(bytes(
                buf[int(p["off"]):int(p["off"]) + int(p["len"])]
            ))
            for p in pages
        ]
        return merge_kv_states(blobs[0], blobs[1:])

    def _load_flat_paged(
        self, detach: bool = True, stats=None,
    ) -> Tuple[
        Optional[CheckpointConfig], Dict[str, Any], Dict[str, Any]
    ]:
        import time as _time

        from dlrover_tpu.checkpoint.restore import detach_flat
        from dlrover_tpu.checkpoint.sparse import KV_STATE_KEY

        t0 = _time.perf_counter()
        d = self._read_paged_directory(verify_pages=True)
        if d is None:
            logger.warning(
                "paged shm snapshot unreadable (torn or absent); "
                "refusing to load"
            )
            return None, {}, {}
        buf = self._shm.buf
        views, metas = self._paged_views(d, buf)
        kv = self._paged_kv_state(d, buf)
        if stats is not None:
            stats.read_s += _time.perf_counter() - t0
            if not detach:
                stats.bytes += sum(v.nbytes for v in views.values())
        flat = detach_flat(views, stats=stats) if detach else views
        flat.update(pickle.loads(d["scalars_blob"]))
        if kv is not None:
            flat.update(_flatten_state_dict({KV_STATE_KEY: kv}))
        return d["config"], flat, metas

    def _read_raw_paged(
        self,
    ) -> Tuple[Optional[CheckpointConfig], Any, Dict]:
        """Materialize the paged snapshot as FLAT raw bytes + flat
        meta — the agent's persist path (and the breakpoint save)
        consume the exact format a flat save would have produced, so
        the storage tier never learns about pages."""
        from dlrover_tpu.checkpoint.sparse import KV_STATE_KEY
        from dlrover_tpu.ops.fastcopy import copy_into

        d = self._read_paged_directory(verify_pages=True)
        if d is None:
            return None, b"", {}
        buf = self._shm.buf
        views, page_metas = self._paged_views(d, buf)
        scalars = dict(pickle.loads(d["scalars_blob"]))
        kv = self._paged_kv_state(d, buf)
        arrays: Dict[str, np.ndarray] = dict(views)
        if kv is not None:
            for k, v in _flatten_state_dict(
                {KV_STATE_KEY: kv}
            ).items():
                if isinstance(v, (np.ndarray, np.generic)):
                    arrays[k] = np.ascontiguousarray(v)
                else:
                    scalars[k] = v
        metas: Dict[str, TensorMeta] = {}
        offset = 0
        for key, arr in arrays.items():
            src = page_metas.get(key)
            dt = np.dtype(arr.dtype)
            count = int(np.prod(arr.shape, dtype=np.int64)) if (
                arr.shape
            ) else 1
            nbytes = count * dt.itemsize
            metas[key] = TensorMeta(
                shape=tuple(arr.shape), dtype=str(dt),
                offset=offset, nbytes=nbytes,
                global_shape=src.global_shape if src else None,
                index=src.index if src else None,
            )
            offset += nbytes
        blob = pickle.dumps(scalars)
        raw = bytearray(offset + len(blob))
        for key, arr in arrays.items():
            m = metas[key]
            if not m.nbytes:
                continue
            dst = np.frombuffer(
                raw, dtype=np.uint8, count=m.nbytes, offset=m.offset
            )
            copy_into(dst, _as_bytes_1d(np.ascontiguousarray(arr)))
        raw[offset:offset + len(blob)] = blob
        config: CheckpointConfig = d["config"]
        meta = {
            "tensors": metas,
            "config": config,
            "scalar_offset": offset,
            "scalar_nbytes": len(blob),
            "paged_generation": int(d["generation"]),
        }
        return config, bytes(raw), meta

    def paged_generation(self) -> int:
        """Generation of the currently readable paged snapshot (0 if
        none) — test/diagnostic surface."""
        d = self._read_paged_directory(verify_pages=False)
        return int(d["generation"]) if d else 0

    # -- read (agent side / restore) --------------------------------------

    def metadata(self) -> Dict[str, Any]:
        return self._meta.get(default_if_absent=True)

    def get_checkpoint_config(self) -> Optional[CheckpointConfig]:
        meta = self._meta.get(default_if_absent=True)
        return meta.get("config") if meta else None

    def no_checkpoint_state(self) -> bool:
        cfg = self.get_checkpoint_config()
        return cfg is None or cfg.step <= 0

    def _attach(
        self, min_size: int = 0
    ) -> Optional[PersistentSharedMemory]:
        """Attach (cached) to the segment; when the trainer grew and
        recreated it, a cached mapping points at the old unlinked
        inode — re-attach rather than silently slicing a truncated,
        stale snapshot (``min_size`` = bytes the caller needs)."""
        if self._shm is None:
            try:
                self._shm = PersistentSharedMemory(name=self._shm_name)
            except FileNotFoundError:
                return None
        if min_size and self._shm.size < min_size:
            try:
                self._shm.close()
            except BufferError:  # a reader still holds a view
                pass
            self._shm = None
            try:
                self._shm = PersistentSharedMemory(name=self._shm_name)
            except FileNotFoundError:
                return None
            if self._shm.size < min_size:
                logger.error(
                    "shm segment %s is %d bytes but the snapshot "
                    "metadata claims %d; refusing a truncated read",
                    self._shm_name, self._shm.size, min_size,
                )
                return None
        return self._shm

    def load_flat(
        self, detach: bool = True, stats=None,
    ) -> Tuple[Optional[CheckpointConfig], Dict[str, Any], Dict[str, Any]]:
        """Read the shm snapshot as (config, flat {key: array or
        scalar}, {key: TensorMeta}) — shard entries keep their
        ``@shardN`` keys for target-sharded reassembly.

        ``detach=True`` copies every leaf out of the segment through
        the staged restore pipeline (chunked, GIL-released, parallel —
        the serial per-leaf ``arr.copy()`` this replaces paid the
        mapping's page faults single-threaded).  ``detach=False``
        returns live views into shm: valid only until the next save
        overwrites the segment, so callers must finish (or detach /
        ``device_put``-copy) before returning control — the GSPMD
        restore path feeds them straight into batched ``device_put``.
        ``stats`` is a :class:`~.restore.RestoreStats` accumulator.
        """
        import time as _time

        from dlrover_tpu.checkpoint.restore import detach_flat

        t0 = _time.perf_counter()
        meta = self._meta.get(default_if_absent=True)
        if not meta:
            # the meta host may have died with the trainer; a paged
            # segment stands alone (the directory IS the metadata)
            if self._paged_active_slot() is not None:
                return self._load_flat_paged(detach=detach, stats=stats)
            return None, {}, {}
        if meta.get("paged"):
            return self._load_flat_paged(detach=detach, stats=stats)
        config: CheckpointConfig = meta["config"]
        if config.writing:
            logger.warning("shm snapshot is mid-write; refusing to load")
            return None, {}, {}
        shm = self._attach(
            min_size=meta["scalar_offset"] + meta["scalar_nbytes"]
        )
        if shm is None:
            return None, {}, {}
        views = _views_from(meta["tensors"], shm.buf)
        blob = bytes(
            shm.buf[
                meta["scalar_offset"]:
                meta["scalar_offset"] + meta["scalar_nbytes"]
            ]
        )
        if stats is not None:
            stats.read_s += _time.perf_counter() - t0
            if not detach:
                stats.bytes += sum(v.nbytes for v in views.values())
        flat = detach_flat(views, stats=stats) if detach else views
        flat.update(pickle.loads(blob))
        return config, flat, meta["tensors"]

    def load_state_dict(
        self, stats=None,
    ) -> Tuple[Optional[CheckpointConfig], Any]:
        """Read the shm snapshot back into a nested dict of private
        numpy arrays (caller device_puts with its shardings).  Shard
        entries of global arrays are assembled to full host arrays
        when this process's shards cover them (always single-host)."""
        import time as _time

        config, flat, metas = self.load_flat(stats=stats)
        if config is None:
            return None, {}
        t0 = _time.perf_counter()
        flat = _assemble_flat(flat, metas)
        if stats is not None:
            stats.assemble_s += _time.perf_counter() - t0
        return config, _unflatten_to_nested(flat)

    def read_raw(self) -> Tuple[Optional[CheckpointConfig], Any, Dict]:
        """Raw snapshot + meta for the agent's persist path (no pytree
        reconstruction).  Returns a PRIVATE ``bytes`` copy: the agent
        takes it under the shard lock (one memcpy) and releases the
        lock before any storage IO, so the trainer's next snapshot is
        never blocked behind a disk/remote write (the former zero-copy
        stream-under-lock mode traded exactly that stall for one saved
        memcpy — the wrong trade; see saver._save_shard)."""
        meta = self._meta.get(default_if_absent=True)
        if not meta:
            if self._paged_active_slot() is not None:
                return self._read_raw_paged()
            return None, b"", {}
        if meta.get("paged"):
            return self._read_raw_paged()
        config: CheckpointConfig = meta["config"]
        total = meta["scalar_offset"] + meta["scalar_nbytes"]
        shm = self._attach(min_size=total)
        if shm is None or config.writing:
            return None, b"", {}
        return config, bytes(shm.buf[:total]), meta

    def prefault(
        self, workers: Optional[int] = None,
        chunk_bytes: int = 64 * 2**20,
    ) -> int:
        """Touch every page of the snapshot so a later read runs warm.

        Page-table population is PER PROCESS: the agent's prefetch
        warms the agent, not the trainer — so the respawned trainer
        runs this itself (engine construction kicks it on a daemon
        thread) while its model build / jit trace proceeds.  Strided
        read-only touches in parallel ~chunk_bytes pieces: numpy
        releases the GIL for the reductions, so the faults overlap
        across the (bounded) pool.  Returns bytes touched (0 when no
        snapshot exists)."""
        meta = self._meta.get(default_if_absent=True)
        if not meta:
            return 0
        total = meta["scalar_offset"] + meta["scalar_nbytes"]
        shm = self._attach(min_size=total)
        if shm is None or total <= 0:
            return 0
        workers = workers if workers is not None else prefault_workers()
        flat = np.frombuffer(shm.buf, dtype=np.uint8, count=total)

        def touch(lo: int, hi: int):
            flat[lo:hi:4096].sum()

        spans = [
            (lo, min(lo + chunk_bytes, total))
            for lo in range(0, total, max(1, chunk_bytes))
        ]
        if workers <= 1 or len(spans) <= 1:
            for lo, hi in spans:
                touch(lo, hi)
        else:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="shm-prefault"
            ) as pool:
                list(pool.map(lambda s: touch(*s), spans))
        return total

    def close(self):
        if self._shm is not None:
            self._shm.close()
            self._shm = None
        self._meta.close()

    def unlink(self):
        if self._attach() is not None:
            self._shm.unlink()
            self._shm = None


def prefault_workers() -> int:
    """Thread budget for page-in prefetch/prefault work.  PINNED low
    by default: the touches deliberately overlap the trainer's
    interpreter/jax import (or its model build), and an unbounded pool
    would starve exactly the work it is hiding latency from.
    ``DLROVER_PREFETCH_WORKERS`` overrides."""
    val = os.getenv("DLROVER_PREFETCH_WORKERS", "").strip()
    if val:
        try:
            return max(1, int(val))
        except ValueError:
            pass
    return min(4, max(1, (os.cpu_count() or 2) // 2))


def _views_from(metas: Dict[str, TensorMeta], buf) -> Dict[str, np.ndarray]:
    """{key: np.frombuffer view} over a shm segment or raw/mmap blob —
    free to build; paging/copy cost is paid by whichever pipeline
    stage consumes the view."""
    views: Dict[str, np.ndarray] = {}
    for key, m in metas.items():
        views[key] = np.frombuffer(
            buf, dtype=np.dtype(m.dtype),
            count=int(np.prod(m.shape, dtype=np.int64)) if m.shape else 1,
            offset=m.offset,
        ).reshape(m.shape)
    return views


def flat_from_raw(
    meta: Dict, raw, detach: bool = True, stats=None,
) -> Tuple[Dict, Dict]:
    """(flat {key: array/scalar}, {key: TensorMeta}) from raw shm
    bytes — or an mmap view from ``storage.read_view`` — shard keys
    preserved.  ``detach=False`` returns views into ``raw`` (the
    caller keeps ``raw`` alive until it is done)."""
    from dlrover_tpu.checkpoint.restore import detach_flat

    views = _views_from(meta["tensors"], raw)
    if stats is not None and not detach:
        stats.bytes += sum(v.nbytes for v in views.values())
    flat = detach_flat(views, stats=stats) if detach else views
    blob = raw[
        meta["scalar_offset"]:meta["scalar_offset"] + meta["scalar_nbytes"]
    ]
    flat.update(pickle.loads(blob))
    return flat, meta["tensors"]


def _assemble_flat(flat: Dict[str, Any], metas: Dict[str, Any]):
    """Assemble ``@shardN`` entries into full host arrays (raises if
    the visible shards do not cover a leaf — topology changed across
    hosts; use the target-sharded restore or the orbax tier)."""
    from dlrover_tpu.checkpoint.sharded import (
        SHARD_SEP,
        assemble_shard,
        group_shard_entries,
    )

    grouped, plain = group_shard_entries(flat, metas)
    for base, entries in grouped.items():
        some_key = f"{base}{SHARD_SEP}0"
        m = metas.get(some_key)
        gshape = tuple(m.global_shape)
        full = assemble_shard(
            tuple((0, d) for d in gshape),
            np.dtype(m.dtype),
            entries,
        )
        if full is None:
            raise ValueError(
                f"shards of '{base}' do not cover its global shape "
                f"{gshape}: restore with a target state "
                f"(load_sharded) or from the orbax tier"
            )
        plain[base] = full
    return plain


def state_dict_from_raw(meta: Dict, raw, stats=None):
    """Rebuild the nested dict from raw shm bytes (storage load path);
    detach copies run through the staged restore pipeline."""
    import time as _time

    flat, metas = flat_from_raw(meta, raw, stats=stats)
    t0 = _time.perf_counter()
    flat = _assemble_flat(flat, metas)
    if stats is not None:
        stats.assemble_s += _time.perf_counter() - t0
    return _unflatten_to_nested(flat)
