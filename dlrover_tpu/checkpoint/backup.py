"""Peer-host checkpoint shard backup.

Reference: ``flash_checkpoint/ckpt_backup.py`` (peer-node backup and
restore of checkpoint shards via torch collectives): each host sends
its shm checkpoint shard to a partner host, so when a host is lost and
replaced, the replacement recovers the shard from the partner instead
of storage.  TPU version: the shard bytes ride the ICI/DCN fabric as a
uint8 ``ppermute`` over the ``data`` axis inside ``shard_map`` — one
collective, no host networking code.
"""

import pickle
from typing import Optional, Tuple

import numpy as np

from dlrover_tpu.common.log import default_logger as logger
from jax import shard_map


def _to_u8(payload: bytes, size: int) -> np.ndarray:
    buf = np.zeros(size, dtype=np.uint8)
    arr = np.frombuffer(payload, dtype=np.uint8)
    buf[: arr.size] = arr
    return buf


def exchange_with_peer(
    payload: bytes,
    mesh,
    axis: str = "data",
    max_bytes: Optional[int] = None,
    shift: int = 1,
) -> Tuple[bytes, int]:
    """Every rank sends ``payload`` to rank+shift (ring) and receives
    rank-shift's payload.  Returns (peer_payload, peer_len).

    All ranks must call this collectively with the same ``max_bytes``
    (defaults to a power-of-two bound of the local payload; callers
    should agree on it out of band, e.g. via the master KV store).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.shape[axis]
    if n == 1:
        return payload, len(payload)
    if max_bytes is None:
        if jax.process_count() > 1:
            # a default derived from the *local* payload length lets
            # processes disagree on the collective's buffer shape and
            # deadlock/crash the ppermute — callers must agree out of
            # band (e.g. via the master KV store)
            raise ValueError(
                "exchange_with_peer requires an explicitly agreed "
                "max_bytes in multi-host runs"
            )
        max_bytes = 1 << (len(payload)).bit_length()
    if len(payload) > max_bytes:
        # fail fast on every rank's next call instead of dying with an
        # opaque broadcast error after peers entered the collective
        raise ValueError(
            f"payload ({len(payload)} bytes) exceeds the agreed "
            f"max_bytes ({max_bytes}); raise max_bytes collectively"
        )
    size = max_bytes
    # [n, size+8] buffer: 8-byte length header + padded payload
    header = np.frombuffer(
        np.int64(len(payload)).tobytes(), dtype=np.uint8
    )
    local = np.concatenate([header, _to_u8(payload, size)])
    stacked = np.zeros((n, size + 8), dtype=np.uint8)
    for i in range(n):
        stacked[i] = local  # every row holds this process's payload

    perm = [(i, (i + shift) % n) for i in range(n)]

    def shard_fn(x):
        return jax.lax.ppermute(x, axis, perm)

    sharded = jax.device_put(
        jnp.asarray(stacked),
        NamedSharding(mesh, P(axis)),
    )
    received = shard_map(
        shard_fn, mesh=mesh, in_specs=P(axis), out_specs=P(axis),
        check_vma=False,
    )(sharded)
    # extract only this process's addressable rows — np.asarray on the
    # global array would raise multi-host where most rows live on
    # other hosts' devices
    local_rows = []
    for sh in received.addressable_shards:
        data = np.asarray(sh.data)
        start = sh.index[0].start or 0
        for j in range(data.shape[0]):
            local_rows.append((start + j, data[j]))
    local_rows.sort(key=lambda t: t[0])
    # multi-host: the single addressable row is what *this* process
    # received; single-host virtual mesh: every row is addressable and
    # the first is rank 0's view (test mode)
    row = local_rows[0][1]
    length = int(np.frombuffer(row[:8].tobytes(), dtype=np.int64)[0])
    peer = bytes(row[8 : 8 + length].tobytes())
    return peer, len(peer)


class BackupManager:
    """Keeps the partner's shard alongside ours (reference:
    ckpt_backup BackupManger semantics)."""

    def __init__(self, mesh, axis: str = "data"):
        self._mesh = mesh
        self._axis = axis
        self._peer_shard: Optional[bytes] = None
        self._own_meta: Optional[dict] = None

    def backup(self, state_dict, step: int, max_bytes: int):
        payload = pickle.dumps({"step": step, "state": state_dict})
        peer, _ = exchange_with_peer(
            payload, self._mesh, self._axis, max_bytes=max_bytes
        )
        self._peer_shard = peer
        logger.info(
            "backed up step %s shard with peer (%s bytes held)",
            step, len(peer),
        )

    def peer_state(self) -> Optional[Tuple[int, dict]]:
        if self._peer_shard is None:
            return None
        data = pickle.loads(self._peer_shard)
        return data["step"], data["state"]
