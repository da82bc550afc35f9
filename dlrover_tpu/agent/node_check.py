"""Node health-check payload: per-chip compute benchmark + fabric
probe.

Reference: ``dlrover/trainer/torch/node_check/{utils,nvidia_gpu}.py``
(matmul + 2^24-float allreduce per round, ``utils.py:57-105``) driven
by ``NodeCheckElasticAgent`` (``elastic_agent/torch/training.py:864``).
On TPU the per-chip probe is a jitted bf16 matmul on every local
device (exercises MXU + HBM); the fabric probe is a timed
psum + ring-ppermute collective over every visible device — riding
ICI within a slice, DCN across slices.  A KV-store barrier against
the master synchronizes rounds and catches dead peers (its wait time
is excluded from the reported number so a slow peer cannot mask
itself).  Elapsed time feeds the master's
NetworkCheckRendezvousManager, which isolates fault nodes and
stragglers (>2x median, rdzv_manager.py:550) over two pairwise
regrouping rounds.

Fault injection: ``MOCK_ERR_RANK`` makes the matching node rank raise
(mirrors ``node_check/utils.py:49 mock_error()``);
``MOCK_STRAGGLER_RANK``/``MOCK_STRAGGLER_DELAY`` make a rank slow —
the chaos experiment of ``docs/tech_report/fault_tolerance_exps.md``.
"""

import json
import os
import subprocess
import sys
import time
from typing import Optional

import numpy as np

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.common import env_utils
from dlrover_tpu.common.constants import (
    NetworkCheckConstant,
    NodeEnv,
    NodeType,
)
from dlrover_tpu.common.log import default_logger as logger
from dlrover_tpu.telemetry import tracing as trace
from dlrover_tpu.telemetry.events import EVENT_SOURCE_ENV, emit_event
from dlrover_tpu.telemetry.metrics import get_registry

_REG = get_registry()
_CHECK_SECONDS = _REG.histogram(
    "dlrover_node_check_seconds",
    "Per-node health-check work time (barrier waits excluded)",
)
_BARRIER_SECONDS = _REG.histogram(
    "dlrover_node_check_barrier_seconds",
    "Node-check barrier wait (dead/slow-peer indicator)",
)


def mock_error():
    """Raise if this node rank is marked faulty (test fault injection)."""
    err_rank = os.getenv(NodeEnv.MOCK_ERR_RANK, "")
    if err_rank and int(err_rank) == int(os.getenv(NodeEnv.NODE_RANK, "0")):
        raise RuntimeError(f"mock error on rank {err_rank}")


def mock_straggle():
    """Sleep if this node rank is marked slow (straggler injection)."""
    slow_rank = os.getenv("MOCK_STRAGGLER_RANK", "")
    if slow_rank and int(slow_rank) == int(
        os.getenv(NodeEnv.NODE_RANK, "0")
    ):
        delay = float(os.getenv("MOCK_STRAGGLER_DELAY", "3.0"))
        logger.info("injected straggle: sleeping %.1fs", delay)
        time.sleep(delay)


def bm_chip_matmul(size: int = 1024, rounds: int = 8) -> float:
    """Time a jitted bf16 matmul chain on every local device.

    A straggling or faulty chip shows up as a slow or failing device;
    bf16 NxN matmuls land on the MXU so this measures the chip, not
    Python.
    """
    import jax
    import jax.numpy as jnp

    elapsed = 0.0
    for dev in jax.local_devices():
        x = jax.device_put(
            jnp.ones((size, size), dtype=jnp.bfloat16), device=dev
        )

        @jax.jit
        def chain(a):
            for _ in range(4):
                a = a @ a / size
            return a

        chain(x).block_until_ready()  # compile outside the timer
        start = time.perf_counter()
        for _ in range(rounds):
            x = chain(x)
        x.block_until_ready()
        elapsed += time.perf_counter() - start
    return elapsed


def bm_collective_probe(
    payload_floats: int = 1 << 22, rounds: int = 2,
) -> Optional[float]:
    """Timed psum + ring ppermute over every visible device.

    The honest fabric probe (reference: ``bm_allreduce``/
    ``bm_allgather``, node_check/utils.py:57-105): the payload crosses
    ICI (intra-slice) / DCN (inter-slice) links, so a degraded link or
    chip inflates this node's elapsed time.  Returns None when fewer
    than two devices are visible (no local fabric to probe; the
    master-mediated barrier in ``run_node_check`` still provides
    cross-node liveness).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    if n < 2:
        return None
    mesh = Mesh(np.array(devices), ("probe",))
    per = max(128, payload_floats // n)
    x = jax.device_put(
        jnp.ones((n, per), jnp.float32),
        NamedSharding(mesh, P("probe")),
    )
    perm = [(i, (i + 1) % n) for i in range(n)]

    def local(block):
        s = jax.lax.psum(block, "probe")       # allreduce
        return jax.lax.ppermute(s, "probe", perm)  # neighbor links

    fn = jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=P("probe"),
            out_specs=P("probe"),
        )
    )
    out = fn(x).block_until_ready()  # compile outside the timer
    start = time.perf_counter()
    for _ in range(rounds):
        out = fn(out / n)
    out.block_until_ready()
    elapsed = time.perf_counter() - start
    logger.info(
        "collective probe: %d devices, %d floats, %d rounds in %.3fs",
        n, per * n, rounds, elapsed,
    )
    return elapsed


def comm_perf_check(
    payload_floats: int = 1 << 24, rounds: int = 4,
) -> Optional[dict]:
    """Fabric bandwidth report: algobw/busbw of a timed psum over the
    visible devices (reference: ``comm_perf_check`` +
    ``bm_allreduce``'s busbw accounting, node_check/utils.py:57-105 —
    busbw = algbw * 2(n-1)/n for a ring allreduce)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    devices = jax.devices()
    n = len(devices)
    if n < 2:
        return None
    mesh = Mesh(np.array(devices), ("probe",))
    per = max(128, payload_floats // n)
    x = jax.device_put(
        jnp.ones((n, per), jnp.float32),
        NamedSharding(mesh, P("probe")),
    )

    def local(block):
        return jax.lax.psum(block, "probe") / n

    fn = jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=P("probe"),
            out_specs=P("probe"),
        )
    )
    out = fn(x).block_until_ready()
    start = time.perf_counter()
    for _ in range(rounds):
        out = fn(out)
    out.block_until_ready()
    elapsed = (time.perf_counter() - start) / rounds
    # per-rank message size is what bandwidth math divides by (each
    # rank reduces its own `per`-float block), matching the reference
    # busbw convention (utils.py bm_allreduce)
    message_bytes = per * 4
    algbw = message_bytes / max(elapsed, 1e-9)
    busbw = algbw * 2 * (n - 1) / n
    report = {
        "devices": n,
        "payload_bytes": message_bytes,
        "allreduce_s": round(elapsed, 6),
        "algbw_gbps": round(algbw / 1e9, 3),
        "busbw_gbps": round(busbw / 1e9, 3),
    }
    logger.info("comm perf: %s", report)
    return report


def bm_sync_barrier(
    client: MasterClient, round_id: int, world_size: int,
    timeout: float = 300.0,
) -> float:
    """All-nodes barrier through the master KV store.

    A liveness/sync gate, not a performance number: it synchronizes
    check rounds across nodes and raises when a peer never arrives
    (dead node -> this node reports abnormal).  Its wait time is
    deliberately NOT part of the reported elapsed — a slow peer would
    inflate every healthy node's number and mask the actual straggler.
    """
    key = f"node_check_barrier_{round_id}"
    start = time.perf_counter()
    client.kv_store_add(key, 1)
    deadline = time.time() + timeout
    while time.time() < deadline:
        if client.kv_store_add(key, 0) >= world_size:
            wait = time.perf_counter() - start
            _BARRIER_SECONDS.observe(wait)
            return wait
        time.sleep(0.1)
    raise TimeoutError(f"node-check barrier round {round_id} timed out")


def run_node_check(
    client: Optional[MasterClient] = None,
    matmul_size: int = 1024,
    world_size: int = 1,
    round_id: int = 0,
) -> float:
    """Full check: fault injection hook, chip matmul, sync probe.

    Returns elapsed seconds; raises on chip failure so the caller
    reports abnormal status.
    """
    client = client or MasterClient.singleton()
    node_rank = int(os.getenv(NodeEnv.NODE_RANK, "0"))
    with trace.span(
        "node_check", round=round_id, node_rank=node_rank
    ) as check_span:
        return _run_node_check(
            client, matmul_size, world_size, round_id, check_span
        )


def _run_node_check(
    client, matmul_size, world_size, round_id, check_span
) -> float:
    mock_error()
    if world_size > 1:
        # ENTRY barrier: align the start of the timed work phase so a
        # peer that arrives late (slow boot, slow previous round)
        # cannot leak into other nodes' work numbers
        wait = bm_sync_barrier(
            client, f"{round_id}_entry", world_size
        )
        logger.info("entry barrier wait %.3fs (not counted)", wait)
    # per-node WORK timer (the reference reports per-node work time,
    # node_check/utils.py:25-46): injected or real chip slowness lands
    # in THIS node's number only
    work_start = time.perf_counter()
    mock_straggle()
    bm_chip_matmul(size=matmul_size)
    elapsed = time.perf_counter() - work_start
    # fabric probe over every visible device — with a live
    # jax.distributed runtime this crosses hosts (ICI/DCN).  Timed
    # SEPARATELY from the work phase: a global collective completes at
    # the pace of its slowest participant, so folding it into elapsed
    # would inflate every healthy node's number and mask attribution.
    bm_collective_probe()
    if world_size > 1:
        # EXIT barrier: synchronizes the round across nodes and fails
        # when a peer is dead
        wait = bm_sync_barrier(client, round_id, world_size)
        logger.info("exit barrier wait %.3fs (not counted)", wait)
    _CHECK_SECONDS.observe(elapsed)
    check_span.set_attribute("elapsed_s", round(elapsed, 4))
    emit_event(
        "node_check", round=round_id,
        elapsed_s=round(elapsed, 4), world_size=world_size,
    )
    logger.info("node check elapsed %.3fs", elapsed)
    return elapsed


def run_node_check_in_child(
    master_addr: str,
    node_id: int,
    node_rank: int,
    world_size: int = 1,
    round_id: int = 0,
    matmul_size: int = 1024,
    timeout: float = NetworkCheckConstant.CHECK_TIMEOUT,
) -> float:
    """:func:`run_node_check` in a short-lived child process.

    A chip belongs to one process at a time, and the agent outlives
    every worker it supervises: if the AGENT ran the matmul it would
    hold the chip before the first worker is spawned, and every worker
    would then fail to open it.  So the check's backend lives in a
    child that has exited — and been reaped — before this returns.
    Raises when the child fails or times out (the caller reports the
    node abnormal)."""
    env = env_utils.with_package_on_pythonpath(dict(os.environ))
    env[NodeEnv.NODE_RANK] = str(node_rank)
    env.setdefault(EVENT_SOURCE_ENV, "agent")
    cmd = [
        sys.executable, "-m", "dlrover_tpu.agent.node_check",
        "--master-addr", master_addr,
        "--node-id", str(node_id),
        "--world-size", str(world_size),
        "--round-id", str(round_id),
        "--matmul-size", str(matmul_size),
    ]
    # run() kills and reaps the child on timeout before raising
    done = subprocess.run(  # noqa: S603
        cmd, env=env, stdout=subprocess.PIPE, text=True,
        timeout=timeout,
    )
    if done.returncode != 0:
        raise RuntimeError(
            f"node check child exited with code {done.returncode}"
        )
    elapsed = float(
        json.loads(done.stdout.strip().splitlines()[-1])["elapsed_s"]
    )
    _CHECK_SECONDS.observe(elapsed)
    return elapsed


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(prog="dlrover_tpu.agent.node_check")
    ap.add_argument("--master-addr", required=True)
    ap.add_argument("--node-id", type=int, required=True)
    ap.add_argument("--world-size", type=int, default=1)
    ap.add_argument("--round-id", type=int, default=0)
    ap.add_argument("--matmul-size", type=int, default=1024)
    args = ap.parse_args(argv)
    client = MasterClient(
        args.master_addr, args.node_id, NodeType.WORKER
    )
    try:
        elapsed = run_node_check(
            client=client, matmul_size=args.matmul_size,
            world_size=args.world_size, round_id=args.round_id,
        )
    finally:
        client.close()
    print(json.dumps({"elapsed_s": elapsed}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
