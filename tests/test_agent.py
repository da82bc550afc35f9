"""Agent-layer tests against a real in-process master (the reference's
local-master fixture pattern): master client RPCs, sharding client,
rendezvous handler, worker supervision and restart, node check."""

import os
import sys
import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.node_check import bm_chip_matmul, mock_error
from dlrover_tpu.agent.sharding_client import (
    IndexShardingClient,
    ShardingClient,
)
from dlrover_tpu.agent.training import (
    ElasticTrainingAgent,
    MasterRendezvousHandler,
    WorkerSpec,
)
from dlrover_tpu.common import env_utils
from dlrover_tpu.common.constants import NodeEnv, RendezvousName
from dlrover_tpu.master.master import JobMaster


@pytest.fixture()
def master():
    m = JobMaster(port=0, node_num=1, job_name="agent-test")
    m.prepare()
    yield m
    m.stop()


@pytest.fixture()
def client(master):
    c = MasterClient(f"127.0.0.1:{master.port}", node_id=0,
                     node_type="worker")
    yield c
    c.close()


def test_kv_store_roundtrip(client):
    client.kv_store_set("k", b"v1")
    assert client.kv_store_get("k") == b"v1"
    assert client.kv_store_add("ctr", 2) == 2
    assert client.kv_store_add("ctr", 3) == 5


def test_rendezvous_handler_single_node(client):
    handler = MasterRendezvousHandler(
        RendezvousName.ELASTIC_TRAINING, node_rank=0, local_world_size=2,
        client=client, timeout=30,
    )
    out = handler.next_rendezvous()
    assert out.world == {0: 2}
    assert out.world_size == 2
    assert out.base_rank(0) == 0
    assert out.coordinator


def test_heartbeat_and_metrics(client):
    assert client.report_heartbeat() == ""
    client.report_global_step(10)
    client.report_resource_stats(12.0, 1024.0)
    client.report_model_info(125_000_000, "bfloat16")


def test_sharding_client_consumes_dataset(client):
    sc = ShardingClient(
        dataset_name="ds1", batch_size=4, num_epochs=1, dataset_size=32,
        master_client=client, num_minibatches_per_shard=2,
    )
    seen = 0
    while True:
        task = sc.fetch_task()
        if task is None:
            break
        seen += task.shard_size
        sc.report_task_done(task.task_id)
    assert seen == 32


def test_index_sharding_client_stream(client):
    isc = IndexShardingClient(
        dataset_name="ds2", batch_size=4, num_epochs=1, dataset_size=16,
        master_client=client,
    )
    indices = []
    while True:
        idx = isc.fetch_sample_index(timeout=30)
        if idx is None:
            break
        indices.append(idx)
        if len(indices) % 4 == 0:
            isc.report_batch_done()
    assert sorted(indices) == list(range(16))
    isc.stop()


def test_dataset_checkpoint_roundtrip(client):
    sc = ShardingClient(
        dataset_name="ds3", batch_size=2, num_epochs=1, dataset_size=8,
        master_client=client,
    )
    sc.fetch_task()
    content = sc.get_checkpoint()
    assert content
    sc.restore_checkpoint(content)


def test_mock_error_fault_injection(monkeypatch):
    monkeypatch.setenv(NodeEnv.MOCK_ERR_RANK, "0")
    monkeypatch.setenv(NodeEnv.NODE_RANK, "0")
    with pytest.raises(RuntimeError):
        mock_error()
    monkeypatch.setenv(NodeEnv.NODE_RANK, "1")
    mock_error()  # other ranks pass


def test_chip_matmul_benchmark():
    elapsed = bm_chip_matmul(size=64, rounds=2)
    assert elapsed > 0


def _worker_script(tmp_path, body: str) -> str:
    path = os.path.join(tmp_path, "worker.py")
    with open(path, "w") as f:
        f.write(body)
    return path


def test_agent_runs_worker_to_success(master, client, tmp_path):
    script = _worker_script(
        str(tmp_path),
        "import os\n"
        "assert os.environ['DLROVER_COORDINATOR_ADDR']\n"
        "assert os.environ['DLROVER_RANK'] == '0'\n"
        "assert os.environ['DLROVER_WORLD_SIZE'] == '1'\n",
    )
    spec = WorkerSpec(
        entrypoint=[sys.executable, script],
        nproc_per_node=1, max_restarts=1, monitor_interval=0.2,
    )
    agent = ElasticTrainingAgent(
        spec, client=client, node_rank=0, start_monitors=False
    )
    assert agent.run() == 0


def test_agent_restarts_then_fails(master, client, tmp_path):
    script = _worker_script(str(tmp_path), "import sys; sys.exit(3)\n")
    spec = WorkerSpec(
        entrypoint=[sys.executable, script],
        nproc_per_node=1, max_restarts=1, monitor_interval=0.2,
    )
    hook_calls = []
    agent = ElasticTrainingAgent(
        spec, client=client, node_rank=0, start_monitors=False,
        save_ckpt_hook=lambda: hook_calls.append(1),
    )
    assert agent.run() == 1
    # breakpoint-save hook fired on restart and on final failure
    assert len(hook_calls) >= 1


def test_agent_worker_succeeds_after_one_restart(master, client, tmp_path):
    flag = os.path.join(str(tmp_path), "flag")
    script = _worker_script(
        str(tmp_path),
        "import os, sys\n"
        f"flag = {flag!r}\n"
        "if not os.path.exists(flag):\n"
        "    open(flag, 'w').close()\n"
        "    sys.exit(5)\n",
    )
    spec = WorkerSpec(
        entrypoint=[sys.executable, script],
        nproc_per_node=1, max_restarts=2, monitor_interval=0.2,
    )
    agent = ElasticTrainingAgent(
        spec, client=client, node_rank=0, start_monitors=False
    )
    assert agent.run() == 0


def test_starter_builds_tpurun_argv():
    """Platform starter: NodeEnv contract -> tpurun argv (reference:
    platform/starter.py:94)."""
    from dlrover_tpu.common.constants import NodeEnv
    from dlrover_tpu.trainer.starter import build_run_argv

    env = {
        NodeEnv.NODE_NUM: "4",
        NodeEnv.LOCAL_WORLD_SIZE: "4",
        NodeEnv.NODE_RANK: "2",
        "DLROVER_MIN_NODES": "2",
        "DLROVER_MAX_NODES": "4",
        "DLROVER_NETWORK_CHECK": "1",
    }
    argv = build_run_argv(["train.py", "--lr", "0.1"], env=env)
    assert argv[:2] == ["--nnodes", "2:4"]
    assert "--nproc_per_node" in argv and "4" in argv
    assert "--node_rank" in argv and "2" in argv
    assert "--network-check" in argv
    assert argv[-3:] == ["train.py", "--lr", "0.1"]


def test_tpurun_auto_config():
    from dlrover_tpu.run import apply_auto_config, parse_args

    args = parse_args(["--auto-config", "t.py"])
    assert apply_auto_config(args).nproc_per_node == 1
    args = parse_args(["--nproc_per_node", "0", "t.py"])
    assert apply_auto_config(args).nproc_per_node == 1
    args = parse_args(["--nproc_per_node", "2", "t.py"])
    assert apply_auto_config(args).nproc_per_node == 2
    # negative values are treated as auto, never zero workers
    args = parse_args(["--nproc_per_node", "-1", "t.py"])
    assert apply_auto_config(args).nproc_per_node == 1


# -- preemption monitor -------------------------------------------------


class _FakeMetadata:
    """Local stand-in for the GCE metadata server: serves FALSE until
    flipped, then TRUE (instance/preempted semantics)."""

    def __init__(self):
        import http.server
        import threading

        fake = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802
                body = b"TRUE" if fake.preempted else b"FALSE"
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # silence
                pass

        self.preempted = False
        self._srv = http.server.ThreadingHTTPServer(
            ("127.0.0.1", 0), Handler
        )
        self.url = f"http://127.0.0.1:{self._srv.server_port}/preempted"
        threading.Thread(
            target=self._srv.serve_forever, daemon=True
        ).start()

    def close(self):
        self._srv.shutdown()
        self._srv.server_close()


def test_preemption_monitor_fires_once_on_notice():
    from dlrover_tpu.agent.preemption import PreemptionMonitor

    meta = _FakeMetadata()
    fired = []
    mon = PreemptionMonitor(
        lambda: fired.append(time.time()), metadata_url=meta.url,
        poll_interval=0.05,
    )
    try:
        mon.start()
        time.sleep(0.3)
        assert not fired  # FALSE -> no callback
        meta.preempted = True
        deadline = time.time() + 5
        while not fired and time.time() < deadline:
            time.sleep(0.05)
        assert len(fired) == 1
        time.sleep(0.2)
        assert len(fired) == 1  # fires once, thread exits
    finally:
        mon.stop()
        meta.close()


def test_agent_preemption_notice_saves_ckpt_and_reports(
    master, client, monkeypatch
):
    """Advance preemption notice -> breakpoint-checkpoint hook runs
    and the master sees the node transition with exit_reason
    'preempted' (instead of waiting for a heartbeat timeout)."""
    from dlrover_tpu.agent.preemption import ENV_METADATA_URL

    meta = _FakeMetadata()
    monkeypatch.setenv(ENV_METADATA_URL, meta.url)
    saved = []
    spec = WorkerSpec(
        entrypoint=[sys.executable, "-c", "import time; time.sleep(30)"],
        nproc_per_node=1, max_restarts=0, monitor_interval=0.2,
    )
    agent = ElasticTrainingAgent(
        spec, client=client, node_rank=0, start_monitors=True,
        save_ckpt_hook=lambda: saved.append(True),
    )
    mon = agent._monitors[-1]
    from dlrover_tpu.agent.preemption import PreemptionMonitor

    assert isinstance(mon, PreemptionMonitor)
    mon._poll_interval = 0.05
    try:
        for m in agent._monitors:
            m.start()
        meta.preempted = True
        deadline = time.time() + 5
        while not saved and time.time() < deadline:
            time.sleep(0.05)
        assert saved, "breakpoint checkpoint hook did not run"
        # master saw the advance notice
        deadline = time.time() + 3
        node = None
        while time.time() < deadline:
            n = master.job_manager.get_node(0)
            if n is not None and n.exit_reason == "preempted":
                node = n
                break
            time.sleep(0.05)
        assert node is not None, "master did not record preemption"
    finally:
        for m in agent._monitors:
            m.stop()
        agent.stop()
        meta.close()


_AGENT_NO_BACKEND = r"""
import json, sys
from dlrover_tpu.agent.diagnosis import DiagnosisMonitor
from dlrover_tpu.common.env_utils import initialized_jax_backends
from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training import ElasticTrainingAgent, WorkerSpec
from dlrover_tpu.master.master import JobMaster

master = JobMaster(port=0, node_num=1, job_name="agent-no-backend")
master.prepare()
client = MasterClient(
    f"127.0.0.1:{master.port}", node_id=0, node_type="worker"
)
try:
    agent = ElasticTrainingAgent(
        WorkerSpec(entrypoint=[sys.executable, "-c", "pass"],
                   network_check=True),
        client=client, node_rank=0, start_monitors=False,
    )
    healthy = agent.node_health_check()   # a --network-check round
    monitor = DiagnosisMonitor(client=client)  # default collectors
    monitor.report_once()
    kinds = sorted(c.data_type for c in monitor._collectors)
finally:
    client.close()
    master.stop()
print(json.dumps({
    "healthy": healthy, "collectors": kinds,
    "backends": initialized_jax_backends(),
}))
"""


def test_agent_process_never_initializes_a_jax_backend(tmp_path):
    """One process per chip: an agent that ran its diagnosis
    collectors and a --network-check round has NO jax backend of its
    own (the node check ran in a child that exited; chip metrics come
    from the trainer's metrics file).  In a process of its own: this
    one has had a CPU backend since conftest."""
    import json
    import subprocess

    metrics = tmp_path / "metrics.json"
    metrics.write_text(json.dumps({
        "global_step": 3, "timestamp": time.time(),
        "chip_metrics": "TPU_0: in_use=1 peak=2 limit=3",
    }))
    env = dict(
        os.environ,
        PYTHONPATH=os.getcwd(),
        DLROVER_METRICS_FILE=str(metrics),
        DLROVER_EVENT_LOG=str(tmp_path / "events.jsonl"),
    )
    done = subprocess.run(
        [sys.executable, "-c", _AGENT_NO_BACKEND], env=env,
        capture_output=True, text=True, timeout=240,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    report = json.loads(done.stdout.strip().splitlines()[-1])
    assert report["healthy"] is True
    assert "chip_metrics" in report["collectors"]
    assert report["backends"] == []
    # the check really ran, in ANOTHER process
    from dlrover_tpu.telemetry.events import read_events

    checks = [
        e for e in read_events(str(tmp_path / "events.jsonl"))
        if e["type"] == "node_check"
    ]
    assert checks


def test_chip_metrics_collector_reads_the_trainers_file(tmp_path):
    import json

    from dlrover_tpu.agent.diagnosis import ChipMetricsCollector

    path = tmp_path / "metrics.json"
    collector = ChipMetricsCollector(str(path))
    assert collector.collect() == ""  # no trainer yet
    path.write_text(json.dumps({
        "global_step": 1,
        "chip_metrics": "TPU_0: in_use=1 peak=2 limit=3",
    }))
    assert collector.collect() == "TPU_0: in_use=1 peak=2 limit=3"


def test_reap_process_group_kills_orphaned_descendants(tmp_path):
    """The respawn gate: after a worker died, whatever it left behind
    in its process group is killed and waited for before the
    replacement may open the chip."""
    import signal
    import subprocess

    from dlrover_tpu.agent.training import reap_process_group

    pid_file = tmp_path / "child.pid"
    # a worker that leaves a grandchild behind and dies
    worker = subprocess.Popen(
        [sys.executable, "-c",
         "import subprocess, sys\n"
         "c = subprocess.Popen([sys.executable, '-c', "
         "'import time; time.sleep(120)'])\n"
         f"open({str(pid_file)!r}, 'w').write(str(c.pid))\n"
         "import time; time.sleep(120)\n"],
        process_group=0,
    )
    deadline = time.time() + 30
    while time.time() < deadline and not pid_file.exists():
        time.sleep(0.05)
    orphan = int(pid_file.read_text())
    worker.send_signal(signal.SIGKILL)
    worker.wait(timeout=10)
    assert orphan in env_utils.live_pids(pgid=worker.pid)
    reap_process_group(worker.pid)
    assert env_utils.live_pids(pgid=worker.pid) == []
    reap_process_group(worker.pid)  # nobody left: a no-op


_WORKER_WITH_A_CHILD = """
import os, subprocess, sys, time
child = subprocess.Popen(
    [sys.executable, "-c", "import time; time.sleep(300)"]
)
with open(sys.argv[1] + ".tmp", "w") as f:
    f.write(f"{os.getpid()} {child.pid}")
os.replace(sys.argv[1] + ".tmp", sys.argv[1])
time.sleep(300)
"""


@pytest.mark.parametrize("signame", ["SIGTERM", "SIGHUP"])
def test_a_signalled_tpurun_leaves_no_worker_behind(tmp_path, signame):
    """Workers lead process groups of their own, so a signal aimed at
    tpurun (or at its group) does not reach them: tpurun itself must
    stop them — and their descendants, and the local master — on its
    way out, or the next job cannot open the chip."""
    import signal
    import subprocess

    script = tmp_path / "worker.py"
    script.write_text(_WORKER_WITH_A_CHILD)
    pids = tmp_path / "pids"
    env = dict(
        os.environ,
        PYTHONPATH=os.getcwd(),
        DLROVER_SHARED_DIR=str(tmp_path / "sock"),
        DLROVER_JOB_NAME=f"sig{os.getpid()}",
    )
    tpurun = subprocess.Popen(
        [sys.executable, "-m", "dlrover_tpu.run",
         "--nproc_per_node=1", "--monitor_interval=0.2",
         str(script), str(pids)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True, start_new_session=True,
    )
    try:
        deadline = time.time() + 120
        while time.time() < deadline and not pids.exists():
            assert tpurun.poll() is None, tpurun.stdout.read()[-2000:]
            time.sleep(0.05)
        worker, child = map(int, pids.read_text().split())
        # the worker is out of the reach of a signal to tpurun's group
        assert os.getpgid(worker) == worker != os.getpgid(tpurun.pid)
        assert sorted(env_utils.live_pids(pgid=worker)) == sorted(
            [worker, child]
        )
        tpurun.send_signal(getattr(signal, signame))
        out, _ = tpurun.communicate(timeout=60)
        assert tpurun.returncode == 128 + getattr(signal, signame), out
        assert env_utils.live_pids(pgid=worker) == []
        # nor anything else of the job: the local master, a template
        assert env_utils.live_pids(session=tpurun.pid) == []
    finally:
        for pgid in (tpurun.pid, locals().get("worker")):
            if pgid:
                try:
                    os.killpg(pgid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass

