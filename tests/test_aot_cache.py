"""AOT executable cache: round-trip bit-identity across a real
process boundary, strict fall-back-to-trace on every mismatch class,
the label-index fast path, and the forkserver pre-load path."""

import json
import os
import pickle
import subprocess
import sys
import textwrap

import pytest

jax = pytest.importorskip("jax")
jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from dlrover_tpu.common import aot_cache  # noqa: E402

optax = pytest.importorskip("optax")

from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    TrainState,
    abstract_like,
    make_train_step,
    resolve_train_step,
)


def _loss(p, batch):
    h = jnp.tanh(batch["x"] @ p["w1"])
    return ((h @ p["w2"] - batch["y"]) ** 2).mean()


def _params():
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    return {
        "w1": jax.random.normal(k1, (6, 8), jnp.float32),
        "w2": jax.random.normal(k2, (8, 2), jnp.float32),
    }


def _batch():
    rng = np.random.default_rng(0)
    return {
        "x": jnp.asarray(rng.normal(size=(4, 6)).astype(np.float32)),
        "y": jnp.asarray(rng.normal(size=(4, 2)).astype(np.float32)),
    }


def _fresh(label="t"):
    optimizer = optax.adam(1e-3)
    step_fn = make_train_step(_loss, optimizer)
    state = TrainState.create(_params(), optimizer)
    return step_fn, state, _batch()


# one subprocess script, two modes: "write" traces+saves and prints
# the traced outputs; "load" must HIT (asserts resolution) and prints
# the deserialized executable's outputs — the parent compares bytes
_CHILD = textwrap.dedent("""
    import json, sys
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.common import aot_cache
    from dlrover_tpu.trainer.elastic_trainer import (
        TrainState, make_train_step,
    )

    mode, cache_dir = sys.argv[1], sys.argv[2]

    def loss(p, batch):
        h = jnp.tanh(batch["x"] @ p["w1"])
        return ((h @ p["w2"] - batch["y"]) ** 2).mean()

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {
        "w1": jax.random.normal(k1, (6, 8), jnp.float32),
        "w2": jax.random.normal(k2, (8, 2), jnp.float32),
    }
    optimizer = optax.adam(1e-3)
    step_fn = make_train_step(loss, optimizer)
    state = TrainState.create(params, optimizer)
    rng = np.random.default_rng(0)
    batch = {
        "x": jnp.asarray(rng.normal(size=(4, 6)).astype(np.float32)),
        "y": jnp.asarray(rng.normal(size=(4, 2)).astype(np.float32)),
    }
    res = aot_cache.resolve_step(
        step_fn, (state, batch), label="xproc", cache_dir=cache_dir
    )
    if mode == "write":
        assert res.source == "trace" and res.wrote, res
    else:
        assert res.source == "aot" and res.hit, (
            res.source, res.hit, res.reason,
        )
    new_state, metrics = res.fn(state, batch)
    out = {
        "loss": np.asarray(metrics["loss"]).tobytes().hex(),
        "grad_norm": np.asarray(
            metrics["grad_norm"]
        ).tobytes().hex(),
        "w1": np.asarray(new_state.params["w1"]).tobytes().hex(),
        "w2": np.asarray(new_state.params["w2"]).tobytes().hex(),
        "step": int(new_state.step),
    }
    print("RESULT " + json.dumps(out))
""")


def _run_child(mode, cache_dir):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.pathsep.join(
            [os.getcwd()] + sys.path[:1]
        ),
    )
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, mode, cache_dir],
        env=env, capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [
        ln for ln in proc.stdout.splitlines()
        if ln.startswith("RESULT ")
    ][-1]
    return json.loads(line[len("RESULT "):])


def test_roundtrip_bit_identity_across_processes(tmp_path):
    """The deserialized executable's outputs are byte-identical to a
    fresh trace's — proven across a REAL process boundary: process A
    traces, compiles, writes; process B deserializes and must agree
    bit for bit."""
    cache_dir = str(tmp_path / "aot")
    traced = _run_child("write", cache_dir)
    assert aot_cache.aot_entries(cache_dir) == 1
    loaded = _run_child("load", cache_dir)
    assert traced == loaded


def test_miss_writes_then_same_process_hits(tmp_path):
    cache_dir = str(tmp_path / "aot")
    step_fn, state, batch = _fresh()
    r1 = aot_cache.resolve_step(
        step_fn, (state, batch), label="t", cache_dir=cache_dir
    )
    assert r1.source == "trace" and r1.wrote and r1.trace_s > 0
    s1, m1 = r1.fn(state, batch)
    step_fn2, state2, batch2 = _fresh()
    r2 = aot_cache.resolve_step(
        step_fn2, (state2, batch2), label="t", cache_dir=cache_dir
    )
    assert r2.source == "aot" and r2.hit
    s2, m2 = r2.fn(state2, batch2)
    assert float(m1["loss"]) == float(m2["loss"])
    assert np.array_equal(
        np.asarray(s1.params["w1"]), np.asarray(s2.params["w1"])
    )


def test_world_size_mismatch_falls_back_to_trace(
    tmp_path, monkeypatch
):
    cache_dir = str(tmp_path / "aot")
    step_fn, state, batch = _fresh()
    r1 = aot_cache.resolve_step(
        step_fn, (state, batch), label="t", cache_dir=cache_dir
    )
    assert r1.wrote
    monkeypatch.setenv("DLROVER_WORLD_SIZE", "4")
    r2 = aot_cache.resolve_step(
        step_fn, (state, batch), label="t", cache_dir=cache_dir
    )
    # a resized world must never run the old world's binary
    assert r2.source == "trace" and not r2.hit
    s, m = r2.fn(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_aval_shape_mismatch_falls_back_to_trace(tmp_path):
    cache_dir = str(tmp_path / "aot")
    step_fn, state, batch = _fresh()
    aot_cache.resolve_step(
        step_fn, (state, batch), label="t", cache_dir=cache_dir
    )
    bigger = {
        "x": jnp.zeros((8, 6), jnp.float32),
        "y": jnp.zeros((8, 2), jnp.float32),
    }
    r2 = aot_cache.resolve_step(
        step_fn, (state, bigger), label="t", cache_dir=cache_dir
    )
    assert r2.source == "trace" and not r2.hit
    s, m = r2.fn(state, bigger)
    assert np.isfinite(float(m["loss"]))


def test_jax_version_mismatch_falls_back_to_trace(tmp_path):
    """An entry stamped by another jax never loads: rewrite the
    stored descriptor (entry + label index) with a foreign version
    string and resolve again — both the fast path and the keyed path
    must refuse it."""
    cache_dir = str(tmp_path / "aot")
    step_fn, state, batch = _fresh()
    r1 = aot_cache.resolve_step(
        step_fn, (state, batch), label="t", cache_dir=cache_dir
    )
    path = aot_cache.entry_path(r1.key, cache_dir)
    with open(path, "rb") as f:
        entry = pickle.loads(f.read())
    entry["desc"]["jax"] = "0.0.0-foreign"
    with open(path, "wb") as f:
        f.write(pickle.dumps(entry))
    idx_path = os.path.join(cache_dir, "t.idx")
    with open(idx_path, "w") as f:
        json.dump({"key": r1.key, "desc": entry["desc"]}, f)
    builder_calls = []

    def builder():
        builder_calls.append(1)
        return abstract_like((state, batch))

    r2 = aot_cache.resolve_step(
        step_fn, builder, label="t", cache_dir=cache_dir
    )
    assert r2.source == "trace" and not r2.hit
    assert builder_calls  # fast path refused -> full path ran
    s, m = r2.fn(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_corrupt_entry_falls_back_to_trace(tmp_path):
    cache_dir = str(tmp_path / "aot")
    step_fn, state, batch = _fresh()
    r1 = aot_cache.resolve_step(
        step_fn, (state, batch), label="t", cache_dir=cache_dir
    )
    s1, m1 = r1.fn(state, batch)
    path = aot_cache.entry_path(r1.key, cache_dir)
    with open(path, "wb") as f:
        f.write(b"definitely not a pickle")
    step_fn2, state2, batch2 = _fresh()
    r2 = aot_cache.resolve_step(
        step_fn2, (state2, batch2), label="t", cache_dir=cache_dir
    )
    assert r2.source == "trace" and not r2.hit  # never a crash
    s2, m2 = r2.fn(state2, batch2)
    assert float(m1["loss"]) == float(m2["loss"])


def test_label_index_fast_path_skips_example_build(tmp_path):
    """The warm fast path resolves by label WITHOUT building the
    abstract examples — the builder must never run on a hit (that
    eval_shape is real critical-path time in a respawn)."""
    cache_dir = str(tmp_path / "aot")
    step_fn, state, batch = _fresh()
    r1 = aot_cache.resolve_step(
        step_fn, (state, batch), label="t", cache_dir=cache_dir
    )
    assert r1.wrote

    def exploding_builder():
        raise AssertionError("builder must not run on a fast hit")

    r2 = aot_cache.resolve_step(
        step_fn, exploding_builder, label="t", cache_dir=cache_dir
    )
    assert r2.source == "aot" and r2.hit and r2.extra.get("fast")
    s, m = r2.fn(state, batch)
    assert np.isfinite(float(m["loss"]))


def test_guarded_call_falls_back_on_first_failure():
    calls = []

    def bad(*a):
        raise ValueError("aval drift")

    def good(*a):
        calls.append(a)
        return "ok"

    guarded = aot_cache._GuardedCall(bad, good)
    assert guarded(1, 2) == "ok"
    assert calls == [(1, 2)]
    # permanently on the fallback afterwards
    assert guarded(3) == "ok"


class _Lowered:
    """A ``Lowered`` that refuses the options with ``error``."""

    def __init__(self, error):
        self.error, self.asked = error, []

    def compile(self, compiler_options=None):
        self.asked.append(compiler_options)
        if compiler_options:
            raise RuntimeError(self.error)
        return "compiled"


@pytest.mark.parametrize("case", ["cpu", "unknown_option", "other_error"])
def test_compile_lowered_asks_for_the_list_order_where_it_can(case):
    """A step is compiled under ``COMPILER_OPTIONS``; a backend that
    has no such option compiles as it would (the CPU's, for real, and
    any that says so), and every other failure is the caller's."""
    assert aot_cache.COMPILER_OPTIONS == {"xla_memory_scheduler": "list"}
    if case == "cpu":
        lowered = jax.jit(lambda x: x + 1).lower(jnp.ones(3))
        out = aot_cache.compile_lowered(lowered)(jnp.ones(3))
        np.testing.assert_array_equal(np.asarray(out), 2.0)
        return
    unknown = case == "unknown_option"
    lowered = _Lowered(
        "INVALID_ARGUMENT: No such compile option: 'xla_memory_scheduler'"
        if unknown else "RESOURCE_EXHAUSTED: out of memory"
    )
    if unknown:
        assert aot_cache.compile_lowered(lowered) == "compiled"
        assert lowered.asked == [aot_cache.COMPILER_OPTIONS, None]
    else:
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            aot_cache.compile_lowered(lowered)
        assert lowered.asked == [aot_cache.COMPILER_OPTIONS]


def test_preload_serves_entries_from_memory(tmp_path):
    """preload_entries + file deletion: the executable still loads —
    this is exactly what a forked worker inherits from the template
    (bytes in memory, no disk on the recovery path)."""
    cache_dir = str(tmp_path / "aot")
    step_fn, state, batch = _fresh()
    r1 = aot_cache.resolve_step(
        step_fn, (state, batch), label="t", cache_dir=cache_dir
    )
    before = aot_cache.preloaded_entries()
    n, nbytes = aot_cache.preload_entries(cache_dir)
    try:
        assert n >= 1 and nbytes > 0
        assert aot_cache.preloaded_entries() >= before + 1
        os.unlink(aot_cache.entry_path(r1.key, cache_dir))
        os.unlink(os.path.join(cache_dir, "t.idx"))
        step_fn2, state2, batch2 = _fresh()
        r2 = aot_cache.resolve_step(
            step_fn2, (state2, batch2), label="t",
            cache_dir=cache_dir,
        )
        assert r2.source == "aot" and r2.hit and r2.preloaded
    finally:
        aot_cache._PRELOADED.clear()


def test_forkserver_pretrace_inherits_entries(tmp_path):
    """DLROVER_AOT_PRETRACE: the template preloads entry bytes and a
    forked child INHERITS them — proven by deleting the cache dir
    after the template started and asking the child (which imports
    no jax) what it sees in memory."""
    from dlrover_tpu.agent.forkserver import WorkerForkServer

    cache_dir = tmp_path / "aot"
    cache_dir.mkdir()
    (cache_dir / "deadbeef.aotx").write_bytes(b"x" * 64)
    out = tmp_path / "seen.txt"
    child = tmp_path / "child.py"
    child.write_text(textwrap.dedent(f"""
        from dlrover_tpu.common import aot_cache
        with open({str(out)!r}, "w") as f:
            f.write(str(aot_cache.preloaded_entries()))
    """))
    probe = tmp_path / "probe.py"
    probe.write_text("pass\n")
    env = dict(
        os.environ,
        DLROVER_AOT_PRETRACE="1",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        DLROVER_PRELOAD="json",
        PYTHONPATH=os.getcwd(),
    )
    old = {
        k: os.environ.get(k)
        for k in ("DLROVER_AOT_PRETRACE", "JAX_COMPILATION_CACHE_DIR",
                  "DLROVER_PRELOAD")
    }
    os.environ.update({
        "DLROVER_AOT_PRETRACE": "1",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "DLROVER_PRELOAD": "json",
    })
    fs = WorkerForkServer()
    try:
        # first spawn forces the template up (it preloads at start
        # and rescans before every fork)
        h = fs.spawn([str(probe)], env, timeout=60)
        assert h.wait(timeout=120) == 0
        # the template holds the bytes now; the dir can vanish
        (cache_dir / "deadbeef.aotx").unlink()
        h = fs.spawn([str(child)], env, timeout=60)
        assert h.wait(timeout=120) == 0
        assert out.read_text().strip() == "1"
    finally:
        fs.close()
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def test_profiler_resolve_books_phases_and_events(
    tmp_path, monkeypatch
):
    """RecoveryProfiler.resolve_step: MISS books the measured
    retrace + writes; HIT books aot with retrace=0; aot_cache and
    compile_cache (status) events land; the timeline budget and
    report read them back."""
    from dlrover_tpu.telemetry import events as ev_mod
    from dlrover_tpu.telemetry.events import read_events
    from dlrover_tpu.telemetry.timeline import recovery_budgets
    from dlrover_tpu.trainer.recovery import RecoveryProfiler

    log = tmp_path / "events.jsonl"
    monkeypatch.setenv("DLROVER_EVENT_LOG", str(log))
    monkeypatch.setenv(
        "JAX_COMPILATION_CACHE_DIR", str(tmp_path)
    )
    step_fn, state, batch = _fresh()
    p0 = RecoveryProfiler(restart_count=0, node_rank=0)
    step0 = p0.resolve_step(step_fn, (state, batch))
    assert p0.aot_hit is False
    assert p0.phases.get("retrace", 0) > 0
    assert "aot" in p0.phases
    s, m = step0(state, batch)
    # the step donates its input state: a second call needs a fresh
    # one (exactly what a respawned incarnation builds from restore)
    step_fn1, state1, batch1 = _fresh()
    p1 = RecoveryProfiler(restart_count=1, node_rank=0)
    step1 = p1.resolve_step(step_fn1, (state1, batch1))
    assert p1.aot_hit is True and p1.cache_hit is True
    assert p1.phases["retrace"] == 0.0
    assert p1.phases["aot"] > 0
    s1, m1 = step1(state1, batch1)
    assert float(m1["loss"]) == float(m["loss"])

    evs = list(read_events(str(log)))
    aot_events = [e for e in evs if e["type"] == "aot_cache"]
    assert [e["hit"] for e in aot_events] == [False, True]
    assert aot_events[0]["wrote"] is True
    cc = [e for e in evs if e["type"] == "compile_cache"]
    assert cc[-1]["status"] == "aot-hit" and cc[-1]["hit"] is True
    assert cc[-1]["aot_entries"] >= 1

    budgets = recovery_budgets(evs)
    rec = budgets[(0, 1)]
    assert rec["aot_cache_hit"] is True
    assert rec["retrace"] == 0.0 and rec["aot"] > 0
    from dlrover_tpu.telemetry import timeline as tl

    report = tl.to_report(tl.assemble(evs))
    assert "aot=HIT" in report


def test_resolve_train_step_helper_without_profiler(tmp_path):
    cache_dir = str(tmp_path / "aot")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    try:
        step_fn, state, batch = _fresh()
        step = resolve_train_step(
            step_fn, abstract_like(state), abstract_like(batch)
        )
        s, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))
        assert aot_cache.aot_entries(cache_dir) == 1
    finally:
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)


def test_code_change_invalidates_entry(tmp_path):
    """Same label, same avals, DIFFERENT code: the fingerprint half
    of the key must refuse the stale executable — a persistent cache
    dir survives across runs, and silently serving an executable
    compiled from an edited loss (or optimizer hyperparameter) would
    be a correctness bug, not a slow path."""
    cache_dir = str(tmp_path / "aot")
    optimizer = optax.adam(1e-3)
    step_a = make_train_step(_loss, optimizer)
    state = TrainState.create(_params(), optimizer)
    batch = _batch()
    r1 = aot_cache.resolve_step(
        step_a, (state, batch), label="t", cache_dir=cache_dir
    )
    assert r1.wrote

    def other_loss(p, b):
        h = jnp.tanh(b["x"] @ p["w1"])
        return 2.0 * ((h @ p["w2"] - b["y"]) ** 2).mean()

    step_b = make_train_step(other_loss, optimizer)
    state_b = TrainState.create(_params(), optimizer)
    r2 = aot_cache.resolve_step(
        step_b, (state_b, _batch()), label="t", cache_dir=cache_dir
    )
    assert r2.source == "trace" and not r2.hit
    # the fast path must refuse it too (index present, fn differs)
    def exploding():
        raise AssertionError("unreachable")
    lr_changed = make_train_step(_loss, optax.adam(5e-3))
    r3 = aot_cache.resolve_step(
        lr_changed, abstract_like((state_b, _batch())), label="t",
        cache_dir=cache_dir,
    )
    assert not r3.hit  # hyperparameter captured in a closure


# -- the sources a flax module's step is built from ------------------------------


def _copy_sources(tmp_path):
    import shutil

    package = os.path.dirname(os.path.dirname(aot_cache.__file__))
    root = tmp_path / "dlrover_tpu"
    for name in aot_cache.SOURCE_PACKAGES:
        shutil.copytree(
            os.path.join(package, name), root / name,
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    return str(root)


def test_editing_a_module_file_changes_the_source_digest(tmp_path):
    """The digest covers the bytes of models/, ops/, parallel/ and
    optim/: an edit to a copied ``parallel/moe.py`` or
    ``models/olmoe.py`` alone changes it (and so the key of every
    step whose closure holds a flax module)."""
    root = _copy_sources(tmp_path)
    assert aot_cache.source_digest(root) == aot_cache.source_digest()
    for edited in ("parallel/moe.py", "models/olmoe.py"):
        before = aot_cache.source_digest(root)
        with open(os.path.join(root, edited), "a") as f:
            f.write("\n# an edit\n")
        aot_cache._SOURCE_DIGESTS.pop(root)
        assert aot_cache.source_digest(root) != before


def test_a_flax_module_in_the_closure_feeds_the_sources(monkeypatch):
    """A loss over a flax module (its class defines ``__call__``): the
    fingerprint changes with the source digest; a loss over plain
    arrays does not read the sources at all."""
    from dlrover_tpu.models.gpt import GPT, GPTConfig

    model = GPT(GPTConfig.tiny())

    def over_module(p, b):
        return model.apply({"params": p}, b["x"]).sum()

    before = aot_cache.fn_fingerprint(over_module)
    plain = aot_cache.fn_fingerprint(_loss)
    monkeypatch.setattr(aot_cache, "source_digest", lambda: "edited")
    assert aot_cache.fn_fingerprint(over_module) != before
    assert aot_cache.fn_fingerprint(_loss) == plain


def test_fingerprint_over_a_module_is_stable_across_processes():
    script = textwrap.dedent("""
        import jax
        jax.config.update("jax_platforms", "cpu")
        from dlrover_tpu.common import aot_cache
        from dlrover_tpu.models.olmoe import (
            Olmoe, OlmoeConfig, make_olmoe_loss,
        )
        from dlrover_tpu.optim import adamw_bf16
        from dlrover_tpu.trainer.elastic_trainer import make_train_step
        step = make_train_step(
            make_olmoe_loss(Olmoe(OlmoeConfig.tiny())),
            adamw_bf16(learning_rate=3e-4, weight_decay=0.1),
        )
        print(aot_cache.fn_fingerprint(step), aot_cache.source_digest())
    """)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=repo, JAX_PLATFORMS="cpu")
    outs = [
        subprocess.run(
            [sys.executable, "-c", script], env=env, cwd=repo,
            capture_output=True, text=True, check=True,
        ).stdout.split()
        for _ in range(2)
    ]
    assert outs[0] == outs[1] and len(outs[0]) == 2
    assert outs[0][0] != "unhashable"
    assert outs[0][1] == aot_cache.source_digest()
