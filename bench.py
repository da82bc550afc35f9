"""Benchmark suite run on the real chip at end of round.

Headline: **flash-checkpoint stall** (reference claim ~10x less
training-blocking time than a synchronous save,
``docs/blogs/flash_checkpoint.md:361-383``) — training stall of a
flash save (on-device snapshot + async shm/persist in a separate
agent process, the real deployment shape) vs a synchronous
device_get + serialize-to-disk of the same state.
``vs_baseline`` = our speedup / 10.

Detail sections: GPT-2-small/XL + Llama-1.1B train-step MFU, flash
vs XLA attention (incl. GQA shapes), bounded auto-config search,
sparse KvVariable path, shm input pipeline, and — on the CPU
backend, concurrently — elastic recovery and goodput under churn.

Emission contract (VERDICT r3 #1 + r4 #1): after EVERY section the
bench prints a COMPACT headline-only JSON line (≤1500 bytes) to
stdout
    {"metric": ..., "value": N, "unit": "x", "vs_baseline": N,
     "detail": {goodput_pct, llama_mfu_2048, ..., "partial": true}}
so a driver that keeps only a 2000-byte stdout tail always finds the
newest metrics parseable in the last line.  The full cumulative
detail goes to stderr for humans and the repo log.  The final stdout
line is the same compact object minus "partial".  Sections run
headline-first, each in its OWN SUBPROCESS (SIGKILLed at its budget
so a hung section cannot contend with later timings), inside a
~14-minute total deadline (override: BENCH_DEADLINE_S).
"""

import json
import math
import os
import pickle
import shutil
import statistics
import subprocess
import sys
import tempfile
import threading
import time

# bf16 peak TFLOP/s per chip by device kind (public spec sheets)
PEAK_FLOPS = {
    "TPU v2": 22.5e12,
    "TPU v3": 61.5e12,  # per chip half of 123 board? v3 chip=123/2? use die
    "TPU v4": 137.5e12,  # per-chip (two cores) bf16 ~275/2 per die pair
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 229e12,
    "TPU v5p": 459e12,
}


def _peak_flops(device) -> float:
    """bf16 peak of the device by its ``device_kind``; a device that
    is not in the table is an error, never a default."""
    kind = getattr(device, "device_kind", "") or ""
    # longest prefix first so "TPU v5p" is not shadowed by "TPU v5"
    for name in sorted(PEAK_FLOPS, key=len, reverse=True):
        if kind.startswith(name):
            return PEAK_FLOPS[name]
    raise ValueError(
        f"no peak FLOP/s for device kind {kind!r} "
        f"(known: {sorted(PEAK_FLOPS)})"
    )


def _best_of(n: int, sample) -> float:
    """Min of ``n`` timing samples: host-side dispatch noise
    swings single samples, and every bench
    section must apply the same sampling policy or its numbers stop
    being comparable.  ``sample()`` runs one timed window (ending on
    a blocking scalar fetch) and returns seconds."""
    best = None
    for _ in range(n):
        dt = sample()
        best = dt if best is None else min(best, dt)
    return best


# supervision trees launched by CPU sections (goodput churn, elastic
# recovery): registered so the deadline/watchdog exit paths can kill
# them instead of orphaning restart-looping trainers on the machine
_LIVE_PROCS = []
_PROCS_SHUTDOWN = False


def _kill_job(proc):
    """SIGKILL a supervision tree started with start_new_session: by
    SESSION, because tpurun's workers lead process groups of their
    own and a kill of tpurun's group would leave them running."""
    import signal

    from dlrover_tpu.common.env_utils import live_pids

    for _ in range(50):
        pids = live_pids(session=proc.pid)
        if not pids:
            return
        for pid in pids:
            try:
                os.kill(pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
        time.sleep(0.05)


def _register_proc(proc):
    if _PROCS_SHUTDOWN:
        # an exit path already swept the registry; the racing CPU
        # thread must not leave a fresh orphan behind
        _kill_job(proc)
        return proc
    _LIVE_PROCS.append(proc)
    return proc


def _kill_live_procs():
    global _PROCS_SHUTDOWN
    _PROCS_SHUTDOWN = True
    for proc in list(_LIVE_PROCS):
        try:
            _kill_job(proc)
        except Exception:  # noqa: BLE001
            pass
    _LIVE_PROCS.clear()


def _round_finite(x, digits: int = 4):
    """round(x) when x is a finite number, else None (JSON-safe)."""
    import math

    return round(x, digits) if x is not None and math.isfinite(x) \
        else None


# set by _child_main: when a section runs in its own subprocess this
# is the child's process-start time, so budget-aware sections can
# compute how long they have before the parent's SIGKILL lands
_CHILD_T0 = None


def _section_remaining() -> float:
    """Seconds left before this section child's budget SIGKILL —
    inf when not running as a budgeted child.  Lets long sections
    (xl_act_offload) finish cleanly with an explicit partial result
    instead of dying mid-leg and landing in "skipped"."""
    try:
        budget = float(os.getenv("BENCH_SECTION_BUDGET_S", "") or 0.0)
    except ValueError:
        budget = 0.0
    if budget <= 0 or _CHILD_T0 is None:
        return float("inf")
    return budget - (time.time() - _CHILD_T0)


def _flops_per_token(cfg, n_params: int, seq: int) -> float:
    """PaLM-appendix accounting: 6N per token for the matmuls plus
    the causal-attention term 12 * L * seq * hidden."""
    return 6 * n_params + 12 * cfg.num_layers * seq * cfg.hidden_dim


def bench_train_step(jax, results: dict):
    """GPT-2-small train step: tokens/s + MFU, flash vs xla attention."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.gpt import (
        GPT,
        GPTConfig,
        count_params,
        cross_entropy_loss,
    )
    from dlrover_tpu.trainer.elastic_trainer import TrainState

    dev = jax.devices()[0]
    peak = _peak_flops(dev)
    smoke = bool(os.getenv("BENCH_SMOKE"))
    # batch 16 fits both attention impls without remat (xla keeps the
    # s^2 probs for backward); flash alone sustains batch 24 (+1% MFU)
    batch, seq = (2, 256) if smoke else (16, 1024)
    steps = 2 if smoke else 16

    def run(attention_impl: str):
        cfg = (
            GPTConfig.tiny(max_seq_len=seq, attention_impl=attention_impl)
            if smoke
            else GPTConfig.gpt2_small(
                max_seq_len=seq, attention_impl=attention_impl
            )
        )
        model = GPT(cfg)
        params = model.init_params(jax.random.PRNGKey(0), seq_len=seq)
        optimizer = optax.adamw(3e-4, weight_decay=0.1)
        state = TrainState.create(params, optimizer)
        n_params = count_params(params)

        def loss_fn(p, tokens):
            logits = model.apply({"params": p}, tokens[:, :-1])
            return cross_entropy_loss(logits, tokens[:, 1:])

        def one_step(state, tokens):
            loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens)
            updates, new_opt = optimizer.update(
                grads, state.opt_state, state.params
            )
            new_params = optax.apply_updates(state.params, updates)
            return (
                TrainState(
                    params=new_params, opt_state=new_opt,
                    step=state.step + 1,
                ),
                loss,
            )

        # K steps inside one jit: the deployment shape (no host sync
        # between steps); the scalar loss fetch at the end of the
        # window is the synchronization point
        @jax.jit
        def multi_step(state, tokens):
            def body(s, _):
                s, loss = one_step(s, tokens)
                return s, loss

            state, losses = jax.lax.scan(
                body, state, None, length=steps
            )
            return state, losses[-1]

        tokens = jnp.asarray(
            np.random.default_rng(0).integers(
                0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32
            )
        )
        state, loss = multi_step(state, tokens)  # compile + warm
        float(loss)

        def sample():
            nonlocal state, loss
            t0 = time.perf_counter()
            state, loss = multi_step(state, tokens)
            loss = float(loss)
            return (time.perf_counter() - t0) / steps

        dt = _best_of(2, sample)
        tokens_per_s = batch * seq / dt
        flops_per_token = _flops_per_token(cfg, n_params, seq)
        mfu = flops_per_token * tokens_per_s / peak
        return {
            "step_time_s": round(dt, 4),
            "tokens_per_s": round(tokens_per_s, 1),
            "mfu": round(mfu, 4),
            "loss": loss,
        }

    flash = run("flash")
    xla = run("xla")
    results["train_step"] = {
        "model": "tiny(smoke)" if smoke else "gpt2_small",
        "batch": batch,
        "seq_len": seq,
        "device_kind": getattr(dev, "device_kind", dev.platform),
        "peak_flops": peak,
        "flash_attention": flash,
        "xla_attention": xla,
        "flash_vs_xla_step_speedup": round(
            xla["step_time_s"] / max(flash["step_time_s"], 1e-9), 3
        ),
    }
    results["mfu"] = max(flash["mfu"], xla["mfu"])
    results["tokens_per_s"] = max(
        flash["tokens_per_s"], xla["tokens_per_s"]
    )


def _make_xl_step(jax, model, opt):
    """ONE step recipe shared by every XL leg (bench_xl_train_step
    and bench_xl_act_offload) — the offload-vs-remat comparison must
    measure the same step as the headline."""
    from functools import partial

    import optax

    from dlrover_tpu.models.gpt import cross_entropy_loss
    from dlrover_tpu.trainer.elastic_trainer import TrainState

    @partial(jax.jit, donate_argnums=0)
    def step(state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p, t: cross_entropy_loss(
                model.apply({"params": p}, t[:, :-1]), t[:, 1:]
            )
        )(state.params, tokens)
        updates, new_opt = opt.update(
            grads, state.opt_state, state.params
        )
        return (
            TrainState(
                params=optax.apply_updates(state.params, updates),
                opt_state=new_opt, step=state.step + 1,
            ),
            loss,
        )

    return step


def bench_xl_train_step(jax, results: dict):
    """GPT-2-XL (1.56B) on ONE chip — the reference's flash-ckpt
    story model (docs/blogs/megatron_flash_checkpoint.md trains
    GPT-1.5B).  Fits in 16 GB HBM via bf16 params + int8 (Pallas)
    optimizer moments + flash attention + remat + buffer donation."""
    from functools import partial

    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.gpt import (
        GPT,
        GPTConfig,
        count_params,
        cross_entropy_loss,
    )
    from dlrover_tpu.optim import adamw_bf16
    from dlrover_tpu.trainer.elastic_trainer import TrainState

    if os.getenv("BENCH_SMOKE"):
        return
    dev = jax.devices()[0]
    peak = _peak_flops(dev)
    batch, seq = 4, 1024
    cfg = GPTConfig(
        num_layers=48, num_heads=25, hidden_dim=1600,
        max_seq_len=seq, attention_impl="flash", remat=True,
        param_dtype=jnp.bfloat16,
    )
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0), seq_len=seq)
    # bf16-moment adam: the model fits at batch 4 with room to
    # spare, and skipping q_adamw's quant/requant pass is worth
    # ~140 ms/step (42% -> 51%+ MFU); int8 moments remain the
    # memory-pressure fallback (xl_act_offload still uses them)
    opt = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
    state = TrainState.create(params, opt)
    n = count_params(params)
    step = _make_xl_step(jax, model, opt)

    tokens = jnp.asarray(
        np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32
        )
    )
    state, loss = step(state, tokens)  # compile + warm
    loss0 = float(loss)
    steps = 8  # past the transient Adam warm-up spike (~step 4)

    def sample():
        nonlocal state, loss
        t0 = time.perf_counter()
        for _ in range(steps):
            state, loss = step(state, tokens)
        loss = float(loss)
        return (time.perf_counter() - t0) / steps

    dt = _best_of(2, sample)
    tokens_per_s = batch * seq / dt
    flops_per_token = _flops_per_token(cfg, n, seq)
    results["xl_train_step"] = {
        "model": "gpt2_xl",
        "num_params": n,
        "batch": batch,
        "seq_len": seq,
        "recipe": "bf16 params + bf16-moment adam + flash + remat",
        "step_time_s": round(dt, 4),
        "tokens_per_s": round(tokens_per_s, 1),
        "mfu": round(flops_per_token * tokens_per_s / peak, 4),
        "loss_first": loss0,
        "loss": loss,
    }
    del state, tokens


def bench_xl_act_offload(jax, results: dict):
    """Selective activation offload (reference:
    selective_offloading_checkpoint.py:1): the lever exists to fit
    shapes plain remat cannot — push an XL-class model to seq 2048 and
    run both remat policies; whichever OOMs is recorded honestly.  Own
    section: XL compiles take a minute or more, and this
    experiment must not time out the headline XL numbers.

    Root-cause of three rounds of silent budget kills (r3-r5): the
    FULL 48-layer GPT-2-XL's offload-policy compile alone exceeds the
    360 s section budget, so the r3-era
    budget gate (which only guarded the SECOND leg) never fired — the
    section died mid-first-leg with nothing but the config keys
    dumped.  Fix: (a) the default config is a HALF-DEPTH 24-layer
    XL slice (same width/heads/seq — the offload-vs-remat comparison
    is per-layer, so halving depth halves compile and step cost
    without changing what is being compared; ``BENCH_XL_OFFLOAD_LAYERS``
    restores the full model on boxes that can afford it), and (b) BOTH
    legs are budget-gated with an explicit skip reason, so a tight
    budget now yields a labeled partial result instead of a kill."""
    from functools import partial

    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.gpt import (
        GPT,
        GPTConfig,
        cross_entropy_loss,
    )
    from dlrover_tpu.optim import q_adamw
    from dlrover_tpu.trainer.elastic_trainer import TrainState

    if os.getenv("BENCH_SMOKE"):
        return
    try:
        num_layers = int(os.getenv("BENCH_XL_OFFLOAD_LAYERS", "24"))
    except ValueError:
        num_layers = 24

    def try_xl(seq2, batch2, policy):
        cfg2 = GPTConfig(
            num_layers=num_layers, num_heads=25, hidden_dim=1600,
            max_seq_len=seq2, attention_impl="flash", remat=True,
            remat_policy=policy, param_dtype=jnp.bfloat16,
        )
        model2 = GPT(cfg2)
        try:
            params2 = model2.init_params(
                jax.random.PRNGKey(0), seq_len=seq2
            )
            opt2 = q_adamw(learning_rate=3e-4, weight_decay=0.1)
            state2 = TrainState.create(params2, opt2)
            step2 = _make_xl_step(jax, model2, opt2)

            toks = jnp.asarray(
                np.random.default_rng(0).integers(
                    0, cfg2.vocab_size, (batch2, seq2 + 1),
                    dtype=np.int32,
                )
            )
            state2, l2 = step2(state2, toks)
            float(l2)
            t0 = time.perf_counter()
            for _ in range(4):
                state2, l2 = step2(state2, toks)
            l2 = float(l2)
            dt2 = (time.perf_counter() - t0) / 4
            return {
                "ok": True, "step_time_s": round(dt2, 4),
                "tokens_per_s": round(batch2 * seq2 / dt2, 1),
                "loss": l2,
            }
        except Exception as e:  # noqa: BLE001 - OOM is the finding
            return {"ok": False, "error": f"{type(e).__name__}: "
                    + str(e)[:200]}

    seq2, batch2 = 2048, 4
    # filled INCREMENTALLY (the key lands before the legs run): the
    # section regularly outlives its budget, and
    # the child's periodic state dump must preserve a completed
    # offload leg even when the control leg's kill arrives
    out = {
        "model": f"gpt2_xl_{num_layers}L",
        "num_layers": num_layers,
        "seq_len": seq2, "batch": batch2,
    }
    results["xl_act_offload"] = out
    # gate the FIRST leg too: its compile is the
    # term that killed r3-r5, and a leg that cannot finish before the
    # subprocess SIGKILL should be an explicit skip, not a corpse.
    # The estimate is env-tunable (measured wall of a warm full-depth
    # leg on the r5 box was >360s; the 24-layer default roughly
    # halves it)
    try:
        est_first = float(os.getenv("BENCH_XL_LEG_EST_S", "150"))
    except ValueError:
        est_first = 150.0
    rem = _section_remaining()
    if rem < est_first:
        out["offload"] = {
            "ok": False,
            "skipped": (
                f"budget: {rem:.0f}s left < ~{est_first:.0f}s "
                "offload leg (BENCH_XL_LEG_EST_S)"
            ),
        }
        out["partial"] = True
        return
    t_leg = time.time()
    out["offload"] = try_xl(seq2, batch2, "offload")
    leg_s = time.time() - t_leg
    # budget-aware: the control leg costs about what the offload leg
    # did (same model, same compile pipeline).  If it cannot finish
    # before the subprocess SIGKILL, record an explicit partial
    # result and exit cleanly — a half-run leg's numbers would be
    # lost at the kill anyway, and "partial": true keeps the section
    # out of the headline's "skipped" list
    rem = _section_remaining()
    est = leg_s * 1.2 + 30.0
    if rem < est:
        out["plain_remat_control"] = {
            "ok": False,
            "skipped": (
                f"budget: {rem:.0f}s left < ~{est:.0f}s control leg"
            ),
        }
        out["partial"] = True
        return
    out["plain_remat_control"] = try_xl(seq2, batch2, "full")


def bench_input_pipeline(jax, results: dict):
    """Input-bound fraction of the train step: GPT-2-small batch 16
    fed by the cross-process shm dataloader (2 workers, synthetic
    token batches) — the loader's measured input_wait over the loop's
    wall time must be a rounding error (reference capability:
    shm_dataloader.py:284 wait-free input)."""
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.models.gpt import (
        GPT,
        GPTConfig,
        cross_entropy_loss,
    )
    from dlrover_tpu.trainer.elastic_trainer import TrainState
    from dlrover_tpu.trainer.shm_loader import ShmDataLoader

    smoke = bool(os.getenv("BENCH_SMOKE"))
    if smoke:
        # tiny config: the smoke run must still drive the loader and
        # coworker data-host process paths end-to-end
        batch, seq = 4, 128
        cfg = GPTConfig.tiny(max_seq_len=seq)
    else:
        batch, seq = 16, 1024
        cfg = GPTConfig.gpt2_small(
            max_seq_len=seq, attention_impl="flash"
        )
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0), seq_len=seq)
    optimizer = optax.adamw(3e-4, weight_decay=0.1)
    state = TrainState.create(params, optimizer)

    @jax.jit
    def step(state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p, t: cross_entropy_loss(
                model.apply({"params": p}, t[:, :-1]), t[:, 1:]
            )
        )(state.params, tokens)
        updates, new_opt = optimizer.update(
            grads, state.opt_state, state.params
        )
        import optax as _o

        return (
            TrainState(
                params=_o.apply_updates(state.params, updates),
                opt_state=new_opt, step=state.step + 1,
            ),
            loss,
        )

    steps = 6 if smoke else 16
    read_fn = _read_tokens_smoke if smoke else _read_tokens
    loader = ShmDataLoader(
        read_fn=read_fn,
        batch_size=batch,
        index_iter=range(batch * (steps + 1)),
        num_workers=2,
    )
    try:
        it = iter(loader)
        first = next(it)  # covers compile + loader spin-up
        state, loss = step(state, jnp.asarray(first))
        float(loss)
        t0 = time.perf_counter()
        wait0 = loader.stats()["input_wait_s"]
        n = 0
        for host_batch in it:
            state, loss = step(state, jnp.asarray(host_batch))
            n += 1
        float(loss)
        wall = time.perf_counter() - t0
        input_wait = loader.stats()["input_wait_s"] - wait0
    finally:
        loader.shutdown()
    results["input_pipeline"] = {
        "model": "tiny(smoke)" if smoke else "gpt2_small",
        "batch": batch,
        "steps": n,
        "loader": "shm 2-proc workers",
        "step_wall_s": round(wall / max(1, n), 4),
        "input_wait_s": round(input_wait, 4),
        "input_bound_pct": round(100 * input_wait / wall, 2),
    }

    # coworker leg: a DATA-HOST PROCESS serves the same batches over
    # the comm layer (reference: coworker_data_service.py:1 CPU pods
    # feeding accelerator pods); input-bound fraction must stay small
    # across the host boundary too
    from dlrover_tpu.trainer.coworker import CoworkerDataLoader

    co_steps = 4 if smoke else 8
    read_name = "_read_tokens_smoke" if smoke else "_read_tokens"
    host_script = (
        "import sys, time\n"
        f"sys.path.insert(0, {os.getcwd()!r})\n"
        "from dlrover_tpu.trainer.coworker import "
        "CoworkerDataService\n"
        f"from bench import {read_name} as read_fn\n"
        "svc = CoworkerDataService(read_fn=read_fn, "
        f"batch_size={batch}, index_iter=range({batch * co_steps}), "
        "num_workers=2, host='127.0.0.1').start()\n"
        "print(f'PORT {svc.port}', flush=True)\n"
        "while True:\n"
        "    time.sleep(0.5)\n"
    )
    # stdout/stderr to a FILE polled under a deadline: a blocking
    # pipe read against a child that prints something else first (or
    # nothing) would hang this section forever (ADVICE r4).  No
    # start_new_session: the host shares this process's group, so the
    # bench's SIGKILL-on-budget reaps it — it can never orphan.
    host_log = tempfile.NamedTemporaryFile(
        mode="w+", suffix=".coworker.log", delete=False,
        # inside the bench workdir when available: a budget SIGKILL
        # skips the finally below, and the parent's rmtree(workdir)
        # must still reclaim the file
        dir=os.getenv("BENCH_WORKDIR") or None,
    )
    data_host = subprocess.Popen(
        [sys.executable, "-c", host_script],
        stdout=host_log, stderr=subprocess.STDOUT,
        text=True, cwd=os.getcwd(),
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    try:
        port = None
        deadline = time.time() + 60
        while time.time() < deadline and port is None:
            with open(host_log.name) as lf:
                for line in lf:
                    if line.startswith("PORT"):
                        port = line.split()[1]
                        break
            if data_host.poll() is not None and port is None:
                break
            time.sleep(0.1)
        if port is None:
            data_host.kill()
            data_host.wait()
            with open(host_log.name) as lf:
                err = lf.read()[-500:]
            raise RuntimeError(
                f"coworker data host failed to start: {err}"
            )
        co_loader = CoworkerDataLoader("127.0.0.1:" + port)
        co_it = iter(co_loader)
        # warm-up batch excludes connect + first un-pipelined round
        # trip, mirroring the shm leg's spin-up exclusion
        state, loss = step(state, jnp.asarray(next(co_it)))
        float(loss)
        co_wait0 = co_loader.stats()["input_wait_s"]
        t0 = time.perf_counter()
        co_n = 0
        for host_batch in co_it:
            state, loss = step(state, jnp.asarray(host_batch))
            co_n += 1
        float(loss)
        co_wall = time.perf_counter() - t0
        co_wait = co_loader.stats()["input_wait_s"] - co_wait0
    finally:
        data_host.kill()
        data_host.wait()
        host_log.close()
        try:
            os.remove(host_log.name)
        except OSError:
            pass
    results["input_pipeline"]["coworker"] = {
        "loader": "coworker data-host process over TCP",
        "steps": co_n,
        "step_wall_s": round(co_wall / max(1, co_n), 4),
        "input_wait_s": round(co_wait, 4),
        "input_bound_pct": round(100 * co_wait / max(co_wall, 1e-9), 2),
    }


def _read_tokens(i: int):
    """Module-level (picklable) synthetic sample for the input bench."""
    import numpy as np

    rng = np.random.default_rng(i)
    return rng.integers(0, 50257, 1025).astype(np.int32)


def _read_tokens_smoke(i: int):
    import numpy as np

    rng = np.random.default_rng(i)
    return rng.integers(0, 256, 129).astype(np.int32)  # tiny vocab


def bench_sparse_kv(jax, results: dict):
    """Sparse path END-TO-END on the chip via the split step
    (host callbacks serialize the device step with the host table,
    so the production path is host gather -> jitted dense step ->
    host group-Adam update, double-buffered so the table work
    overlaps device compute — the reference's CPU-parameter-server
    shape, tfplus kv_variable_ops.cc:37 + training/group_adam.py:28).
    Reports raw host table rates AND full DeepFM steps/s with device
    compute included, pipelined vs strict."""
    import numpy as np
    import optax

    from dlrover_tpu.models.deepfm import DeepFM, DeepFMConfig
    from dlrover_tpu.ops.kv_variable import (
        GroupAdamOptimizer,
        KvVariable,
    )
    from dlrover_tpu.trainer.sparse_pipeline import (
        SparseTrainPipeline,
        make_deepfm_device_step,
    )

    if os.getenv("BENCH_SMOKE"):
        return
    dim, B = 64, 4096
    table = KvVariable(dim=dim, initial_capacity=1 << 16)
    opt = GroupAdamOptimizer(table, learning_rate=1e-2)
    rng = np.random.default_rng(0)
    key_sets = [
        rng.integers(0, 200_000, B).astype(np.int64)
        for _ in range(8)
    ]

    # (a) host-only table rates.  FIRST pass over fresh keys measures
    # INSERT (hash insert + slab growth); steady-state training hits
    # the warm path, so gather is measured on the second pass — the
    # r4 record conflated them and reported insert cost as "gather"
    # (0.3 M/s for what is an ~18 M/s warm lookup)
    t0 = time.perf_counter()
    for k in key_sets:
        table.gather(k)
    insert_dt = (time.perf_counter() - t0) / len(key_sets)
    reps = 5
    t0 = time.perf_counter()
    for _ in range(reps):
        for k in key_sets:
            table.gather(k)
    host_dt = (time.perf_counter() - t0) / (len(key_sets) * reps)

    # (b) host gather + host GroupAdam update (the sparse train step
    # minus device compute)
    grads = np.ones((B, dim), np.float32)
    t0 = time.perf_counter()
    for k in key_sets:
        table.gather(k)
        opt.apply_gradients(k, grads)
    step_dt = (time.perf_counter() - t0) / len(key_sets)

    # (a2) hybrid two-tier cold-miss cost: spill most rows to disk,
    # then gather a batch of COLD keys (every one promotes from the
    # spill file) vs the warm in-DRAM batch
    spill_dir = tempfile.mkdtemp(prefix="kv_spill_")
    spill_table = KvVariable(dim=dim, initial_capacity=1 << 16)
    all_keys = np.unique(
        np.concatenate(key_sets)
    ).astype(np.int64)
    spill_table.insert(
        all_keys,
        np.zeros((all_keys.size, dim), np.float32),
    )
    hot = all_keys[: B]
    for _ in range(3):
        spill_table.gather(hot)  # heat a resident working set
    spill_table.enable_spill(
        os.path.join(spill_dir, "bench.spill"),
        max_dram_rows=2 * B,
    )
    st0 = spill_table.spill_stats()
    cold = all_keys[-B:]
    t0 = time.perf_counter()
    spill_table.gather(cold, insert_missing=False)
    cold_dt = time.perf_counter() - t0
    t0 = time.perf_counter()
    spill_table.gather(hot[:B], insert_missing=False)
    warm_dt = time.perf_counter() - t0
    st1 = spill_table.spill_stats()
    spill_detail = {
        "disk_rows_before": st0["disk_rows"],
        "cold_batch_promotions": st1["promotions"]
        - st0["promotions"],
        "cold_gather_Mlookups_per_s": round(B / cold_dt / 1e6, 3),
        "warm_gather_Mlookups_per_s": round(B / warm_dt / 1e6, 3),
        "cold_miss_penalty_x": round(cold_dt / max(warm_dt, 1e-9), 2),
    }
    shutil.rmtree(spill_dir, ignore_errors=True)

    # (c) the full hybrid train step: criteo-class DeepFM, 26 sparse
    # fields, FM + deep tower on the chip, tables on the host
    cfg = DeepFMConfig(embedding_dim=16)
    batch, steps = 512, 12
    data_rng = np.random.default_rng(1)

    def make_batches(n):
        out = []
        for _ in range(n):
            sparse = data_rng.integers(
                0, 200_000, (batch, cfg.num_sparse_fields)
            ).astype(np.int64)
            dense = data_rng.normal(
                size=(batch, cfg.num_dense_features)
            ).astype(np.float32)
            labels = (sparse[:, 0] % 2).astype(np.float32)
            out.append((sparse, dense, labels))
        return out

    # ONE jitted step shared by both tiers (model.apply is a pure
    # function of the config; the tables are host objects handed to
    # the pipeline, so the second tier reuses the compiled HLO)
    optimizer = optax.adam(1e-2)
    shared_model = DeepFM(cfg)
    dstep = make_deepfm_device_step(shared_model, optimizer)

    def run_tier(pipeline: bool):
        model = DeepFM(cfg)
        params = model.init_dense_params()
        state = (params, optimizer.init(params))
        pipe = SparseTrainPipeline(
            model.table, model.sparse_optimizer, dstep,
            pipeline=pipeline,
        )
        state = pipe.run(state, make_batches(2))  # compile + warm
        pipe.stats.update(
            steps=0, gather_s=0.0, fetch_s=0.0, update_s=0.0,
            dispatch_s=0.0, wall_s=0.0,
        )
        last = {}
        state = pipe.run(
            state, make_batches(steps),
            on_aux=lambda a: last.update(a),
        )
        loss = float(last["loss"])  # the honest end-of-run sync
        rep = pipe.overlap_report()
        rep["loss"] = round(loss, 4)
        rep["steps_per_s"] = round(steps / rep["wall_s"], 2)
        for k in ("gather_s", "fetch_s", "update_s", "dispatch_s",
                  "wall_s"):
            rep[k] = round(rep[k], 4)
        return rep

    pipelined = run_tier(True)
    strict = run_tier(False)

    # (d) kv flash-checkpoint cost (ROADMAP item 2 follow-on): how
    # long the table + GroupAdam slot export that rides EVERY sparse
    # save takes, and how long the import on the restore side — on
    # the real table the rate benches above populated
    from dlrover_tpu.checkpoint.sparse import SparseStateAdapter

    adapter = SparseStateAdapter(digest=False)
    adapter.register_optimizer(opt)
    t0 = time.perf_counter()
    kv_state = adapter.export_state(step=1, rank=0)
    kv_export_s = time.perf_counter() - t0
    kv_rows = len(table)
    kv_bytes = sum(
        sum(a.nbytes for a in blobs.values())
        for name, blobs in kv_state.items()
        if isinstance(blobs, dict) and "keys" in blobs
    )
    fresh_table = KvVariable(
        dim=dim, initial_capacity=1 << 16, name=table.name
    )
    fresh_opt = GroupAdamOptimizer(fresh_table, learning_rate=1e-2)
    fresh = SparseStateAdapter(digest=False)
    fresh.register_optimizer(fresh_opt)
    t0 = time.perf_counter()
    fresh.import_state(kv_state, tier="bench", step=1, rank=0)
    kv_restore_s = time.perf_counter() - t0
    kv_detail = {
        "export_s": round(kv_export_s, 4),
        "restore_s": round(kv_restore_s, 4),
        "rows": int(kv_rows),
        "mb": round(kv_bytes / 2**20, 1),
        "export_MBps": round(
            kv_bytes / 2**20 / max(kv_export_s, 1e-9), 1
        ),
        "restore_MBps": round(
            kv_bytes / 2**20 / max(kv_restore_s, 1e-9), 1
        ),
        "tables": "embedding + group-adam m/v slots",
    }

    results["sparse_kv"] = {
        "dim": dim,
        "batch_keys": B,
        "table_rows": len(table),
        "host_gather_Mlookups_per_s": round(B / host_dt / 1e6, 3),
        "host_insert_Mkeys_per_s": round(B / insert_dt / 1e6, 3),
        "host_step_per_s": round(1.0 / step_dt, 2),
        "host_Mlookups_per_s": round(B / step_dt / 1e6, 3),
        "bytes_per_gather_mb": round(B * dim * 4 / 2**20, 2),
        "spill_tier": spill_detail,
        "kv_checkpoint": kv_detail,
        "deepfm_e2e": {
            "model": "deepfm 26 sparse fields, dim 16",
            "batch": batch,
            "split_step": "host gather -> device FM+MLP -> host "
                          "group-adam (staleness-1 double buffer)",
            "pipelined": pipelined,
            "strict": strict,
            "pipeline_speedup": round(
                strict["wall_s"] / max(pipelined["wall_s"], 1e-9), 3
            ),
        },
    }


def bench_auto_config(jax, results: dict):
    """BOUNDED strategy search ON THE CHIP (VERDICT r3 #4: the
    unbounded profile-everything search is what blew the round-3
    deadline): the static cost-model tier ranks every HBM-surviving
    candidate from compiles alone, and only the top-1 pays for
    on-chip profiled steps — compared against the hand-tuned
    GPT-2-small recipe measured by ``bench_train_step`` (reference
    pitch: the machine finds the config —
    atorch/auto/engine/acceleration_engine.py:13)."""
    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.accel.model_context import ModelContext
    from dlrover_tpu.accel.strategy_search import search_strategy
    from dlrover_tpu.models.gpt import (
        GPT,
        GPTConfig,
        cross_entropy_loss,
    )

    if os.getenv("BENCH_SMOKE"):
        return
    # same model/shape as bench_train_step so its measured flash
    # step is the hand-recipe control
    batch, seq = 16, 1024
    cfg = GPTConfig.gpt2_small(max_seq_len=seq)
    model = GPT(cfg)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32
    )
    batch_dict = {
        "x": jnp.asarray(tokens[:, :-1]),
        "y": jnp.asarray(tokens[:, 1:]),
    }

    def loss_fn(p, b, model=model):
        logits = model.apply({"params": p}, b["x"])
        return cross_entropy_loss(logits, b["y"])

    context = ModelContext(
        model=model,
        optim_factory=lambda: optax.adamw(3e-4, weight_decay=0.1),
        loss_fn=loss_fn,
        sample_batch=batch_dict,
        model_config=cfg,
    )
    t0 = time.perf_counter()
    result = search_strategy(
        context, num_devices=1, grad_accums=(1,),
        rank_mode="hybrid", profile_top_k=1, profile_steps=4,
        # XL compiles are ~60s cold: 2 cost compiles + 1 profile
        # keeps the section inside its budget even cache-cold
        cost_budget=2,
    )
    search_wall = time.perf_counter() - t0
    # the fair comparator runs the HAND recipe through the SAME
    # profiling harness (per-dispatch timing adds host overhead
    # per step that the train_step section's scan-of-steps never pays,
    # which would charge the search for harness overhead)
    from dlrover_tpu.accel.dry_runner import profile_plan
    from dlrover_tpu.accel.opt_lib import OptimizationLibrary
    from dlrover_tpu.accel.strategy import Strategy

    hand_opts = [("parallel_mode", {}), ("amp_native", {})]
    if jax.default_backend() == "tpu":
        hand_opts.append(("module_replace", {"attention": "flash"}))
    hand_plan = OptimizationLibrary().apply_strategy(
        Strategy(opts=hand_opts), context
    )
    hand_prof = profile_plan(
        hand_plan, context, profile_steps=4
    )
    hand = (
        hand_prof.step_time_s if hand_prof.ok
        else results.get("train_step", {})
        .get("flash_attention", {})
        .get("step_time_s")
    )
    best_t = result.best.step_time_s or result.best.est_step_time_s
    results["auto_config"] = {
        "model": "gpt2_small",
        "search": "hybrid: cost-model ranks all, top-1 profiled",
        "searched_recipe": result.best.describe(),
        "searched_step_time_s": round(best_t, 4),
        "hand_recipe_step_time_s": (
            round(hand, 4) if hand else None
        ),
        "hand_profiled_same_harness": hand_prof.ok,
        "train_section_step_time_s": (
            results.get("train_step", {})
            .get("flash_attention", {})
            .get("step_time_s")
        ),
        "searched_vs_hand": (
            round(best_t / hand, 3) if hand else None
        ),
        "search_wall_s": round(search_wall, 1),
        "evaluated": [
            {"recipe": c.describe(),
             "est_step_time_s": _round_finite(c.est_step_time_s),
             "step_time_s": _round_finite(c.step_time_s)}
            for c in result.evaluated
        ],
    }


def bench_llama_train_step(jax, results: dict):
    """Flagship family on the chip: Llama-class GQA model (TinyLlama
    1.1B shape: 22L x 2048h, 32 q-heads / 4 kv-heads, SwiGLU 5632),
    seq 2048 and 4096, flash attention + bf16 params + int8 moments +
    remat — the BASELINE.md north-star path scaled to the one 16 GB
    chip (ref acceleration path: atorch/modules/transformer/
    layers.py:1353 LlamaAttentionFA)."""
    from functools import partial

    import jax.numpy as jnp
    import numpy as np
    import optax

    from dlrover_tpu.models.gpt import cross_entropy_loss
    from dlrover_tpu.models.llama import Llama, LlamaConfig
    from dlrover_tpu.optim import adamw_bf16

    if os.getenv("BENCH_SMOKE"):
        return
    dev = jax.devices()[0]
    peak = _peak_flops(dev)
    out = {}
    for seq, batch in ((2048, 4), (4096, 2)):
        cfg = LlamaConfig(
            vocab_size=32000, max_seq_len=seq, num_layers=22,
            num_heads=32, num_kv_heads=4, hidden_dim=2048,
            intermediate_dim=5632, attention_impl="flash",
            remat=True, param_dtype=jnp.bfloat16,
        )
        model = Llama(cfg)
        params = model.init_params(jax.random.PRNGKey(0), seq_len=seq)
        n = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(params)
        )
        # bf16-moment adam beats int8 moments by ~11 MFU points at
        # this scale (the quant pass is ~20% of step wall); int8
        # stays the memory-tight fallback
        opt = adamw_bf16(learning_rate=3e-4, weight_decay=0.1)
        from dlrover_tpu.trainer.elastic_trainer import TrainState

        state = TrainState.create(params, opt)

        @partial(jax.jit, donate_argnums=0)
        def step(state, tokens, model=model, opt=opt):
            loss, grads = jax.value_and_grad(
                lambda p, t: cross_entropy_loss(
                    model.apply({"params": p}, t[:, :-1]), t[:, 1:]
                )
            )(state.params, tokens)
            updates, new_opt = opt.update(
                grads, state.opt_state, state.params
            )
            return (
                TrainState(
                    params=optax.apply_updates(state.params, updates),
                    opt_state=new_opt, step=state.step + 1,
                ),
                loss,
            )

        tokens = jnp.asarray(
            np.random.default_rng(0).integers(
                0, cfg.vocab_size, (batch, seq + 1), dtype=np.int32
            )
        )
        state, loss = step(state, tokens)  # compile + warm
        loss0 = float(loss)
        steps = 8

        def sample():
            nonlocal state, loss
            t0 = time.perf_counter()
            for _ in range(steps):
                state, loss = step(state, tokens)
            loss = float(loss)
            return (time.perf_counter() - t0) / steps

        dt = _best_of(2, sample)
        tokens_per_s = batch * seq / dt
        fpt = _flops_per_token(cfg, n, seq)
        out[f"seq{seq}"] = {
            "batch": batch,
            "step_time_s": round(dt, 4),
            "tokens_per_s": round(tokens_per_s, 1),
            "mfu": round(fpt * tokens_per_s / peak, 4),
            "loss_first": loss0,
            "loss": loss,
        }
        del state, params, tokens
    out.update({
        "model": "llama_1.1b_gqa",
        "num_params": n,
        "num_heads": 32,
        "num_kv_heads": 4,
        "recipe": "bf16 params + bf16-moment adam + flash(GQA) + remat",
    })
    results["llama_train_step"] = out


def bench_gqa_attention_kernel(jax, results: dict):
    """GQA flash vs XLA attention at Llama shapes (32 q-heads /
    4 kv-heads, head_dim 64): fwd+bwd wall time, seq 2048/4096."""
    import jax.numpy as jnp

    from dlrover_tpu.ops.flash_attention import flash_attention

    if os.getenv("BENCH_SMOKE"):
        return
    h, kv, d = 32, 4, 64
    out = {}
    for seq, b in ((2048, 4), (4096, 2)):
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, seq, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, seq, kv, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, seq, kv, d), jnp.bfloat16)

        def loss_flash(q, k, v):
            return flash_attention(q, k, v, causal=True).sum()

        def loss_xla(q, k, v):
            # GQA via explicit KV repeat (what a non-GQA-aware kernel
            # must do)
            kk = jnp.repeat(k, h // kv, axis=2)
            vv = jnp.repeat(v, h // kv, axis=2)
            qt = q.transpose(0, 2, 1, 3)
            kt = kk.transpose(0, 2, 1, 3)
            vt = vv.transpose(0, 2, 1, 3)
            s = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) / d**0.5
            mask = jnp.tril(jnp.ones((seq, seq), bool))
            s = jnp.where(mask, s, -1e9)
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
            o = jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(jnp.bfloat16), vt
            )
            return o.sum()

        def time_fn(fn):
            g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)))
            r = g(q, k, v)  # compile + warm
            float(r[0].ravel()[0])

            def sample():
                t0 = time.perf_counter()
                for _ in range(5):
                    out = g(q, k, v)
                float(out[0].ravel()[0])
                return (time.perf_counter() - t0) / 5

            return _best_of(3, sample)

        tf = time_fn(loss_flash)
        tx = time_fn(loss_xla)
        out[f"seq{seq}"] = {
            "shape": [b, seq, h, d],
            "kv_heads": kv,
            "gqa_flash_fwd_bwd_s": round(tf, 5),
            "xla_repeat_fwd_bwd_s": round(tx, 5),
            "speedup": round(tx / max(tf, 1e-9), 3),
        }
    results["gqa_attention_kernel"] = out


def bench_attention_kernel(jax, results: dict):
    """Microbench: Pallas flash attention vs plain XLA attention,
    fwd+bwd at a training seq len and a long-context one (where XLA
    must materialize the s^2 probs and flash pulls far ahead)."""
    import jax.numpy as jnp

    from dlrover_tpu.models.gpt import xla_causal_attention
    from dlrover_tpu.ops.flash_attention import flash_attention

    smoke = bool(os.getenv("BENCH_SMOKE"))
    reps = 3 if smoke else 10
    shapes = (
        [(1, 256, 4, 64)] if smoke
        else [(4, 2048, 12, 64), (1, 8192, 12, 64)]
    )

    def time_impl(fn, q, k, v):
        # reps chained inside one jit + scalar fetch: one dispatch
        # and one synchronization per timed window
        @jax.jit
        def fwd_bwd_loop(q, k, v):
            def scalar(q):
                return fn(q, k, v).astype(jnp.float32).sum()

            def body(_, carry):
                val, g = jax.value_and_grad(scalar)(carry)
                # fold the grad back in so iterations depend on each
                # other and cannot be collapsed
                return carry + 1e-6 * g.astype(carry.dtype)

            q = jax.lax.fori_loop(0, reps, body, q)
            return q.astype(jnp.float32).sum()

        float(fwd_bwd_loop(q, k, v))  # compile + warm

        def sample():
            t0 = time.perf_counter()
            float(fwd_bwd_loop(q, k, v))
            return (time.perf_counter() - t0) / reps

        return _best_of(3, sample)

    out = {}
    for b, s, h, d in shapes:
        q = jax.random.normal(
            jax.random.PRNGKey(1), (b, s, h, d), jnp.bfloat16
        )
        k = jax.random.normal(
            jax.random.PRNGKey(2), (b, s, h, d), jnp.bfloat16
        )
        v = jax.random.normal(
            jax.random.PRNGKey(3), (b, s, h, d), jnp.bfloat16
        )
        t_flash = time_impl(flash_attention, q, k, v)
        t_xla = time_impl(xla_causal_attention, q, k, v)
        out[f"seq{s}"] = {
            "shape": [b, s, h, d],
            "flash_fwd_bwd_s": round(t_flash, 5),
            "xla_fwd_bwd_s": round(t_xla, 5),
            "flash_vs_xla_speedup": round(
                t_xla / max(t_flash, 1e-9), 3
            ),
        }
    results["attention_kernel"] = out


AGENT_SCRIPT = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
from dlrover_tpu.checkpoint.saver import AsyncCheckpointSaver
AsyncCheckpointSaver.start_async_saving_ckpt()
print("agent-ready", flush=True)
while True:
    time.sleep(0.5)
"""


def bench_flash_ckpt(jax, results: dict, workdir: str):
    """Flash-ckpt stall vs sync save; saver in a separate process."""
    import jax.numpy as jnp
    import optax

    from dlrover_tpu.checkpoint.engine import CheckpointEngine
    from dlrover_tpu.common.constants import CheckpointConstant
    from dlrover_tpu.models.gpt import GPT, GPTConfig, count_params
    from dlrover_tpu.trainer.elastic_trainer import TrainState

    # a 2-layer 512-wide GPT slice + adam: ~32M params x3 states
    # ~0.39 GB fp32 pytree.  Sized small on purpose (a state of
    # real size — GBs — is what chip_smoke.py's elastic phase
    # saves); the stall-vs-sync
    # RATIO — the reference's headline (flash_checkpoint.md:361-383)
    # — is size-independent, and state_mb is reported alongside
    cfg = (
        GPTConfig.tiny()
        if os.getenv("BENCH_SMOKE")
        else GPTConfig(
            num_layers=2, num_heads=8, hidden_dim=512,
            max_seq_len=512,
        )
    )
    model = GPT(cfg)
    params = model.init_params(
        jax.random.PRNGKey(0), seq_len=min(512, cfg.max_seq_len)
    )
    state = TrainState.create(params, optax.adam(1e-4))
    jax.block_until_ready(state.params)
    state_dict = {
        "params": state.params,
        "opt_state": state.opt_state,
        "step": 100,
    }
    state_bytes = sum(
        l.size * l.dtype.itemsize
        for l in jax.tree_util.tree_leaves(state_dict)
        if hasattr(l, "dtype")
    )

    # -- synchronous save: the path flash ckpt replaces.  HONEST
    # baseline (VERDICT r2): the device->host transfer is paid inside
    # the timed region on FRESH arrays — a real sync save always pays
    # it (round 2 warmed jax's host cache first, hiding ~90% of the
    # cost and making the async path look pathologically slow against
    # a fake 10s number).  Measured TWICE — before and after the
    # flash saves — and averaged: host transfer bandwidth drifts,
    # and a single sample makes the
    # snapshot-vs-sync ratio a coin flip.
    # fresh per-attempt dirs: run_section retries this function, and
    # a stale tracker from a failed attempt would make the
    # persist-commit wait a no-op (falsifying persist_e2e)
    attempt_dir = tempfile.mkdtemp(prefix="attempt_", dir=workdir)
    sync_dir = os.path.join(attempt_dir, "sync")
    os.makedirs(sync_dir, exist_ok=True)

    def sync_save():
        fresh = jax.jit(
            lambda t: jax.tree.map(lambda x: x + 0, t)
        )(state_dict)
        float(jax.tree_util.tree_leaves(fresh)[0].ravel()[0])
        t0 = time.perf_counter()
        host_state = jax.device_get(fresh)
        t_d2h = time.perf_counter() - t0
        with open(os.path.join(sync_dir, "ckpt.pkl"), "wb") as f:
            pickle.dump(host_state, f)
        return time.perf_counter() - t0, t_d2h

    f_sync_pre, t_d2h = sync_save()

    # -- separate agent process hosting the async saver
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"  # the agent never touches the chip
    agent = subprocess.Popen(
        [sys.executable, "-c", AGENT_SCRIPT.format(repo=os.getcwd())],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=env, text=True, cwd=os.getcwd(),
    )
    line = agent.stdout.readline()
    assert "agent-ready" in line, f"agent failed to start: {line!r}"

    ckpt_dir = os.path.join(attempt_dir, "flash")
    engine = CheckpointEngine(
        ckpt_dir, replicated=True, local_rank=0, global_rank=0,
        world_size=1,
    )
    stalls = []
    snapshot_e2e = persist_e2e = -1.0
    try:
        # warm up (jit of the on-device copy, shm allocation, saver
        # handshake) — pays one full snapshot
        assert engine.save(1, state_dict, persist=True)
        assert engine.wait_async(timeout=240.0)
        tracker = os.path.join(ckpt_dir, CheckpointConstant.TRACKER_FILE)

        def committed_step():
            if os.path.exists(tracker):
                with open(tracker) as f:
                    return int(f.read().strip() or -1)
            return -1

        # timed save: stall (training-thread block), snapshot e2e
        # (crash-restorable in shm), persist e2e (committed on disk)
        t0 = time.perf_counter()
        ok = engine.save(2, state_dict, persist=True)
        stalls.append(time.perf_counter() - t0)
        assert ok, "flash save of step 2 was skipped"
        assert engine.wait_async(timeout=240.0)
        assert engine._last_async_error is None
        snapshot_e2e = time.perf_counter() - t0
        deadline = time.time() + 240
        while time.time() < deadline and committed_step() < 2:
            time.sleep(0.5)
        persist_e2e = time.perf_counter() - t0
        committed = committed_step()

        f_flash = statistics.median(stalls)
        # restore FROM HOST SHM — the reference's recovery-side
        # baseline ("seconds-order restore from host shared memory",
        # flash_checkpoint.md:389-394): engine.load() takes the shm
        # snapshot path, what crash recovery actually pays.  The
        # disk tier (load_from_storage) is timed separately — it is
        # the cold-start path, not the recovery one.
        t0 = time.perf_counter()
        # the shm handler DIRECTLY — engine.load() silently falls
        # back to the disk tier on an shm error, which would mislabel
        # disk latency as the shm recovery number
        shm_config, _shm_state = engine.get_state_dict_from_memory()
        restore_shm_s = time.perf_counter() - t0
        restore_shm_phases = dict(engine.last_restore_phases)
        assert shm_config is not None and shm_config.step >= 2, (
            "shm snapshot unreadable - shm restore not measured"
        )
        t0 = time.perf_counter()
        step, restored = engine.load_from_storage()
        restore_disk_s = time.perf_counter() - t0
        restore_disk_phases = dict(engine.last_restore_phases)
        assert step == committed >= 2, (
            f"persisted step {step} != committed {committed}"
        )
    finally:
        engine.close()
        agent.kill()
        agent.wait()

    f_sync_post, _ = sync_save()
    f_sync = (f_sync_pre + f_sync_post) / 2
    d2h_mbps = state_bytes / 2**20 / max(t_d2h, 1e-9)
    # raw host memcpy bandwidth on THIS box, measured the moment the
    # restore ran: the shm restore's assemble stage copies each byte
    # exactly once, so assemble_s ~= bytes / this number means the
    # residual is the host's memory bandwidth (an irreducible term),
    # while assemble_s >> it means faults/contention are still in
    # play — the breakdown is provable either way (ISSUE 10)
    import numpy as _np

    _src = _np.ones(64 * 2**20, dtype=_np.uint8)
    _dst = _np.empty_like(_src)
    _dst[:] = _src  # warm both buffers
    t0 = time.perf_counter()
    _dst[:] = _src
    memcpy_mbps = 64.0 / max(time.perf_counter() - t0, 1e-9)
    del _src, _dst
    results["_speedup"] = f_sync / max(f_flash, 1e-9)
    results["flash_ckpt"] = {
        "sync_save_s": round(f_sync, 3),
        "sync_save_pre_post_s": [
            round(f_sync_pre, 3), round(f_sync_post, 3),
        ],
        "sync_d2h_s": round(t_d2h, 3),
        "d2h_MBps": round(d2h_mbps, 1),
        "flash_stall_s": round(f_flash, 4),
        "snapshot_e2e_s": round(snapshot_e2e, 3),
        "persist_e2e_s": round(persist_e2e, 3),
        "snapshot_vs_sync": round(snapshot_e2e / max(f_sync, 1e-9), 3),
        "restore_shm_s": round(restore_shm_s, 4),
        "restore_shm_MBps": round(
            state_bytes / 2**20 / max(restore_shm_s, 1e-9), 1
        ),
        # per-stage pipeline breakdown (read / assemble / h2d) of each
        # restore tier — the recovery-side twin of save_phases
        "restore_shm_phases": restore_shm_phases,
        "restore_disk_s": round(restore_disk_s, 4),
        "restore_disk_MBps": round(
            state_bytes / 2**20 / max(restore_disk_s, 1e-9), 1
        ),
        "restore_disk_phases": restore_disk_phases,
        "memcpy_baseline_MBps": round(memcpy_mbps, 1),
        "save_phases": dict(engine.last_save_phases),
        "state_mb": round(state_bytes / 2**20, 1),
        "num_params": count_params(params),
        "committed_step": committed,
        "saver": "separate-process agent",
    }
    return f_sync / max(f_flash, 1e-9)


# One elastic train script for the recovery bench AND the e2e tests
# (tests/test_e2e_elastic.py imports it) — a single source of truth
# for the crash/restore flow.  Every incarnation runs the
# RecoveryProfiler: restore overlaps the model/step build via
# load_checkpoint_async, the first step's trace+compile is bracketed
# as the retrace phase (compile-cache hit/miss witnessed from the
# cache dir), and the whole death->first-step budget lands as
# recovery_phase events the bench section parses.  argv: ckpt_dir
# crash_flag restored_flag crash_mode(exit|kill)
ELASTIC_TRAIN_SCRIPT = r'''
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer, TrainState, abstract_like, make_train_step,
    restore_train_state,
)
from dlrover_tpu.trainer.recovery import RecoveryProfiler

ckpt_dir, crash_flag, restored_flag, crash_mode = sys.argv[1:5]

prof = RecoveryProfiler()
# restore overlap: read/assemble run on a background thread while the
# model/optimizer/jitted step are built below
ckpt = Checkpointer(ckpt_dir)
load_handle = ckpt.load_checkpoint_async()

cfg = GPTConfig.tiny()
model = GPT(cfg)
optimizer = optax.adam(1e-3)

def loss_fn(p, batch):
    logits = model.apply({"params": p}, batch["x"])
    return cross_entropy_loss(logits, batch["y"])

step_fn = make_train_step(loss_fn, optimizer)
rng = np.random.default_rng(0)
data = rng.integers(0, cfg.vocab_size, (8, 17), dtype=np.int32)

# AOT executable cache, resolved while the restore read runs on its
# own thread: a warm incarnation resolves through the label index
# and deserializes the compiled step (no eval_shape, no trace); a
# cold one traces and writes the entry + index the replacement hits
batch = {"x": jnp.asarray(data[:, :-1]), "y": jnp.asarray(data[:, 1:])}

def _abstract_examples():
    abs_params = jax.eval_shape(
        model.init_params, jax.random.PRNGKey(0)
    )
    abs_state = jax.eval_shape(
        lambda p: TrainState.create(p, optimizer), abs_params
    )
    return abs_state, abstract_like(batch)

step = prof.resolve_step(
    step_fn, _abstract_examples,
    restore_busy=lambda: not load_handle.done(),
)

start_step, restored = load_handle.result()
prof.record_restore(ckpt.last_restore_phases)
if start_step is None:
    params = model.init_params(jax.random.PRNGKey(0))
    start_step = 0
    state = TrainState.create(params, optimizer)
else:
    # shaved state_build: batched device_put + deferred optimizer
    # init (the checkpoint supplies the optax slots)
    state = restore_train_state(optimizer, restored["state"])

trainer = ElasticTrainer(global_batch_size=8, micro_batch_size=8,
                         dp_size=1)
trainer.global_step = start_step

_first_step = True
for i in range(start_step, 5):
    with trainer.profile("h2d"):
        batch = {"x": jnp.asarray(data[:, :-1]),
                 "y": jnp.asarray(data[:, 1:])}
    with trainer.profile("compute") as _p:
        state, metrics = step(state, batch)
        if _first_step:
            _first_step = False
            jax.block_until_ready(metrics)
            prof.record_first_step()
        _p.block(metrics)
    trainer.report_step(metrics)
    ckpt.save_checkpoint(
        trainer.global_step,
        {"state": state, "trainer": trainer.state_dict()},
        storage_type=StorageType.MEMORY,
    )
    ckpt.wait()  # the crash below comes AFTER the commit to shm
    if start_step > 0 and not os.path.exists(restored_flag):
        open(restored_flag, "w").close()  # first step after restore
    if trainer.global_step == 3 and not os.path.exists(crash_flag):
        open(crash_flag, "w").close()
        if crash_mode == "kill":
            os.kill(os.getpid(), 9)  # hard kill AFTER the shm save
        sys.exit(17)  # simulated crash AFTER the shm save

ckpt.save_checkpoint(
    5, {"state": state, "trainer": trainer.state_dict()},
    storage_type=StorageType.DISK,
)
# wait for the agent-side async persist to commit before exiting
ckpt.wait()
tracker = os.path.join(ckpt_dir, "latest_checkpointed_iteration.txt")
deadline = time.time() + 60
while time.time() < deadline and not os.path.exists(tracker):
    time.sleep(0.2)
assert os.path.exists(tracker), "checkpoint commit did not land"
ckpt.close()
'''


# Churn-goodput train script: flash-ckpt every CKPT_EVERY steps,
# appends "ts step" progress lines, runs until killed.  argv:
# ckpt_dir progress_path
CHURN_TRAIN_SCRIPT = r'''
import os, sys, time
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp
import numpy as np
import optax

from dlrover_tpu.checkpoint.checkpointer import Checkpointer, StorageType
from dlrover_tpu.models.gpt import GPT, GPTConfig, cross_entropy_loss
from dlrover_tpu.trainer.elastic_trainer import (
    ElasticTrainer, TrainState, make_train_step,
)

ckpt_dir, progress_path = sys.argv[1:3]
CKPT_EVERY = 2
_t0 = time.time()
_prog = open(progress_path, "a")
def _mark(name):
    now = time.time()
    _prog.write(f"# {name} {now:.4f} {now - _t0:.2f}\n")
    _prog.flush()
_mark("boot")

cfg = GPTConfig.tiny(max_seq_len=128)
model = GPT(cfg)
optimizer = optax.adam(1e-3)

def loss_fn(p, batch):
    logits = model.apply({"params": p}, batch["x"])
    return cross_entropy_loss(logits, batch["y"])

step_fn = make_train_step(loss_fn, optimizer)
_mark("imports+model")
ckpt = Checkpointer(ckpt_dir)
_mark("checkpointer")
start_step, restored = ckpt.load_checkpoint()
_mark("restore")
if start_step is None:
    params = model.init_params(jax.random.PRNGKey(0))
    start_step = 0
    state = TrainState.create(params, optimizer)
else:
    from dlrover_tpu.trainer.elastic_trainer import restore_train_state
    state = restore_train_state(optimizer, restored["state"])

trainer = ElasticTrainer(global_batch_size=16, micro_batch_size=16,
                         dp_size=1)
trainer.global_step = start_step
rng = np.random.default_rng(0)
data = rng.integers(0, cfg.vocab_size, (16, 129), dtype=np.int32)
batch = {"x": jnp.asarray(data[:, :-1]), "y": jnp.asarray(data[:, 1:])}

progress = _prog
progress.write(f"pid {os.getpid()}\n")
progress.flush()
_first = True
for i in range(start_step, 10**9):
    # real per-step h2d under the always-on profiler (the built-in
    # loops previously profiled only data_wait/compute, so the h2d
    # phase of every step_phases event was structurally zero)
    with trainer.profile("h2d"):
        batch = {"x": jnp.asarray(data[:, :-1]),
                 "y": jnp.asarray(data[:, 1:])}
    with trainer.profile("compute") as _p:
        state, metrics = step_fn(state, batch)
        _p.block(metrics)
    float(metrics["loss"])  # complete the step before reporting it
    if _first:
        _mark("first_step")
        _first = False
    trainer.report_step(metrics)
    progress.write(f"{time.time()} {i + 1}\n")
    progress.flush()
    if (i + 1) % CKPT_EVERY == 0:
        with trainer.profile("checkpoint"):
            ckpt.save_checkpoint(
                i + 1,
                {"state": state,
                 "trainer": trainer.state_dict()},
                storage_type=StorageType.MEMORY,
            )
'''


def bench_serving(results: dict, workdir: str):
    """Serving plane (ISSUE 13): the train-to-serve loop's three
    headline numbers, measured in-process on host cores.

    1. **Delta economics** — full-table export stall (the PR 9 path)
       vs dirty-row delta export at the SAME table size after a ~2%
       training interval: the stall must scale with rows touched,
       not table size.
    2. **Freshness** — train-commit -> servable latency through the
       committed-generation protocol (publish + replica poll +
       digest-verified apply), per generation over a 10-delta chain.
    3. **Lookup p99 under concurrent ingest** — a reader thread
       hammering the replica's host-gather path while generations
       apply under the swap lock, vs the quiet baseline."""
    import numpy as np

    from dlrover_tpu.checkpoint.sparse import SparseStateAdapter
    from dlrover_tpu.ops.kv_variable import KvVariable
    from dlrover_tpu.serving import EmbeddingPublisher, ServingReplica

    smoke = bool(os.getenv("BENCH_SMOKE"))
    out: dict = {}
    results["serving"] = out
    rows = int(os.getenv(
        "BENCH_SERVING_ROWS", "8000" if smoke else "200000"
    ))
    dim = 32
    touch_frac = 0.02
    rng = np.random.default_rng(0)
    table = KvVariable(dim, initial_capacity=rows * 2, name="emb")
    table.enable_dirty_tracking()
    table.insert(
        np.arange(rows, dtype=np.int64),
        rng.normal(size=(rows, dim)).astype(np.float32),
    )
    adapter = SparseStateAdapter(digest=True).register_table(table)

    # (1) export stall: full table vs dirty rows at the same size
    t0 = time.perf_counter()
    adapter.export_state()
    full_s = time.perf_counter() - t0
    table.clear_dirty()
    touched = rng.choice(
        rows, size=max(1, int(rows * touch_frac)), replace=False
    ).astype(np.int64)
    table.scatter_add(
        touched,
        rng.normal(size=(len(touched), dim)).astype(np.float32),
    )
    t0 = time.perf_counter()
    delta = adapter.export_delta(clear=False)
    delta_s = time.perf_counter() - t0
    delta_rows = sum(
        len(sub["keys"]) for sub in delta.values()
        if isinstance(sub, dict) and "keys" in sub
    )
    out["table_rows"] = rows
    out["full_export_s"] = round(full_s, 4)
    out["delta_export_s"] = round(delta_s, 4)
    out["delta_rows"] = int(delta_rows)
    out["delta_ratio"] = round(delta_rows / rows, 4)
    out["export_stall_speedup"] = round(
        full_s / delta_s, 1
    ) if delta_s > 0 else None

    # (2+3) freshness + lookup tail under live ingest
    serving_dir = os.path.join(workdir, "serving_bench")
    pub = EmbeddingPublisher(
        adapter, serving_dir, compact_every=64
    )
    pub.publish(step=0)
    rep = ServingReplica(serving_dir)
    rep.ingest_pending()

    lookup_keys = [
        rng.integers(0, rows, 512).astype(np.int64)
        for _ in range(8)
    ]

    def _lookup_pass(samples, n):
        for i in range(n):
            t0 = time.perf_counter()
            rep.lookup(lookup_keys[i % len(lookup_keys)])
            samples.append(time.perf_counter() - t0)

    quiet: list = []
    _lookup_pass(quiet, 60 if smoke else 400)

    stop = threading.Event()
    busy: list = []

    def reader():
        while not stop.is_set():
            _lookup_pass(busy, 20)

    thread = threading.Thread(target=reader, daemon=True)
    thread.start()
    freshness: list = []
    n_gens = 4 if smoke else 10
    try:
        for g in range(1, n_gens + 1):
            touched = rng.choice(
                rows, size=max(1, int(rows * touch_frac)),
                replace=False,
            ).astype(np.int64)
            table.scatter_add(
                touched,
                rng.normal(
                    size=(len(touched), dim)
                ).astype(np.float32),
            )
            pub.publish(step=g)
            commit_t = time.time()
            # replica poll cadence is part of real freshness: poll at
            # the production default-ish 100 ms until the generation
            # lands
            deadline = time.time() + 30
            while (
                rep.generation < pub.generation
                and time.time() < deadline
            ):
                time.sleep(0.1)
                rep.ingest_pending()
            freshness.append(time.time() - commit_t)
    finally:
        stop.set()
        thread.join(timeout=10)

    def _pct(samples, q):
        return (
            round(float(np.percentile(np.asarray(samples), q)) * 1e3, 3)
            if samples else None
        )

    out["generations"] = n_gens
    out["freshness_mean_s"] = round(
        float(np.mean(freshness)), 4
    ) if freshness else None
    out["freshness_max_s"] = round(
        float(np.max(freshness)), 4
    ) if freshness else None
    out["lookup_p50_quiet_ms"] = _pct(quiet, 50)
    out["lookup_p99_quiet_ms"] = _pct(quiet, 99)
    out["lookup_p50_under_ingest_ms"] = _pct(busy, 50)
    out["lookup_p99_under_ingest_ms"] = _pct(busy, 99)
    out["lookup_batches_under_ingest"] = len(busy)


def bench_serving_fleet(results: dict, workdir: str):
    """Serving fleet (ISSUE 17): routed-lookup capacity of the
    replica pool behind the freshness-aware router, over the real
    framed-pickle transport on host cores.

    1. **QPS scaling** — routed throughput at pool size N=1/2/4 with
       a modeled per-batch device-gather floor on every replica
       (``--lookup-floor-ms``).  The router keeps ONE pooled
       connection per member (fail-fast, serialized roundtrips), so
       per-member routed throughput is floor-bound and fleet capacity
       must scale with the pool even on a host-core box where raw
       loopback RPC would not.
    2. **Zero-downtime re-base tail** — p99 while the publisher's
       compaction forces every replica through the drain-before-
       re-base protocol (serialized by the router's ``min_available``
       gate) vs the quiet p99 at the same pool size, plus the
       client-visible failure count, which must be zero."""
    import numpy as np

    from dlrover_tpu.checkpoint.sparse import SparseStateAdapter
    from dlrover_tpu.fleet.lookup_load import LookupLoadHarness
    from dlrover_tpu.ops.kv_variable import KvVariable
    from dlrover_tpu.serving import EmbeddingPublisher
    from dlrover_tpu.serving.pool import ReplicaPool
    from dlrover_tpu.serving.router import LookupRouter

    smoke = bool(os.getenv("BENCH_SMOKE"))
    out: dict = {}
    results["serving_fleet"] = out
    rows, dim = 4000, 16
    floor_ms = float(os.getenv("BENCH_FLEET_FLOOR_MS", "2.0"))
    measure_s = 2.0 if smoke else 4.0
    sizes = (1, 2) if smoke else (1, 2, 4)
    out["lookup_floor_ms"] = floor_ms
    out["rows"] = rows

    base = os.path.join(workdir, "serving_fleet")
    serving_dir = os.path.join(base, "pub")
    rng = np.random.default_rng(0)
    table = KvVariable(dim, initial_capacity=rows * 2, name="emb")
    table.enable_dirty_tracking()
    table.insert(
        np.arange(rows, dtype=np.int64),
        rng.normal(size=(rows, dim)).astype(np.float32),
    )
    adapter = SparseStateAdapter(digest=True).register_table(table)
    # small compact_every so the re-base phase's publishes hit a
    # compaction (full base reload -> the drain protocol) quickly
    pub = EmbeddingPublisher(adapter, serving_dir, compact_every=3)
    pub.publish(step=0)

    def _wait_admitted(router, n, timeout_s=30.0):
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            live = [
                m for m in router.table.members.values()
                if not m.removed and not m.draining
                and not m.suspect and m.generation >= 0
                and m.last_seen > 0.0
            ]
            if len(live) >= n:
                return
            time.sleep(0.05)
        raise TimeoutError(f"{n} replicas not admitted in time")

    qps_by_n: dict = {}
    quiet_p99 = None
    for n in sizes:
        router = LookupRouter(
            journal_dir=os.path.join(base, f"journal_n{n}"),
            heartbeat_timeout_s=3.0, stats_every_s=60.0,
            min_available=1,
        )
        pool = ReplicaPool(
            serving_dir, os.path.join(base, f"pool_n{n}"),
            router_addr=f"127.0.0.1:{router.port}", size=n,
            heartbeat_s=0.25, lookup_floor_ms=floor_ms,
            stats_every_s=60.0, max_respawns=0, router=router,
        )
        try:
            pool.wait_ports(timeout_s=60.0)
            _wait_admitted(router, n)
            load = LookupLoadHarness(
                f"127.0.0.1:{router.port}",
                streams=2 * n + 2, batch=128, key_space=rows,
                retries=4, seed=n,
            )
            s = load.run_for(measure_s)
            qps_by_n[n] = s["qps"]
            out[f"n{n}"] = {
                "qps": s["qps"], "p50_ms": s.get("p50_ms"),
                "p99_ms": s.get("p99_ms"), "failed": s["failed"],
                "lookups": s["lookups"], "streams": s["streams"],
            }
            if n == 2:
                quiet_p99 = s.get("p99_ms")
                # re-base under load: publish a delta chain through a
                # compaction; both replicas drain-and-reload one at a
                # time behind the router's min_available gate while
                # the streams keep hammering
                load2 = LookupLoadHarness(
                    f"127.0.0.1:{router.port}",
                    streams=2 * n + 2, batch=128, key_space=rows,
                    retries=4, seed=100 + n,
                )
                load2.start()
                n_gens = 4
                for g in range(1, n_gens + 1):
                    touched = rng.choice(
                        rows, size=256, replace=False
                    ).astype(np.int64)
                    table.scatter_add(
                        touched,
                        rng.normal(
                            size=(len(touched), dim)
                        ).astype(np.float32),
                    )
                    pub.publish(step=g)
                    time.sleep(0.4)
                # every member back at the newest generation = the
                # re-base cycle (drain -> reload -> re-admit) is done
                deadline = time.time() + 30
                while time.time() < deadline:
                    live = [
                        m for m in router.table.members.values()
                        if not m.removed
                    ]
                    if live and all(
                        m.generation >= pub.generation
                        and not m.draining for m in live
                    ):
                        break
                    time.sleep(0.1)
                load2.stop()
                s2 = load2.summary()
                reb = {
                    "qps": s2["qps"], "p50_ms": s2.get("p50_ms"),
                    "p99_ms": s2.get("p99_ms"),
                    "failed": s2["failed"],
                    "lookups": s2["lookups"],
                    "generations": n_gens,
                    "outcomes": s2["outcomes"],
                }
                if quiet_p99 and s2.get("p99_ms"):
                    reb["p99_over_quiet_x"] = round(
                        s2["p99_ms"] / quiet_p99, 2
                    )
                out["rebase"] = reb
        finally:
            pool.stop()
            router.stop()

    if 1 in qps_by_n and 2 in qps_by_n and qps_by_n[1]:
        out["scaling_1_to_2_x"] = round(
            qps_by_n[2] / qps_by_n[1], 2
        )
    if 2 in qps_by_n and 4 in qps_by_n and qps_by_n[2]:
        out["scaling_2_to_4_x"] = round(
            qps_by_n[4] / qps_by_n[2], 2
        )
    out["max_qps"] = max(qps_by_n.values()) if qps_by_n else None


def bench_sparse_scale(results: dict, workdir: str):
    """Streaming sparse state at scale (ISSUE 14): the bulk-data
    paths of a spill-backed table built ≥ 4x its DRAM budget (real
    rows live on the cold tier), all measured in-process:

    1. **Delta flash-checkpoint economics** — full export stall vs
       the checkpoint-consumer delta export after a ~1% training
       interval: the hot save path's stall must scale with rows
       touched, not table size.
    2. **Streaming reshard** — the 2-shard -> new-world windowed
       reshard's throughput (MB/s over the input bytes) and its peak
       extra RSS vs the one-shot path on the SAME shards: the
       windowed path must hold ~window-sized transients while the
       one-shot concatenate/dedup/select chain materializes the
       whole table severalfold."""
    import numpy as np

    from dlrover_tpu.checkpoint.sparse import (
        SparseStateAdapter,
        owner_of_keys,
    )
    from dlrover_tpu.common.env_utils import PeakRssSampler
    from dlrover_tpu.ops.kv_variable import KvVariable

    smoke = bool(os.getenv("BENCH_SMOKE"))
    out: dict = {}
    results["sparse_scale"] = out
    rows = int(os.getenv(
        "BENCH_SPARSE_SCALE_ROWS", "20000" if smoke else "150000"
    ))
    dim = int(os.getenv("BENCH_SPARSE_SCALE_DIM", "64"))
    row_bytes = dim * 4 + 16
    window_mb = float(os.getenv("BENCH_SPARSE_SCALE_WINDOW_MB", "2"))
    win_rows = max(1, int(window_mb * 2**20 / row_bytes))
    touch_frac = 0.01
    dram_budget = max(1024, rows // 4)  # table == 4x the budget
    scale_dir = os.path.join(workdir, "sparse_scale")
    os.makedirs(scale_dir, exist_ok=True)
    rng = np.random.default_rng(0)

    table = KvVariable(dim, initial_capacity=rows * 2, name="emb")
    table.enable_spill(
        os.path.join(scale_dir, "emb.spill"), dram_budget
    )
    # chunked fill so the spill passes run DURING construction (the
    # table never holds all rows in DRAM)
    for lo in range(0, rows, win_rows):
        hi = min(rows, lo + win_rows)
        table.insert(
            np.arange(lo, hi, dtype=np.int64),
            rng.normal(size=(hi - lo, dim)).astype(np.float32),
        )
    st = table.spill_stats()
    out["table_rows"] = rows
    out["table_mb"] = round(rows * row_bytes / 2**20, 1)
    out["spill_budget_mb"] = round(
        dram_budget * row_bytes / 2**20, 1
    )
    out["spill_over_budget_x"] = round(rows / dram_budget, 1)
    out["disk_rows"] = st["disk_rows"]

    # (1) delta flash-checkpoint stall vs full export at this size
    adapter = SparseStateAdapter(digest=False).register_table(table)
    adapter.enable_delta_checkpoints(full_every=8)
    t0 = time.perf_counter()
    base = adapter.export_for_checkpoint(step=1, durable=True)
    full_s = time.perf_counter() - t0
    del base
    touched = rng.choice(
        rows, size=max(1, int(rows * touch_frac)), replace=False
    ).astype(np.int64)
    table.scatter_add(
        touched,
        rng.normal(size=(len(touched), dim)).astype(np.float32),
    )
    t0 = time.perf_counter()
    delta = adapter.export_for_checkpoint(step=2, durable=True)
    delta_s = time.perf_counter() - t0
    delta_rows = sum(
        len(sub["keys"]) for sub in delta.values()
        if isinstance(sub, dict) and "keys" in sub
    )
    del delta
    out["full_export_s"] = round(full_s, 4)
    out["delta_export_s"] = round(delta_s, 4)
    out["delta_rows"] = int(delta_rows)
    out["delta_ratio"] = round(delta_rows / rows, 4)
    out["export_stall_speedup"] = round(
        full_s / delta_s, 1
    ) if delta_s > 0 else None

    # (2) streaming vs one-shot reshard on the same 2-shard split.
    # New world 16 so the destination subset stays small relative to
    # the window — the measured extra RSS is the TRANSIENT cost of
    # the path, not the inevitable destination table.
    keys_all, values_all, freq_all = table.export()
    own = owner_of_keys(keys_all, 2)
    shards = {}
    for r in range(2):
        m = own == r
        shards[r] = {"emb": {
            "keys": keys_all[m], "values": values_all[m],
            "freq": freq_all[m],
        }}
    input_mb = (
        keys_all.nbytes + values_all.nbytes + freq_all.nbytes
    ) / 2**20
    del keys_all, values_all, freq_all, own
    new_world = 16

    def fresh_target(tag):
        t = KvVariable(dim, name="emb")
        t.enable_spill(
            os.path.join(scale_dir, f"target_{tag}.spill"),
            dram_budget,
        )
        return t, SparseStateAdapter(digest=False).register_table(t)

    t_stream, a_stream = fresh_target("stream")
    with PeakRssSampler() as rss_stream:
        t0 = time.perf_counter()
        info = a_stream.import_shards_streaming(
            shards, world_size=new_world, rank=0,
            from_world=2, tier="bench", window_rows=win_rows,
        )
        stream_s = time.perf_counter() - t0
    t_oneshot, a_oneshot = fresh_target("oneshot")
    with PeakRssSampler() as rss_oneshot:
        a_oneshot.import_shards(
            shards, world_size=new_world, rank=0, from_world=2,
            tier="bench",
        )
    assert len(t_oneshot) == len(t_stream)  # same owned subset
    out["reshard_window_mb"] = round(window_mb, 2)
    out["reshard_chunks"] = int(info.get("kv_chunks", 0))
    out["reshard_streaming_s"] = round(stream_s, 4)
    out["reshard_MBps"] = round(
        input_mb / stream_s, 1
    ) if stream_s > 0 else None
    out["reshard_peak_extra_rss_mb"] = round(
        rss_stream.peak_extra_bytes / 2**20, 1
    )
    out["oneshot_peak_extra_rss_mb"] = round(
        rss_oneshot.peak_extra_bytes / 2**20, 1
    )
    if rss_stream.peak_extra_bytes > 0:
        out["rss_oneshot_over_streaming_x"] = round(
            rss_oneshot.peak_extra_bytes
            / rss_stream.peak_extra_bytes, 1
        )


def bench_fleet_control_plane(results: dict, workdir: str):
    """Fleet observatory: the first capacity number of the project.

    Hundreds of synthetic agents (subprocess packs driving REAL
    MasterClients through the production verb mix) against one
    journal-backed master, three legs:

    1. step-report piggybacking before/after at fixed load (the
       agent-side RPC coalescing fix the scoreboard motivated);
    2. the ``DLROVER_JOURNAL_FSYNC_WINDOW_S`` sweep under load —
       measured append p99 per window sizes the group-commit window
       (ROADMAP 1 carried-forward from the window's introduction);
    3. the SLO-green capacity search: max sustained agents with
       every windowed default-SLO rule green, per-verb p99 at that
       capacity.

    Runs on host cores; scheduled FIRST in the CPU-section thread so
    the capacity number is taken before the heavier churn/recovery
    sections pile on (device-section children may still overlap —
    the concurrency note in the results flags it)."""
    import dataclasses as _dc

    from dlrover_tpu.fleet import AgentProfile, FleetRunner
    from dlrover_tpu.fleet.runner import (
        INFORMED_FSYNC_WINDOW_S,
        sweep_fsync_window,
    )

    smoke = bool(os.getenv("BENCH_SMOKE"))
    out: dict = {}
    results["fleet_control_plane"] = out
    fleet_dir = os.path.join(workdir, "fleet")
    os.makedirs(fleet_dir, exist_ok=True)
    os.environ.setdefault(
        "DLROVER_EVENT_LOG",
        os.path.join(fleet_dir, "events.jsonl"),
    )
    profile = AgentProfile(
        heartbeat_interval=2.0,
        step_interval=1.0,
        shard_interval=4.0,
        kv_interval=8.0,
        reconnect_prob=0.002,
    )
    pack = 25 if smoke else 50
    hold_agents = 25 if smoke else 100
    # 8 s probe windows: a single storage-tail stall must not decide
    # a level's p99 off ~250 samples (measured: 6 s windows flip the
    # 200-agent level run to run, 8 s holds it green 2/3+)
    window_s = 2.0 if smoke else 8.0
    budget_s = float(os.getenv("BENCH_FLEET_BUDGET_S", "300"))
    t0 = time.time()

    def remaining() -> float:
        return budget_s - (time.time() - t0)

    # -- leg 1: piggyback before/after at fixed load ------------------
    for label, pgy in (("direct", False), ("piggyback", True)):
        runner = FleetRunner(
            max_nodes=512,
            profile=profile,
            workdir=os.path.join(fleet_dir, f"pgy_{label}"),
            fsync_window_s=INFORMED_FSYNC_WINDOW_S,
            piggyback=pgy,
            pack_size=pack,
        )
        try:
            level = runner._probe_level(
                hold_agents, window_s=window_s, settle_s=1.0
            )
            worst = level["worst_p99_ms"]
            out[f"{label}_rps"] = level["mean_rps"]
            out[f"{label}_green"] = level["green"]
            out[f"{label}_step_p99_ms"] = worst.get(
                "report.GlobalStepRecord", 0.0
            )
            out[f"{label}_heartbeat_p99_ms"] = worst.get(
                "get.HeartbeatRequest", 0.0
            )
        finally:
            runner.stop()
    if out.get("direct_rps"):
        # coalescing delivers the same fleet with FEWER control-plane
        # RPCs: the ratio is the fan-in relief
        out["piggyback_rpc_ratio"] = round(
            out.get("piggyback_rps", 0.0) / out["direct_rps"], 3
        )
    _emit(results, partial=True)

    # -- leg 2: journal fsync-window sweep under load ------------------
    if remaining() > 60 or smoke:
        sweep = sweep_fsync_window(
            windows=(0.0, 0.05) if smoke else (0.0, 0.01, 0.05, 0.25),
            agents=hold_agents,
            duration_s=window_s,
            profile=profile,
            max_nodes=256,
            pack_size=pack,
        )
        out["fsync_sweep"] = {
            f"w{w['window_s']:g}": {
                "append_p99_ms": w["append_p99_ms"],
                "lock_wait_p99_ms": w["lock_wait_p99_ms"],
            }
            for w in sweep["windows"]
        }
        out["fsync_chosen_window_s"] = sweep["chosen_window_s"]
        out["fsync_informed_default_s"] = (
            sweep["informed_default_s"]
        )
        _emit(results, partial=True)
    else:
        out["fsync_sweep_note"] = "skipped: fleet budget exhausted"

    # -- leg 3: SLO-green capacity search ------------------------------
    runner = FleetRunner(
        max_nodes=512,
        profile=profile,
        workdir=os.path.join(fleet_dir, "capacity"),
        fsync_window_s=INFORMED_FSYNC_WINDOW_S,
        piggyback=True,
        pack_size=pack,
    )
    try:
        cap = runner.capacity_search(
            start=25 if smoke else 100,
            step=25 if smoke else 50,
            max_agents=25 if smoke else int(
                os.getenv("BENCH_FLEET_MAX_AGENTS", "400")
            ),
            window_s=window_s,
            settle_s=2.0,
            deadline_s=max(30.0, remaining()),
        )
        out["max_sustained_agents"] = cap["max_sustained_agents"]
        out["rps_at_capacity"] = cap["rps_at_capacity"]
        out["p99_at_capacity_ms"] = {
            verb: p for verb, p in sorted(
                cap["p99_at_capacity_ms"].items(),
                key=lambda kv: -kv[1],
            )[:6]
        }
        out["first_breach"] = cap["first_breach"]
        out["levels"] = cap["levels"]
        out["search_s"] = cap["search_s"]
        out["agent_stats"] = runner.stats()["ops"]
        out["profile"] = _dc.asdict(profile)
    finally:
        runner.stop()
    _emit(results, partial=True)


def bench_goodput_churn(results: dict, workdir: str):
    """Goodput-% under sustained churn — the reference's headline
    metric (README.md:55-57 claims 69% -> 95% with fault tolerance +
    flash ckpt).  A real tpurun supervision tree trains while an
    external killer SIGKILLs the trainer every ~KILL_EVERY s; goodput
    compares distinct step completions against the churn-free step
    rate measured in a calibration window, and the SpeedMonitor's own
    gap accounting is replayed over the progress log as a
    cross-check."""
    import signal

    duration = float(os.getenv("BENCH_GOODPUT_S", "300"))
    kill_every = float(os.getenv("BENCH_GOODPUT_KILL_EVERY", "60"))
    churn_dir = os.path.join(workdir, "goodput")
    os.makedirs(churn_dir, exist_ok=True)
    script = os.path.join(churn_dir, "churn_train.py")
    with open(script, "w") as f:
        f.write(CHURN_TRAIN_SCRIPT)

    def launch(tag: str):
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            PYTHONPATH=os.getcwd(),
            DLROVER_SHARED_DIR=os.path.join(churn_dir, f"sock_{tag}"),
        )
        ckpt_dir = os.path.join(churn_dir, f"ckpt_{tag}")
        progress = os.path.join(churn_dir, f"progress_{tag}")
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "dlrover_tpu.run",
                "--nproc_per_node=1", "--max_restarts=100",
                "--monitor_interval=0.2", "--warm-restart",
                script, ckpt_dir, progress,
            ],
            env=env, cwd=os.getcwd(), stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL, start_new_session=True,
        )
        return _register_proc(proc), progress

    def read_progress(path):
        out = []
        if os.path.exists(path):
            with open(path) as f:
                for line in f:
                    if line.startswith("pid "):
                        continue
                    try:
                        ts, step = line.split()
                        out.append((float(ts), int(step)))
                    except ValueError:
                        continue
        return out

    def read_marks(path):
        """Worker lifecycle marks ``# name abs_ts rel_ts`` in file
        order — one boot/restore/first_step triple per incarnation."""
        out = []
        if os.path.exists(path):
            with open(path) as f:
                for line in f:
                    if not line.startswith("# "):
                        continue
                    parts = line.split()
                    if len(parts) >= 3:
                        try:
                            out.append((parts[1], float(parts[2])))
                        except ValueError:
                            continue
        return out

    def current_trainer_pid(path):
        pid = None
        if os.path.exists(path):
            with open(path) as f:
                for line in f:
                    if line.startswith("pid "):
                        try:
                            pid = int(line.split()[1])
                        except (ValueError, IndexError):
                            pass
        return pid

    def stop(proc):
        try:
            os.killpg(proc.pid, signal.SIGTERM)
        except ProcessLookupError:
            pass
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            _kill_job(proc)
            proc.wait()
        if proc in _LIVE_PROCS:
            _LIVE_PROCS.remove(proc)


    # -- calibration: churn-free step rate, measured from the first
    # completed step so agent startup/compile does not dilute it
    calib_s = min(45.0, duration / 4)
    proc, progress = launch("calib")
    deadline = time.time() + 120
    while time.time() < deadline and not read_progress(progress):
        time.sleep(0.5)
    time.sleep(calib_s)
    stop(proc)
    entries = read_progress(progress)
    assert len(entries) >= 10, (
        f"calibration produced {len(entries)} steps"
    )
    # steady-state rate: drop the first entries (jit compile)
    ts = [e[0] for e in entries]
    n_skip = min(5, len(entries) // 3)
    clean_rate = (len(entries) - 1 - n_skip) / (ts[-1] - ts[n_skip])

    # -- churn run
    proc, progress = launch("churn")
    t_start = time.time()
    kill_times = []
    next_kill = t_start + kill_every
    while time.time() - t_start < duration:
        time.sleep(1.0)
        if time.time() >= next_kill:
            pid = current_trainer_pid(progress)
            if pid is not None:
                try:
                    os.kill(pid, signal.SIGKILL)
                    kill_times.append(time.time())
                except ProcessLookupError:
                    pass
            next_kill += kill_every
    wall = time.time() - t_start
    stop(proc)
    kills = len(kill_times)

    entries = read_progress(progress)
    distinct = len({step for _, step in entries})
    goodput_vs_calib = 100.0 * distinct / max(1.0, wall * clean_rate)

    # headline goodput is SELF-calibrated: the churn run's own
    # steady-state step rate (median interval between consecutive
    # first-completion steps whose span contains no kill).  The
    # separate calibration run happens in a different host-load
    # window — on the real bench the churn run overlaps the
    # flash-ckpt section's 600MB host serialization, and measuring
    # churn loss against a cleaner window books that external drift
    # as churn loss (r4 first chip run: 88.2% vs-calibration while
    # the per-kill breakdown accounted for only ~2.6% of wall).
    first_seen = {}
    for ts_i, step in entries:
        if step not in first_seen:
            first_seen[step] = ts_i
    fc = sorted(first_seen.values())
    recov = 5.0
    intervals = [
        b - a
        for a, b in zip(fc, fc[1:])
        if b > a and not any(a < k + recov and k < b
                             for k in kill_times)
    ]
    if intervals:
        steady_rate = 1.0 / max(1e-9, statistics.median(intervals))
    else:
        steady_rate = clean_rate
    # the churn window opens at the FIRST completed step: the one-time
    # job boot (agent + template spin-up + first trace) is startup,
    # not churn loss — reported separately as boot_s.  Trailing dead
    # time after the last kill stays inside the window.
    t_end = t_start + wall
    boot_s = (fc[0] - t_start) if fc else 0.0
    churn_wall = max(1.0, t_end - (fc[0] if fc else t_start))
    goodput_raw = 100.0 * distinct / max(1.0, churn_wall * steady_rate)
    # >100% means sampling noise, not free work; clamp the headline
    goodput_pct = min(100.0, goodput_raw)

    # SpeedMonitor cross-check: replay first-completion step reports
    from dlrover_tpu.master.speed_monitor import SpeedMonitor

    mon = SpeedMonitor()
    mon._start_time = entries[0][0] if entries else t_start
    best = 0
    last_ts = mon._start_time
    for ts_i, step in entries:
        if step > best:
            best = step
            mon.collect_global_step(step, timestamp=ts_i)
            last_ts = ts_i
    sm_goodput = (
        mon._productive_seconds / max(1e-9, last_ts - mon._start_time)
    )

    # -- per-phase loss breakdown (VERDICT r3 #2): align each kill
    # with the next incarnation's lifecycle marks
    marks = read_marks(progress)
    step_time = 1.0 / max(steady_rate, 1e-9)
    cycles = []
    claimed_recoveries = set()
    aligned_kills = set()
    for k_ts in kill_times:
        boot = next(
            (t for n, t in marks if n == "boot" and t > k_ts), None
        )
        if boot is None:
            continue
        # marks from a LATER incarnation must not be attributed to
        # this kill: bound the search at the next boot
        next_boot = next(
            (t for n, t in marks if n == "boot" and t > boot),
            float("inf"),
        )
        restore = next(
            (t for n, t in marks
             if n == "restore" and boot <= t < next_boot),
            None,
        )
        first = next(
            (t for n, t in marks
             if n == "first_step" and boot <= t < next_boot),
            None,
        )
        best_before = max(
            (s for t, s in entries if t <= k_ts), default=0
        )
        new_step = next(
            (t for t, s in entries
             if t > k_ts and s > best_before), None
        )
        if restore is None or first is None or new_step is None:
            continue
        if new_step in claimed_recoveries:
            # two kills resolved to the same recovery (the second
            # landed mid-recovery); its loss is already inside the
            # first kill's cycle — mark it aligned with zero marginal
            # charge so the unaligned fallback cannot bill it again
            aligned_kills.add(k_ts)
            continue
        claimed_recoveries.add(new_step)
        aligned_kills.add(k_ts)
        cycles.append({
            "detect_respawn_s": round(boot - k_ts, 3),
            "restore_s": round(restore - boot, 3),
            "retrace_first_step_s": round(first - restore, 3),
            "refill_s": round(max(0.0, new_step - first), 3),
            "total_lost_s": round(
                max(0.0, new_step - k_ts - step_time), 3
            ),
        })
    breakdown = {}
    if cycles:
        for key in cycles[0]:
            vals = [c[key] for c in cycles]
            breakdown[key] = {
                "mean": round(sum(vals) / len(vals), 3),
                "max": round(max(vals), 3),
            }

    # HEADLINE: direct churn-loss accounting — goodput is the wall
    # fraction NOT lost to kill recovery (detect+respawn+restore+
    # retrace+refill per aligned cycle; kills with no aligned cycle
    # are charged the worst observed cycle, conservatively).  The
    # distinct-step ratio below is a cross-check: it also absorbs
    # EXTERNAL host-load stalls (on the real bench the churn window
    # overlaps XL cold compiles), which are not churn loss.
    lost_s = sum(c["total_lost_s"] for c in cycles)
    unaligned = [k for k in kill_times if k not in aligned_kills]
    if cycles and unaligned:
        # kills with no aligned cycle (missing marks, double-claimed
        # recovery, or window-truncated recovery) are charged the
        # smaller of the worst observed cycle and the time the
        # SPECIFIC kill could actually have cost inside the window —
        # charging by position would bill the wrong kills' windows
        # when a mid-run kill fails to align (ADVICE r4)
        worst = max(c["total_lost_s"] for c in cycles)
        lost_s += sum(
            min(worst, max(0.0, t_end - k)) for k in unaligned
        )
    if cycles:
        goodput_pct = max(0.0, min(
            100.0, 100.0 * (1.0 - lost_s / churn_wall)
        ))

    results["goodput"] = {
        "goodput_pct": round(goodput_pct, 1),
        "churn_lost_s": round(lost_s, 2),
        "goodput_step_ratio_pct": round(
            min(100.0, goodput_raw), 1
        ),
        "goodput_vs_calibration_pct": round(goodput_vs_calib, 1),
        "steady_steps_per_s": round(steady_rate, 2),
        "boot_s": round(boot_s, 2),
        "churn_wall_s": round(churn_wall, 1),
        "speed_monitor_goodput_pct": round(100 * sm_goodput, 1),
        "duration_s": round(wall, 1),
        "kill_every_s": kill_every,
        "kills_delivered": kills,
        "distinct_steps": distinct,
        "clean_steps_per_s": round(clean_rate, 2),
        # lost time per kill cycle is ~constant, so the loss fraction
        # scales with kill frequency: at 1 preempt/hour the measured
        # loss (100-g)% shrinks by kill_every/3600
        "extrapolated_goodput_at_1_per_hour_pct": round(
            100 - (100 - goodput_pct) * kill_every / 3600.0, 2
        ),
        # where each kill's lost time went: agent detection + warm
        # fork, shm restore, jit re-trace (compile-cache hit) to the
        # first step, then recomputing steps since the last ckpt
        "phase_breakdown": breakdown,
        "phase_cycles": cycles,
    }


def bench_elastic_recovery(results: dict, workdir: str):
    """Crash -> agent restart -> shm restore -> first new step, on the
    CPU mesh via the real tpurun supervision path (the north-star
    story: fast recovery is what goodput under churn is made of).

    Runs the PRODUCTION recovery posture — warm forks with the
    framework preloaded, the job-keyed persistent compile cache, the
    shm prefetch/pre-fault overlap and the overlapped breakpoint save
    — and reports the measured per-phase budget
    (spawn/import/restore/retrace/first_step) plus the compile-cache
    hit/miss per recovery cycle, parsed from the run's own
    recovery_phase/compile_cache events.  ``recovery_s`` stays the
    driver-comparable end-to-end number (crash-flag mtime to
    restored-flag mtime)."""
    from dlrover_tpu.agent.forkserver import TRAINER_PRELOAD

    recovery_dir = os.path.join(workdir, "recovery")
    os.makedirs(recovery_dir, exist_ok=True)
    script = os.path.join(recovery_dir, "train.py")
    with open(script, "w") as f:
        f.write(ELASTIC_TRAIN_SCRIPT)
    ckpt_dir = os.path.join(recovery_dir, "ckpt")
    crash_flag = os.path.join(recovery_dir, "crashed")
    restored_flag = os.path.join(recovery_dir, "restored")
    event_log = os.path.join(recovery_dir, "events.jsonl")
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.getcwd(),
        DLROVER_SHARED_DIR=os.path.join(recovery_dir, "sock"),
        DLROVER_EVENT_LOG=event_log,
        JAX_COMPILATION_CACHE_DIR=os.environ.get(
            "JAX_COMPILATION_CACHE_DIR"
        ) or os.path.join(recovery_dir, "jax_cache"),
        DLROVER_MONITOR_REPORT_INTERVAL="0.5",
        DLROVER_PRELOAD=TRAINER_PRELOAD,
        # AOT executable cache: the first incarnation writes the
        # serialized step executable, the template pre-loads it
        # before every fork, the replacement deserializes (no trace)
        DLROVER_AOT_PRETRACE="1",
    )
    proc = _register_proc(subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.run",
            "--nproc_per_node=1", "--max_restarts=2",
            "--monitor_interval=0.1", "--warm-restart",
            script, ckpt_dir, crash_flag, restored_flag, "kill",
        ],
        env=env, cwd=os.getcwd(), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, start_new_session=True,
    ))
    try:
        _, err = proc.communicate(timeout=600)
    except subprocess.TimeoutExpired:
        _kill_job(proc)
        raise
    finally:
        if proc in _LIVE_PROCS:
            _LIVE_PROCS.remove(proc)
    assert proc.returncode == 0, err[-1500:]
    assert os.path.exists(crash_flag) and os.path.exists(restored_flag)
    recovery_s = os.path.getmtime(restored_flag) - os.path.getmtime(
        crash_flag
    )
    out = {
        "recovery_s": round(recovery_s, 2),
        "flow": "SIGKILL -> warm fork + AOT executable deserialize "
        "(no retrace) + overlapped shm restore -> next step",
    }
    # per-cycle budget from the run's own telemetry (no jax import —
    # the timeline module is event-plumbing only)
    try:
        from dlrover_tpu.telemetry.events import read_events
        from dlrover_tpu.telemetry.timeline import recovery_budgets

        budgets = recovery_budgets(read_events(event_log))
        cycles = {
            f"restart{count}": {
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in phases.items()
            }
            for (_rank, count), phases in sorted(budgets.items())
            if count > 0
        }
        if cycles:
            out["cycles"] = cycles
            retraces = [
                c["retrace"] for c in cycles.values()
                if "retrace" in c
            ]
            if retraces:
                out["retrace_s"] = max(retraces)
            aots = [
                c["aot"] for c in cycles.values() if "aot" in c
            ]
            if aots:
                out["aot_s"] = max(aots)
            hits = [
                c.get("compile_cache_hit") for c in cycles.values()
                if "compile_cache_hit" in c
            ]
            if hits:
                out["cache_hits"] = sum(1 for h in hits if h)
                out["cache_misses"] = sum(1 for h in hits if not h)
            aot_hits = [
                c.get("aot_cache_hit") for c in cycles.values()
                if "aot_cache_hit" in c
            ]
            if aot_hits:
                out["aot_hits"] = sum(1 for h in aot_hits if h)
                out["aot_misses"] = sum(
                    1 for h in aot_hits if not h
                )
    except Exception as e:  # noqa: BLE001 - breakdown is best-effort
        out["phases_error"] = f"{type(e).__name__}: {e}"
    results["elastic_recovery"] = out


def bench_rl_elastic(results: dict, workdir: str):
    """Elastic RL plane (ISSUE 16), measured on the real chaos path:
    SIGKILL the PPO rollout worker mid-lease, let the master requeue
    the lease and the replacement restore the iteration-granular
    flash snapshot, and report (a) death -> first replayed PPO
    update committed (``rl_recovery_s``), (b) event-attributed
    goodput of the whole churned run (``rl_goodput_pct``), and (c)
    the steady-state iteration anatomy (rollout/score/gae/train
    seconds) from the run's own ``rl_iteration`` telemetry.  The
    scenario exits 0 only if every invariant held — including the
    loss trajectory matching an uninterrupted control bit-for-bit —
    so the numbers are from a PROVEN-correct recovery, not merely a
    surviving one."""
    rl_dir = os.path.join(workdir, "rl_elastic")
    os.makedirs(rl_dir, exist_ok=True)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.getcwd(),
    )
    proc = _register_proc(subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.chaos",
            "--scenario", "rl_rollout_worker_kill",
            "--workdir", rl_dir,
        ],
        env=env, cwd=os.getcwd(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
        start_new_session=True,
    ))
    try:
        cli_out, _ = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        _kill_job(proc)
        raise
    finally:
        if proc in _LIVE_PROCS:
            _LIVE_PROCS.remove(proc)
    assert proc.returncode == 0, cli_out[-1500:]
    # event-log post-mortem only — no jax in the bench process
    from dlrover_tpu.telemetry import timeline as flight
    from dlrover_tpu.telemetry.events import read_events

    # read_events streams lazily — materialize before the multiple
    # passes below
    events = list(
        read_events(os.path.join(rl_dir, "events.jsonl"))
    )
    kills = [
        e for e in events
        if e.get("type") == "chaos_inject"
        and e.get("action") == "kill"
    ]
    iters = [
        e for e in events if e.get("type") == "rl_iteration"
    ]
    out = {
        "flow": "SIGKILL mid-lease -> lease requeued + flash "
        "restore -> replayed PPO update, loss == control",
        "iterations": len(iters),
        "leases": sum(int(e.get("leases", 0)) for e in iters),
    }
    replays = [
        e["ts"] for e in iters if e.get("restart_count", 0) > 0
    ]
    if kills and replays:
        out["recovery_s"] = round(
            min(replays) - kills[0]["ts"], 2
        )
    # goodput from the iteration anatomy, NOT the dense-loop
    # attribution (whose step-cadence silence rule files rollout
    # phases under "lost"): useful = each iteration's phase seconds
    # counted ONCE per iteration index — a replayed iteration's
    # duplicate work and the restart dead time both land in the
    # wall-but-not-useful remainder
    def _total_s(e):
        return sum(
            float(e.get(f"{p}_s") or 0.0)
            for p in ("rollout", "score", "gae", "train")
        )

    if iters:
        useful = {}
        for e in iters:
            useful[e.get("iteration")] = _total_s(e)
        # iteration indexes emitted more than once = work redone
        # after the kill (the interrupted iteration's PPO replay)
        out["replayed_iterations"] = len(iters) - len(useful)
        wall = max(e["ts"] for e in iters) - min(
            e["ts"] - _total_s(e) for e in iters
        )
        if wall > 0:
            out["goodput_pct"] = round(
                min(100.0, 100.0 * sum(useful.values()) / wall), 1
            )
            out["lost_s"] = round(
                max(0.0, wall - sum(useful.values())), 2
            )
    # the flight recorder still proves the loss is ATTRIBUTED (the
    # scenario's GoodputLossAttributed invariant); surface its
    # bucket total as the cross-check
    tl = flight.assemble(events)
    attribution = flight.attribute_goodput_loss(tl)
    if attribution:
        out["attributed_lost_s"] = round(
            attribution.get("loss_s", 0.0), 2
        )
    steady = [
        e for e in iters if e.get("restart_count", 0) == 0
    ]
    if steady:
        for phase in ("rollout_s", "score_s", "gae_s", "train_s"):
            vals = [
                float(e[phase]) for e in steady
                if isinstance(e.get(phase), (int, float))
            ]
            if vals:
                out[f"iter_{phase}"] = round(
                    sum(vals) / len(vals), 3
                )
    results["rl_elastic"] = out


def bench_goodput_ledger(results: dict, workdir: str):
    """Goodput ledger (ISSUE 20), measured on the real chaos path:
    SIGKILL a worker mid-step, then assemble the ledger from the
    run's event logs and report how much of the wall clock the
    attribution NAMES — per-category seconds, the top loss cause,
    and the conservation residual.  The scenario exits 0 only if
    every invariant held, including ``GoodputConservation`` with the
    90% named floor, so ``attributed_pct`` is a proven number."""
    gl_dir = os.path.join(workdir, "goodput_ledger")
    os.makedirs(gl_dir, exist_ok=True)
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.getcwd(),
    )
    proc = _register_proc(subprocess.Popen(
        [
            sys.executable, "-m", "dlrover_tpu.chaos",
            "--scenario", "kill_worker_midstep",
            "--workdir", gl_dir,
        ],
        env=env, cwd=os.getcwd(), stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True,
        start_new_session=True,
    ))
    try:
        cli_out, _ = proc.communicate(timeout=420)
    except subprocess.TimeoutExpired:
        _kill_job(proc)
        raise
    finally:
        if proc in _LIVE_PROCS:
            _LIVE_PROCS.remove(proc)
    assert proc.returncode == 0, cli_out[-1500:]
    # event-log post-mortem only — no jax in the bench process
    from dlrover_tpu.telemetry import goodput as _goodput
    from dlrover_tpu.telemetry.events import read_events

    events = list(
        read_events(os.path.join(gl_dir, "events.jsonl"))
    )
    ledger = _goodput.build_ledger(events)
    summary = _goodput.to_dict(ledger)
    out = {
        "flow": "SIGKILL mid-step -> ledger from event logs; "
        "conservation + 90% named floor proven by the scenario",
        "attributed_pct": summary["attributed_pct"],
        "top_loss_cause": summary["top_loss_cause"],
        "goodput": summary["goodput"],
        "incarnations": summary["incarnations"],
        "wall_s": summary["wall_s"],
        "conservation_ok": not ledger.conservation_errors(),
        "totals_s": {
            cat: secs
            for cat, secs in summary["totals"].items() if secs > 0
        },
    }
    causes = summary["top_loss_causes"]
    if causes:
        out["top_loss_causes"] = {
            c["cause"]: c["seconds"] for c in causes
        }
    results["goodput_ledger"] = out


_EMIT_LOCK = threading.Lock()


def _snapshot_blob(results: dict) -> str:
    """JSON snapshot of a dict other threads mutate lock-free:
    bounded retry on the dict-iteration race, '{}' if it never
    settles or holds something unserializable."""
    for _ in range(10):
        try:
            return json.dumps(dict(results))
        except RuntimeError:
            time.sleep(0.01)
        except (TypeError, ValueError):
            break
    return "{}"


def _dig(d: dict, *path):
    for p in path:
        if not isinstance(d, dict) or p not in d:
            return None
        d = d[p]
    return d


def _headline(snapshot: dict) -> dict:
    """Headline-only scalars.  The driver keeps a 2000-byte stdout
    tail and parses the LAST JSON line it finds there — three rounds
    of chip numbers died to oversized final lines (VERDICT r4 #1), so
    this detail dict must stay well under 1500 bytes total."""
    h = {}

    def put(key, val):
        if val is not None:
            h[key] = val

    put("goodput_pct", _dig(snapshot, "goodput", "goodput_pct"))
    put("goodput_kills", _dig(snapshot, "goodput", "kills_delivered"))
    put(
        "goodput_lost_s", _dig(snapshot, "goodput", "churn_lost_s")
    )
    put(
        "goodput_worst_cycle_s",
        _dig(
            snapshot, "goodput", "phase_breakdown", "total_lost_s",
            "max",
        ),
    )
    # goodput ledger: how much of the churned run's wall clock the
    # causal attribution NAMES, and the dominant loss cause
    put(
        "goodput_attributed_pct",
        _dig(snapshot, "goodput_ledger", "attributed_pct"),
    )
    put(
        "goodput_top_loss_cause",
        _dig(snapshot, "goodput_ledger", "top_loss_cause"),
    )
    put(
        "llama_mfu_2048",
        _dig(snapshot, "llama_train_step", "seq2048", "mfu"),
    )
    put(
        "llama_mfu_4096",
        _dig(snapshot, "llama_train_step", "seq4096", "mfu"),
    )
    put(
        "gpt2s_mfu",
        _dig(snapshot, "train_step", "flash_attention", "mfu"),
    )
    put("xl_mfu", _dig(snapshot, "xl_train_step", "mfu"))
    # fleet control plane: the max-sustained-agents headline + the
    # worst verb p99 at that capacity + the sweep-chosen journal
    # group-commit window
    put(
        "fleet_max_agents",
        _dig(snapshot, "fleet_control_plane",
             "max_sustained_agents"),
    )
    cap_p99 = _dig(
        snapshot, "fleet_control_plane", "p99_at_capacity_ms"
    )
    if isinstance(cap_p99, dict) and cap_p99:
        put(
            "fleet_worst_p99_ms",
            round(max(cap_p99.values()), 1),
        )
    put(
        "fleet_rps",
        _dig(snapshot, "fleet_control_plane", "rps_at_capacity"),
    )
    put(
        "fleet_fsync_window_s",
        _dig(snapshot, "fleet_control_plane",
             "fsync_chosen_window_s"),
    )
    ratio = _dig(
        snapshot, "fleet_control_plane", "piggyback_rpc_ratio"
    )
    put("fleet_piggyback_rpc_ratio", ratio)
    # serving plane: train-commit -> servable latency, lookup tail
    # under live ingest, and the delta economics that bound the
    # export stall by rows-touched instead of table size
    put(
        "serving_freshness_s",
        _dig(snapshot, "serving", "freshness_mean_s"),
    )
    put(
        "serving_lookup_p99_ms",
        _dig(snapshot, "serving", "lookup_p99_under_ingest_ms"),
    )
    put("delta_ratio", _dig(snapshot, "serving", "delta_ratio"))
    # serving fleet: routed capacity at the largest pool, the 1->2
    # replica scaling factor, and the routed p99 while the pool
    # cycles through a drained re-base under load (ISSUE 17)
    put(
        "serving_fleet_qps",
        _dig(snapshot, "serving_fleet", "max_qps"),
    )
    put(
        "serving_route_p99_ms",
        _dig(snapshot, "serving_fleet", "rebase", "p99_ms"),
    )
    # streaming sparse state at scale: reshard throughput, the
    # windowed-vs-one-shot RSS ratio, and the delta-checkpoint stall
    # win at a table 4x its spill DRAM budget
    put(
        "kv_reshard_MBps",
        _dig(snapshot, "sparse_scale", "reshard_MBps"),
    )
    put(
        "kv_reshard_rss_x",
        _dig(snapshot, "sparse_scale", "rss_oneshot_over_streaming_x"),
    )
    put(
        "kv_delta_ckpt_x",
        _dig(snapshot, "sparse_scale", "export_stall_speedup"),
    )
    put("flash_ckpt_stall_s", _dig(snapshot, "flash_ckpt", "flash_stall_s"))
    put(
        "flash_ckpt_restore_s",
        _dig(snapshot, "flash_ckpt", "restore_shm_s"),
    )
    speedup = snapshot.get("_speedup")
    put(
        "flash_ckpt_speedup_x",
        round(speedup, 1) if speedup else None,
    )
    sv = _dig(snapshot, "auto_config", "searched_vs_hand")
    put(
        "auto_config_delta_pct",
        round(100.0 * (sv - 1.0), 1) if sv else None,
    )
    put(
        "sparse_steps_per_s",
        _dig(
            snapshot, "sparse_kv", "deepfm_e2e", "pipelined",
            "steps_per_s",
        ),
    )
    put(
        "sparse_pipeline_speedup",
        _dig(snapshot, "sparse_kv", "deepfm_e2e", "pipeline_speedup"),
    )
    put(
        "host_gather_Mps",
        _dig(snapshot, "sparse_kv", "host_gather_Mlookups_per_s"),
    )
    put(
        "input_bound_pct",
        _dig(snapshot, "input_pipeline", "input_bound_pct"),
    )
    put(
        "gqa_speedup_2048",
        _dig(snapshot, "gqa_attention_kernel", "seq2048", "speedup"),
    )
    put(
        "flash_speedup_8192",
        _dig(
            snapshot, "attention_kernel", "seq8192",
            "flash_vs_xla_speedup",
        ),
    )
    put(
        "elastic_recovery_s",
        _dig(snapshot, "elastic_recovery", "recovery_s"),
    )
    # invisible-recovery breakdown: the measured death->first-step
    # budget of the first recovery cycle, the retrace term and the
    # compile-cache witness — the numbers that make the residual
    # provable instead of guessed (ISSUE 10).  Flattened to compact
    # STRINGS: the headline contract is scalars-only (VERDICT r5 #10,
    # pinned by test_bench_guard)
    cycle = _dig(snapshot, "elastic_recovery", "cycles", "restart1")
    if isinstance(cycle, dict):
        h["recovery_phases"] = " ".join(
            f"{p}={cycle[p]:.2f}"
            for p in ("spawn", "import", "restore", "aot",
                      "retrace", "first_step")
            if isinstance(cycle.get(p), (int, float))
        )
    put("retrace_s", _dig(snapshot, "elastic_recovery", "retrace_s"))
    put("aot_s", _dig(snapshot, "elastic_recovery", "aot_s"))
    # RL plane: death -> first replayed PPO update on the proven
    # scenario, plus its event-attributed goodput (ISSUE 16)
    put("rl_recovery_s", _dig(snapshot, "rl_elastic", "recovery_s"))
    put("rl_goodput_pct", _dig(snapshot, "rl_elastic", "goodput_pct"))
    # XL activation offload: throughput with the offload policy and
    # its ratio over the plain-remat control (ROADMAP 5(b) debt —
    # the legs measured tokens/s but never surfaced a headline)
    off_tok = _dig(snapshot, "xl_act_offload", "offload", "tokens_per_s")
    put("xl_offload_tok_s", off_tok)
    ctl_tok = _dig(
        snapshot, "xl_act_offload", "plain_remat_control",
        "tokens_per_s",
    )
    if off_tok and ctl_tok:
        put("xl_offload_vs_remat_x", round(off_tok / ctl_tok, 2))
    hits = _dig(snapshot, "elastic_recovery", "cache_hits")
    misses = _dig(snapshot, "elastic_recovery", "cache_misses")
    if hits is not None or misses is not None:
        h["compile_cache"] = f"{hits or 0}h/{misses or 0}m"
    ahits = _dig(snapshot, "elastic_recovery", "aot_hits")
    amisses = _dig(snapshot, "elastic_recovery", "aot_misses")
    if ahits is not None or amisses is not None:
        h["aot_cache"] = f"{ahits or 0}h/{amisses or 0}m"
    shm_phases = _dig(snapshot, "flash_ckpt", "restore_shm_phases")
    if isinstance(shm_phases, dict):
        h["flash_restore_phases"] = " ".join(
            f"{k[:-2]}={shm_phases[k]:.2f}"
            for k in ("read_s", "assemble_s", "h2d_s")
            if isinstance(shm_phases.get(k), (int, float))
        )
    put(
        "restore_memcpy_MBps",
        _dig(snapshot, "flash_ckpt", "memcpy_baseline_MBps"),
    )
    put(
        "kv_export_s",
        _dig(snapshot, "sparse_kv", "kv_checkpoint", "export_s"),
    )
    put(
        "kv_restore_s",
        _dig(snapshot, "sparse_kv", "kv_checkpoint", "restore_s"),
    )
    errors = sorted(
        k[: -len("_error")] for k in snapshot if k.endswith("_error")
    )
    if errors:
        # byte diet: an everything-errored run must not spend the
        # whole budget enumerating section names — the stderr detail
        # line carries the full list and the messages.  The cap is
        # display-only; the skipped/partial dedup below still keys on
        # the FULL error set
        if len(errors) > 7:
            h["errors"] = errors[:7] + [
                f"+{len(errors) - 7} more"
            ]
        else:
            h["errors"] = errors
    notes = sorted(
        k[: -len("_note")]
        for k in snapshot
        if k.endswith("_note")
        and ("skipped" in str(snapshot[k])
             or "killed" in str(snapshot[k]))
        # a section that emitted a partial result is reported under
        # partial_sections, not written off as skipped — and an
        # errored section is already flagged under errors (the same
        # redundancy-byte rule partial_sections applies)
        and k[: -len("_note")] not in errors
        and not (
            isinstance(snapshot.get(k[: -len("_note")]), dict)
            and snapshot[k[: -len("_note")]].get("partial")
        )
    )
    if notes:
        h["skipped"] = notes
    partials = sorted(
        name for name, val in snapshot.items()
        if isinstance(val, dict) and val.get("partial")
        # an errored section is already flagged under errors —
        # repeating it here spent headline bytes on redundancy
        and name not in errors
    )
    if partials:
        h["partial_sections"] = partials
    # byte diet: three significant digits is more precision than any
    # consumer of this line uses, and the raw floats (often 6+
    # decimals from time.perf_counter math) were the single biggest
    # contributor to the 1500-byte budget as sections accumulated
    for key, val in h.items():
        if isinstance(val, float) and val and math.isfinite(val):
            digits = 2 - math.floor(math.log10(abs(val)))
            val = round(val, max(0, digits))
            if val == int(val):
                val = int(val)
            h[key] = val
    return h


def _emit(results: dict, partial: bool = False):
    """Two JSON lines per call: the full cumulative detail on STDERR
    (for humans and the repo log), then a compact headline-only line
    on STDOUT guaranteed to fit the driver's 2000-byte tail.  Called
    after EVERY section (VERDICT r3 #1 + r4 #1): the driver records
    the LAST parseable stdout JSON line, so a kill at any point
    leaves the newest compact metrics in the tail.  Stdout NEVER
    carries the multi-KB detail line — a kill landing mid-detail
    would leave the tail holding the unparseable middle of it, the
    exact r4 failure.

    Concurrency: the CPU-section thread inserts keys while this runs
    — snapshot with a bounded retry (each section writes whole keys
    atomically, so a clean copy is a consistent view) and serialize
    the print so two emitters cannot interleave one line."""
    with _EMIT_LOCK:
        snapshot = json.loads(_snapshot_blob(results))
        speedup = float(snapshot.get("_speedup", 0.0))
        detail = {k: v for k, v in snapshot.items() if k != "_speedup"}
        if partial:
            detail["partial"] = True
        head = {
            "metric": "flash_ckpt_stall_speedup_vs_sync_save",
            "value": round(speedup, 2),
            "unit": "x",
            # reference claims ~10x vs sync NVMe save
            "vs_baseline": round(speedup / 10.0, 3),
        }
        print(
            json.dumps({**head, "detail": detail}),
            file=sys.stderr, flush=True,
        )
        compact = dict(head)
        compact["detail"] = _headline(snapshot)
        if partial:
            compact["detail"]["partial"] = True
        line = json.dumps(compact)
        while len(line) > 1500 and compact["detail"]:
            # hard guarantee: drop the bulkiest entry until it fits
            bulkiest = max(
                compact["detail"],
                key=lambda k: len(json.dumps(compact["detail"][k])),
            )
            del compact["detail"][bulkiest]
            line = json.dumps(compact)
        print(line, flush=True)


def _enable_compile_cache(jax):
    """Persistent XLA compile cache, where the job keeps it
    (``JAX_COMPILATION_CACHE_DIR``, else the fixed in-checkout
    directory): the auto-config section recompiles near-identical HLO
    per candidate, and warm restarts/replays across rounds reuse it."""
    from dlrover_tpu.common.compile_cache import (
        enable_persistent_cache,
    )

    enable_persistent_cache()


# device sections run in CHILD PROCESSES (VERDICT r4 #3): a section
# that blows its budget is SIGKILLed — the kill releases the device
# it holds, so it cannot contend with later sections' timings the
# way r4's abandoned threads did.  The parent never opens the device.
DEVICE_SECTIONS = {
    "train_step": bench_train_step,
    "llama_train_step": bench_llama_train_step,
    "auto_config": bench_auto_config,
    "attention_kernel": bench_attention_kernel,
    "gqa_attention_kernel": bench_gqa_attention_kernel,
    "sparse_kv": bench_sparse_kv,
    "input_pipeline": bench_input_pipeline,
    "xl_train_step": bench_xl_train_step,
    "xl_act_offload": bench_xl_act_offload,
}


def _dump_state(results: dict, state_path: str) -> None:
    """Atomic snapshot -> state_path.out."""
    blob = _snapshot_blob(results)
    if blob == "{}" and results:
        return  # never clobber a good out-file with an empty one
    tmp = state_path + ".out.tmp"
    with open(tmp, "w") as f:
        f.write(blob)
    os.replace(tmp, state_path + ".out")


def _child_main(name: str, state_path: str, workdir: str) -> int:
    """One device section in its own process: read the cumulative
    results, run, write them back atomically.  stdout/stderr go to
    the parent's per-section log, never to the JSON stdout stream.
    A background thread re-dumps the state every 2s so a budget
    SIGKILL (or a mid-section crash) still leaves every completed
    sub-measurement for the parent to merge — os.replace keeps the
    out-file a consistent snapshot at all times."""
    global _CHILD_T0
    t0 = time.time()
    _CHILD_T0 = t0
    import jax

    _enable_compile_cache(jax)
    with open(state_path) as f:
        results = json.load(f)
    results["platform"] = jax.devices()[0].platform
    results.setdefault("child_init_s", {})[name] = round(
        time.time() - t0, 1
    )

    def dumper():
        while True:
            time.sleep(2.0)
            try:
                _dump_state(results, state_path)
            except OSError:
                pass

    threading.Thread(target=dumper, daemon=True).start()
    try:
        if name == "flash_ckpt":
            bench_flash_ckpt(jax, results, workdir)
        else:
            DEVICE_SECTIONS[name](jax, results)
    finally:
        _dump_state(results, state_path)
    return 0


def main() -> int:
    t_process_start = time.time()
    workdir = tempfile.mkdtemp(prefix="dlrover_bench_")
    os.environ.setdefault(
        "DLROVER_SHARED_DIR", os.path.join(workdir, "sockets")
    )
    os.environ["BENCH_WORKDIR"] = workdir
    results = {}
    smoke = bool(os.getenv("BENCH_SMOKE"))

    # total budget NEAR the driver kill window (r3 died at ~19 min
    # with zero emissions; r2 survived at ~16; r4 completed at ~19.5
    # with rc=0).  A mid-run kill is now harmless — the compact
    # headline line streams after EVERY section, so the stdout tail
    # always parses — which lets the deadline sit closer to the
    # window than the r3-era all-or-nothing run could afford.
    # Sections get individual budgets; whatever does not fit is
    # skipped with a note.
    deadline_s = float(os.getenv("BENCH_DEADLINE_S", "1130"))
    # count from PROCESS start; jax backend init happens inside each
    # section child and is reported per-child in child_init_s (it is
    # part of every section_wall_s entry — budget-tuners beware)
    t_start = t_process_start
    results["section_wall_s"] = {}

    def remaining() -> float:
        return deadline_s - (time.time() - t_start)

    done_evt = threading.Event()

    def watchdog():
        # last resort: a hung device transfer inside a section thread
        # must not keep the process alive past the driver's patience
        if done_evt.wait(deadline_s + 60):
            return
        results["watchdog"] = (
            f"bench exceeded {deadline_s + 60:.0f}s; emitting "
            "partial results (a device transfer likely hung)"
        )
        _kill_live_procs()
        _emit(results, partial=True)
        # exit 0 deliberately: an rc-gating harness that discards
        # output on failure would lose the partial results; the
        # "watchdog" key marks the run as abnormal for any consumer
        # that reads the JSON
        os._exit(0)

    threading.Thread(target=watchdog, daemon=True).start()

    # CPU-only sections (subprocesses on the virtual CPU backend) run
    # in the background CONCURRENTLY with the device sections: they
    # share no chip time, but they do contend for host cores, which
    # is the bench's documented dispatch-noise source — so they start
    # only after the small-MFU headline section has finished clean,
    # and the overlap is flagged in the emitted detail
    def cpu_sections():
        # fleet first: the capacity search is the most
        # contention-sensitive CPU measurement — take it before the
        # churn/recovery supervision trees pile onto the host cores
        try:
            bench_fleet_control_plane(results, workdir)
        except Exception as e:  # noqa: BLE001
            results["fleet_error"] = f"{type(e).__name__}: {e}"
        # serving is cheap (seconds) and pure-host: take it before
        # the churn/recovery supervision trees add scheduler noise to
        # the freshness / lookup-tail numbers
        try:
            bench_serving(results, workdir)
            _emit(results, partial=True)
        except Exception as e:  # noqa: BLE001
            results["serving_error"] = f"{type(e).__name__}: {e}"
        # serving fleet: real router + replica subprocesses under
        # synthetic routed load — tens of seconds, pure-host
        try:
            bench_serving_fleet(results, workdir)
            _emit(results, partial=True)
        except Exception as e:  # noqa: BLE001
            results["serving_fleet_error"] = (
                f"{type(e).__name__}: {e}"
            )
        # sparse scale: pure-host numpy + native table work, tens of
        # seconds — the streaming-reshard and delta-checkpoint
        # headline numbers at a table ≥ 4x the spill DRAM budget
        try:
            bench_sparse_scale(results, workdir)
            _emit(results, partial=True)
        except Exception as e:  # noqa: BLE001
            results["sparse_scale_error"] = f"{type(e).__name__}: {e}"
        try:
            bench_elastic_recovery(results, workdir)
        except Exception as e:  # noqa: BLE001
            results["elastic_recovery_error"] = (
                f"{type(e).__name__}: {e}"
            )
        if not smoke:
            # RL plane: the full proven-recovery scenario (incl. the
            # uninterrupted control) costs a couple of minutes —
            # churn-class, so smoke skips it with goodput
            try:
                bench_rl_elastic(results, workdir)
                _emit(results, partial=True)
            except Exception as e:  # noqa: BLE001
                results["rl_elastic_error"] = (
                    f"{type(e).__name__}: {e}"
                )
            try:
                bench_goodput_churn(results, workdir)
            except Exception as e:  # noqa: BLE001
                results["goodput_error"] = f"{type(e).__name__}: {e}"
            # goodput ledger: one proven worker-kill cycle + the
            # event-log post-mortem — churn-class, so smoke skips it
            try:
                bench_goodput_ledger(results, workdir)
                _emit(results, partial=True)
            except Exception as e:  # noqa: BLE001
                results["goodput_ledger_error"] = (
                    f"{type(e).__name__}: {e}"
                )

    cpu_thread = threading.Thread(target=cpu_sections, daemon=True)
    state_path = os.path.join(workdir, "state.json")
    this_file = os.path.abspath(__file__)

    def run_section(name: str, budget_s: float) -> None:
        """One section in a CHILD PROCESS: a hung device call gets
        the child SIGKILLed at its budget, which also tears down its
        in-flight device work — later sections measure clean.  One
        retry on a nonzero exit inside the same budget."""
        import signal

        rem = remaining()
        if rem < min(45.0, budget_s):
            results[name + "_note"] = (
                f"skipped: {rem:.0f}s left < section budget"
            )
            _emit(results, partial=True)
            return
        budget = min(budget_s, rem)
        log_path = os.path.join(workdir, f"log_{name}.txt")
        t0 = time.time()

        def merge_out(sent, out_path):
            """Fold the child's added/changed keys into results —
            ALWAYS called, even after a budget kill or crash: the
            child re-dumps every 2s, so completed sub-measurements
            survive its death."""
            if not os.path.exists(out_path):
                return False
            try:
                with open(out_path) as f:
                    child = json.load(f)
            except (OSError, ValueError):
                return False
            for k, v in child.items():
                if k not in sent or sent[k] != v:
                    results[k] = v
            return True

        def attempts():
            for attempt in (1, 2):
                # snapshot under the emit lock: the CPU thread writes
                # whole keys lock-free, and the child must start from
                # a clean view
                with _EMIT_LOCK:
                    blob = _snapshot_blob(results)
                sent = json.loads(blob)
                with open(state_path, "w") as f:
                    f.write(blob)
                out_path = state_path + ".out"
                if os.path.exists(out_path):
                    os.remove(out_path)
                with open(log_path, "ab") as lf:
                    proc = _register_proc(subprocess.Popen(
                        [sys.executable, this_file, "--section", name,
                         state_path, workdir],
                        stdout=lf, stderr=lf, cwd=os.getcwd(),
                        start_new_session=True,
                        # budget-aware sections read this to finish
                        # with a partial result before the SIGKILL;
                        # REMAINING budget, not the nominal one — a
                        # retry attempt starts with whatever attempt
                        # 1 left, and overstating it would let the
                        # child start a leg the parent kills mid-run
                        env={
                            **os.environ,
                            "BENCH_SECTION_BUDGET_S": f"{max(5.0, budget - (time.time() - t0)):.0f}",
                        },
                    ))
                killed = False
                try:
                    rc = proc.wait(
                        timeout=max(5.0, budget - (time.time() - t0))
                    )
                except subprocess.TimeoutExpired:
                    killed = True
                    try:
                        os.killpg(proc.pid, signal.SIGKILL)
                    except (ProcessLookupError, PermissionError):
                        pass
                    proc.wait()
                    results[name + "_note"] = (
                        f"killed at budget {budget:.0f}s (subprocess "
                        "SIGKILL — no residual device work survives)"
                    )
                finally:
                    if proc in _LIVE_PROCS:
                        _LIVE_PROCS.remove(proc)
                merged = merge_out(sent, out_path)
                if not killed and rc == 0 and merged:
                    results.pop(name + "_error", None)
                    return
                if killed:
                    # sub-measurements the child dumped before the
                    # kill are real results — mark the section partial
                    # so the headline reports it as such instead of
                    # filing it under "skipped"
                    sec = results.get(name)
                    if isinstance(sec, dict) and sec:
                        sec["partial"] = True
                    return  # budget exhausted — no retry
                tail = ""
                try:
                    with open(log_path, "rb") as lf:
                        tail = lf.read()[-300:].decode(
                            "utf-8", "replace"
                        )
                except OSError:
                    pass
                results[name + "_error"] = f"rc={rc}: {tail}"
                time.sleep(3)

        try:
            attempts()
        except Exception as e:  # noqa: BLE001 - one section must
            # never abort the run (the old thread body had this
            # containment; the subprocess rewrite keeps it)
            results[name + "_error"] = (
                f"parent: {type(e).__name__}: {e}"
            )
        results["section_wall_s"][name] = round(time.time() - t0, 1)
        _emit(results, partial=True)

    # headline-first: by the time anything is killed, the required
    # metrics (train MFU, llama MFU, flash-ckpt stall+snapshot_e2e,
    # bounded auto-config) are already on stdout; goodput arrives
    # from the CPU thread, re-emitted at the join below
    # ordered by value-per-second: the four REQUIRED sections, then
    # cheap detail sections, then the expensive XL legs last (their
    # compiles are minutes cold — they may be skipped,
    # never starve the rest).  Budgets from measured warm-cache walls
    # (section_wall_s of the r4 chip runs) + headroom.
    # budgets = measured cache-cold walls (r5 full-run
    # section_wall_s: train 125, llama 278, flash 230, auto 194,
    # attn 33, gqa 16, sparse 27, input 58) + headroom + ~10s child
    # jax backend init.  xl_train_step runs RIGHT AFTER the four
    # required sections: its MFU is a headline metric, and in the r5
    # validation run the tail position cost it the deadline.
    sections = [
        ("train_step", 200),
        ("llama_train_step", 330),
        ("flash_ckpt", 300),
        ("auto_config", 240),
        ("xl_train_step", 300),
        ("attention_kernel", 80),
        ("gqa_attention_kernel", 120),
        ("sparse_kv", 100),
        ("input_pipeline", 150),
        ("xl_act_offload", 360),
    ]
    for name, budget in sections:
        run_section(name, budget)
        if not cpu_thread.is_alive() and cpu_thread.ident is None:
            # first section done: launch the CPU-side benches; device
            # timings from here on share host cores with them
            results["cpu_concurrency_note"] = (
                "goodput/recovery ran on host cores concurrently "
                "with the device sections after train_step"
            )
            cpu_thread.start()

    cpu_thread.join(max(10.0, remaining()))
    if cpu_thread.is_alive():
        results["cpu_sections_note"] = (
            "goodput/recovery still running at deadline; their "
            "supervision trees were killed"
        )
        _kill_live_procs()
    shutil.rmtree(workdir, ignore_errors=True)
    done_evt.set()
    _emit(results)
    # hard exit: abandoned section threads may hold in-flight device
    # work whose C++ teardown aborts the interpreter AFTER the final
    # line (observed: SIGABRT "exception not rethrown" post-emission
    # turning a complete run into rc=134); the JSON is already out
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    if len(sys.argv) >= 5 and sys.argv[1] == "--section":
        sys.exit(_child_main(sys.argv[2], sys.argv[3], sys.argv[4]))
    sys.exit(main())
