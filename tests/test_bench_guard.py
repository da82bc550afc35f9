"""BENCH compact-final-line contract guard (VERDICT r5 #10).

The bench driver keeps only a 2000-byte stdout tail and parses the
LAST JSON line; three rounds of chip numbers died to oversized final
lines before the ≤1500-byte scalars-only contract was frozen.  This
tier-1 guard pins the contract so profiler/diagnosis additions (new
sections, new headline keys) can never silently bloat it again."""

import importlib.util
import json
import os
import sys

import pytest

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT = 1500


@pytest.fixture(scope="module")
def bench():
    """Import bench.py as a module (it lives at the repo root, not in
    the package; import has no side effects — sections only run under
    __main__)."""
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(REPO_ROOT, "bench.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fat_snapshot() -> dict:
    """A worst-case cumulative snapshot: every headline key present
    with wide float values, every section erroring AND skipping, so
    the headline is as fat as it can ever legitimately get."""
    snap = {
        "_speedup": 1398.123456,
        "goodput": {
            "goodput_pct": 96.789123, "kills_delivered": 5,
            "churn_lost_s": 123.456789,
            "phase_breakdown": {"total_lost_s": {"max": 45.678901}},
        },
        "llama_train_step": {
            "seq2048": {"mfu": 0.591234}, "seq4096": {"mfu": 0.541234},
        },
        "train_step": {"flash_attention": {"mfu": 0.481234}},
        "xl_train_step": {"mfu": 0.391234},
        "flash_ckpt": {
            "flash_stall_s": 0.012345, "restore_shm_s": 3.971234,
            # the ISSUE-10 breakdown keys must flatten to compact
            # scalar strings in the headline
            "restore_shm_phases": {
                "read_s": 0.123456, "assemble_s": 3.456789,
                "h2d_s": 0.345678, "bytes": 402653184, "workers": 8,
            },
            "memcpy_baseline_MBps": 1234.567,
        },
        "auto_config": {"searched_vs_hand": 0.9661234},
        "sparse_kv": {
            "deepfm_e2e": {
                "pipelined": {"steps_per_s": 15.123456},
                "pipeline_speedup": 2.212345,
            },
            "host_gather_Mlookups_per_s": 16.312345,
            "kv_checkpoint": {
                "export_s": 0.123456, "restore_s": 0.234567,
            },
        },
        "input_pipeline": {"input_bound_pct": 12.345678},
        "serving": {
            "freshness_mean_s": 0.123456,
            "freshness_max_s": 0.234567,
            "lookup_p99_under_ingest_ms": 1.234567,
            "lookup_p99_quiet_ms": 0.912345,
            "delta_ratio": 0.021234,
            "export_stall_speedup": 43.212345,
            "full_export_s": 0.345678,
            "delta_export_s": 0.008123,
        },
        "serving_fleet": {
            "max_qps": 1234.512345,
            "scaling_1_to_2_x": 1.812345,
            "rebase": {
                "p99_ms": 12.345678, "failed": 0,
                "p99_over_quiet_x": 1.512345,
            },
        },
        "sparse_scale": {
            "table_rows": 150000,
            "table_mb": 38.912345,
            "spill_budget_mb": 9.712345,
            "delta_ratio": 0.012345,
            "export_stall_speedup": 690.612345,
            "reshard_MBps": 1424.612345,
            "reshard_chunks": 20,
            "reshard_peak_extra_rss_mb": 7.212345,
            "oneshot_peak_extra_rss_mb": 73.212345,
            "rss_oneshot_over_streaming_x": 10.212345,
        },
        "gqa_attention_kernel": {"seq2048": {"speedup": 1.812345}},
        "attention_kernel": {"seq8192": {"flash_vs_xla_speedup": 2.9}},
        "rl_elastic": {
            "recovery_s": 4.712345,
            "goodput_pct": 91.212345,
            "lost_s": 6.812345,
            "iterations": 6,
            "iter_train_s": 0.412345,
        },
        "goodput_ledger": {
            "attributed_pct": 95.512345,
            "top_loss_cause": "compile_trace",
            "goodput": 0.174512,
            "incarnations": 2,
            "wall_s": 9.480123,
            "conservation_ok": True,
            # the full per-category sub-dict must NOT leak into the
            # headline — only the two scalar keys above do
            "totals_s": {
                "productive_step": 0.300123,
                "compile_trace": 7.539123,
                "restore": 0.098123,
                "rendezvous": 0.007123,
                "respawn_gap": 1.087123,
                "checkpoint_stall": 0.024123,
                "idle_unattributed": 0.424123,
            },
            "top_loss_causes": {
                "compile_trace": 7.539123,
                "respawn_gap": 1.087123,
                "idle_unattributed": 0.424123,
            },
        },
        "xl_act_offload": {
            "offload": {"tokens_per_s": 1234.567891},
            "plain_remat_control": {"tokens_per_s": 987.654321},
        },
        "elastic_recovery": {
            "recovery_s": 3.612345,
            "retrace_s": 1.103456,
            "cache_hits": 1, "cache_misses": 0,
            "cycles": {
                "restart1": {
                    "spawn": 0.147123, "import": 0.129456,
                    "restore": 0.019789, "retrace": 1.103456,
                    "first_step": 0.655123,
                    "compile_cache_hit": True,
                },
            },
        },
    }
    # every known section both errors and is skipped — the headline's
    # lists must survive the worst case
    sections = [
        "goodput", "llama_train_step", "train_step", "xl_train_step",
        "xl_act_offload", "flash_ckpt", "auto_config", "sparse_kv",
        "input_pipeline", "gqa_attention_kernel", "attention_kernel",
        "elastic_recovery", "serving", "serving_fleet",
        "sparse_scale", "multislice",
        "sequence_parallel", "rl_elastic", "goodput_ledger",
    ]
    for name in sections:
        snap[f"{name}_error"] = "boom " * 50
        snap[f"{name}_note"] = "skipped: over budget"
    # partial markers
    for name in ("goodput", "flash_ckpt", "sparse_kv"):
        snap[name]["partial"] = True
    return snap


def _is_scalar(v) -> bool:
    return isinstance(v, (int, float, str, bool)) or v is None


def test_headline_is_scalars_only_and_bounded(bench):
    head = bench._headline(_fat_snapshot())
    for key, val in head.items():
        if key in ("errors", "skipped", "partial_sections"):
            assert isinstance(val, list)
            assert all(isinstance(x, str) for x in val), key
        else:
            assert _is_scalar(val), (
                f"headline key {key!r} is not a scalar: {val!r}"
            )
    # the full compact object (head + detail) must fit the contract
    compact = {
        "metric": "flash_ckpt_stall_speedup_vs_sync_save",
        "value": 1398.12,
        "unit": "x",
        "vs_baseline": 139.812,
        "detail": dict(head, partial=True),
    }
    line = json.dumps(compact)
    assert len(line) <= LIMIT, (
        f"compact line {len(line)}B > {LIMIT}B: {line}"
    )


def test_emit_final_stdout_line_fits_tail(bench, capsys):
    """Drive the REAL emission path with the fat snapshot: the last
    stdout line must parse and fit, whatever lands in the detail."""
    bench._emit(_fat_snapshot(), partial=True)
    out = capsys.readouterr().out.strip().splitlines()
    assert out, "no stdout line emitted"
    last = out[-1]
    assert len(last) <= LIMIT
    doc = json.loads(last)
    assert doc["metric"] == "flash_ckpt_stall_speedup_vs_sync_save"
    assert isinstance(doc["detail"], dict)
    for key, val in doc["detail"].items():
        if key in ("errors", "skipped", "partial_sections"):
            assert isinstance(val, list)
        else:
            assert _is_scalar(val), key


def test_emit_trim_loop_guarantees_fit_under_adversarial_bloat(
    bench, capsys
):
    """Even a pathological snapshot (a future section stuffing huge
    values into headline-visible paths) is trimmed down to ≤1500
    bytes — the hard guarantee, not a convention."""
    snap = _fat_snapshot()
    # bloat the error list beyond any reasonable size
    for i in range(60):
        snap[f"imaginary_section_{i:02d}_error"] = "x"
    bench._emit(snap, partial=False)
    last = capsys.readouterr().out.strip().splitlines()[-1]
    assert len(last) <= LIMIT
    json.loads(last)
