"""Seconds to a ready step executable: the wall time of the worker's
``RecoveryProfiler.resolve_step`` (``aot_cache`` event), with the
``compile_cache`` status (aot-hit / xla-cache-hit / cold)."""

NAME = "cache.load_s"
UNIT = "s"
LAYER = "compile caches"
MOVES = "setup_s"
SOURCE = "program_span"


def read(run):
    resolved = run.of("aot_cache")
    if not resolved:
        return None
    status = [e.get("status") for e in run.of("compile_cache")]
    run.note(
        f"step executable: {status[0] if status else 'not reported'} "
        f"(aot load {resolved[0].get('load_s')} s, trace+compile "
        f"{resolved[0].get('trace_s')} s, save {resolved[0].get('save_s')}"
        " s)"
    )
    return resolved[0]["seconds"]
