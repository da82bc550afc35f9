"""Static event-schema lint (telemetry/lint_events.py): every
statically-visible ``emit_event(...)`` / ``.emit(...)`` type must be
registered, and every registered type must have an emitting call site
— including emitters inside embedded train-script string constants.
Running it over the real package IS the tier-1 gate: a PR that emits
an unregistered event or strands a schema entry fails here."""

import os
import textwrap

from dlrover_tpu.telemetry import lint_events
from dlrover_tpu.telemetry.schema import EVENT_SCHEMAS


def test_package_emit_surface_matches_schema():
    problems = lint_events.lint()
    assert problems == [], "\n".join(problems)


def test_unregistered_emit_is_reported(tmp_path):
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        from dlrover_tpu.telemetry.events import emit_event

        def f():
            emit_event("totally_unregistered_event", foo=1)
    """))
    problems = lint_events.lint(str(tmp_path))
    assert any(
        "totally_unregistered_event" in p and "not registered" in p
        for p in problems
    ), problems


def test_dead_schema_entries_are_reported(tmp_path):
    # a package emitting nothing leaves EVERY schema entry dead
    (tmp_path / "mod.py").write_text("x = 1\n")
    problems = lint_events.lint(str(tmp_path))
    dead = [p for p in problems if "no emitting call site" in p]
    assert any("'train_step'" in p for p in dead), problems
    assert len(dead) >= len(EVENT_SCHEMAS) - len(
        lint_events.ALLOWED_UNEMITTED
    )


def test_embedded_script_strings_are_linted(tmp_path):
    # the chaos scenarios ship trainers as string constants; their
    # emit sites must count as call sites
    script = "\n".join(
        ["from dlrover_tpu.telemetry.events import emit_event"]
        + ["# padding line to cross the embedded-script floor"] * 8
        + ["emit_event(\"my_embedded_event\", step=1)"]
    )
    (tmp_path / "mod.py").write_text(
        f"TRAIN_SCRIPT = {script!r}\n"
    )
    emitted = lint_events.collect_emitted_types(str(tmp_path))
    assert "my_embedded_event" in emitted
    assert "<embedded>" in emitted["my_embedded_event"][0]


def test_exporter_style_emit_is_collected(tmp_path):
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        def f(exporter):
            exporter.emit("exporter_style_event", path="p")
    """))
    emitted = lint_events.collect_emitted_types(str(tmp_path))
    assert "exporter_style_event" in emitted


def test_unparseable_source_is_a_problem(tmp_path):
    (tmp_path / "broken.py").write_text("def f(:\n")
    problems = lint_events.lint(str(tmp_path))
    assert any("unparseable" in p for p in problems), problems


def test_package_span_names_match_the_registry():
    """Every literal span name of the package is in
    ``schema.SPAN_SCHEMAS`` (README "Telemetry" prints that table),
    and every registered name has a call site."""
    problems = lint_events.lint_spans()
    assert problems == [], "\n".join(problems)


def test_unregistered_span_name_is_reported(tmp_path):
    (tmp_path / "mod.py").write_text(textwrap.dedent("""
        from dlrover_tpu.telemetry.tracing import span as _span

        def f():
            with _span("totally.unregistered_span", step=1):
                pass
    """))
    problems = lint_events.lint_spans(str(tmp_path))
    assert any(
        "totally.unregistered_span" in p and "not registered" in p
        for p in problems
    ), problems
    # and a package with no span leaves every registered name dead
    assert any("'ckpt.save'" in p and "no call site" in p
               for p in problems)


def test_readme_lists_every_span():
    from dlrover_tpu.telemetry.schema import SPAN_SCHEMAS

    readme = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "README.md",
    )
    with open(readme) as f:
        text = f.read()
    missing = [n for n in SPAN_SCHEMAS if f"| `{n}` |" not in text]
    assert missing == []
