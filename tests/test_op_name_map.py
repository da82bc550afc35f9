"""The op-name map names what the compiler made
(``common/aot_cache.py::op_names``: ``inherited``, ``containers``,
``unnamed``, and ``scopes`` from ``telemetry/tracing.py::device_scope``):
one small HLO text a rule, ``op_names`` itself against the loop it
was until PR 54, and three families' toy steps compiled on the CPU."""

import os
import re
import subprocess
import sys

import pytest

jax = pytest.importorskip("jax")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "benchmarks"))

import loader  # noqa: E402  (the benchmark's own)

from dlrover_tpu.common import aot_cache  # noqa: E402
from dlrover_tpu.telemetry import tracing  # noqa: E402
from dlrover_tpu.trainer.elastic_trainer import (  # noqa: E402
    TrainState,
    make_train_step,
)

NORM = "jit(step_fn)/forward_backward/jvp(M)/block_0/ssm_norm"
PROJ = "jit(step_fn)/forward_backward/jvp(M)/block_0/ssm_out_proj"


def meta(stack):
    return f'metadata={{op_name="{stack}" stack_frame_id=3}}'


def module(entry, *others):
    return "\n".join(
        ["HloModule jit_step_fn, is_scheduled=true", ""] + list(others)
        + ["ENTRY %main.1 (p0: f32[8,128], p1: f32[8,128]) -> f32[8,128] {",
           "  %p0 = f32[8,128]{1,0:T(8,128)} parameter(0)",
           "  %p1 = f32[8,128]{1,0:T(8,128)} parameter(1)"]
        + ["  " + line for line in entry] + ["}", ""]
    )


FUSED = [
    "%fused_computation.1 (param_0: f32[8,128]) -> f32[8,128] {",
    "  %param_0 = f32[8,128]{1,0} parameter(0)",
    f"  %neg.1 = f32[8,128]{{1,0}} negate(%param_0), {meta(PROJ + '/neg')}",
    f"  ROOT %mul.1 = f32[8,128]{{1,0}} multiply(%neg.1, %neg.1), "
    f"{meta(NORM + '/mul')}",
    "}", "",
]
FUSED_BARE_ROOT = [
    "%fused_computation.2 (param_0: f32[8,128]) -> f32[8,128] {",
    "  %param_0 = f32[8,128]{1,0} parameter(0)",
    f"  %neg.2 = f32[8,128]{{1,0}} negate(%param_0), {meta(NORM + '/neg')}",
    f"  %exp.2 = f32[8,128]{{1,0}} exponential(%neg.2), {meta(NORM + '/exp')}",
    "  ROOT %copy.2 = f32[8,128]{0,1} copy(%exp.2)",
    "}", "",
]
FUSED_TWO_LAYERS = [
    "%fused_computation.3 (param_0: f32[8,128]) -> f32[8,128] {",
    "  %param_0 = f32[8,128]{1,0} parameter(0)",
    f"  %neg.3 = f32[8,128]{{1,0}} negate(%param_0), {meta(NORM + '/neg')}",
    f"  %exp.3 = f32[8,128]{{1,0}} exponential(%param_0), "
    f"{meta(NORM + '/exp')}",
    "  ROOT %add.3 = f32[8,128]{1,0} add(%neg.3, %exp.3)",
    "}", "",
]
LOOP = [
    "%body.1 (arg: (s32[], f32[8,128])) -> (s32[], f32[8,128]) {",
    "  %arg = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)}) parameter(0)",
    "  %i = s32[]{:T(128)} get-tuple-element(%arg), index=0",
    "  %x = f32[8,128]{1,0:T(8,128)} get-tuple-element(%arg), index=1",
    "  %copy.7 = f32[8,128]{0,1:T(8,128)} copy(%x)",
    f"  %tanh.7 = f32[8,128]{{0,1:T(8,128)}} tanh(%copy.7), "
    f"{meta(NORM + '/while/body/tanh')}",
    "  ROOT %tuple.7 = (s32[]{:T(128)}, f32[8,128]{0,1:T(8,128)}) "
    "tuple(%i, %tanh.7)",
    "}", "",
    "%cond.1 (arg.1: (s32[], f32[8,128])) -> pred[] {",
    "  %arg.1 = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)}) parameter(0)",
    "  %i.1 = s32[]{:T(128)} get-tuple-element(%arg.1), index=0",
    "  %limit = s32[]{:T(128)} constant(4)",
    f"  ROOT %lt.1 = pred[]{{:T(512)}} compare(%i.1, %limit), direction=LT, "
    f"{meta(NORM + '/while/cond/lt')}",
    "}", "",
]
# the same loop with the layout copy AFTER the body's one named
# operation, into the carry
CARRY = LOOP[:4] + [
    f"  %tanh.7 = f32[8,128]{{1,0:T(8,128)}} tanh(%x), "
    f"{meta(NORM + '/while/body/tanh')}",
    "  %copy.9 = f32[8,128]{0,1:T(8,128)} copy(%tanh.7)",
    "  ROOT %tuple.7 = (s32[]{:T(128)}, f32[8,128]{0,1:T(8,128)}) "
    "tuple(%i, %copy.9)",
] + LOOP[7:]
START = (
    "%copy-start.1 = (f32[8,128]{1,0:T(8,128)S(1)}, f32[8,128]{1,0:T(8,128)}, "
    "u32[]{:S(2)}) copy-start(%p0)"
)
CASES = {
    # the done has no name, its one user has: the done takes the
    # user's, the start the done's
    "start": (module([
        START,
        "%copy-done.1 = f32[8,128]{1,0:T(8,128)S(1)} copy-done(%copy-start.1)",
        f"ROOT %add.1 = f32[8,128]{{1,0:T(8,128)}} add(%copy-done.1, %p1), "
        f"{meta(NORM + '/add')}",
    ]), {"%copy-done.1": [NORM + "/add", "user"],
         "%copy-start.1": [NORM + "/add", "start"]}),
    # ... and the other way: one of 117 starts carries a name
    "start named": (module([
        START + ", " + meta(PROJ + "/dot_general"),
        "ROOT %copy-done.1 = f32[8,128]{1,0:T(8,128)S(1)} "
        "copy-done(%copy-start.1)",
    ]), {"%copy-done.1": [PROJ + "/dot_general", "start"]}),
    "body, the root's": (module([
        "ROOT %fusion.1 = f32[8,128]{1,0:T(8,128)} fusion(%p0), kind=kLoop, "
        "calls=%fused_computation.1",
    ], *FUSED), {"%fusion.1": [NORM + "/mul", "body"]}),
    # a root of the compiler's own: what it is made from
    "body, the root's producer": (module([
        "ROOT %fusion.2 = f32[8,128]{0,1:T(8,128)} fusion(%p0), kind=kLoop, "
        "calls=%fused_computation.2",
    ], *FUSED_BARE_ROOT), {"%fusion.2": [NORM + "/exp", "body"]}),
    "body, what the body shares": (module([
        "ROOT %fusion.3 = f32[8,128]{0,1:T(8,128)} fusion(%p0), kind=kLoop, "
        "calls=%fused_computation.3",
    ], *FUSED_TWO_LAYERS), {"%fusion.3": [NORM, "body"]}),
    # through a tuple and a get-tuple-element to two users that agree
    # on the layer, not on the operation
    "user": (module([
        "%copy.3 = f32[8,128]{0,1:T(8,128)} copy(%p0)",
        "%tuple.3 = (f32[8,128]{0,1:T(8,128)}, f32[8,128]{1,0:T(8,128)}) "
        "tuple(%copy.3, %p1)",
        "%gte.3 = f32[8,128]{0,1:T(8,128)} get-tuple-element(%tuple.3), "
        "index=0",
        f"%exp.3 = f32[8,128]{{1,0:T(8,128)}} exponential(%gte.3), "
        f"{meta(NORM + '/exp')}",
        f"ROOT %add.3 = f32[8,128]{{1,0:T(8,128)}} add(%gte.3, %exp.3), "
        f"{meta(NORM + '/reduce_sum')}",
    ]), {"%copy.3": [NORM, "user"]}),
    # users that agree on the jit's root alone say nothing: the
    # producer does
    "operand": (module([
        f"%exp.4 = f32[8,128]{{1,0:T(8,128)}} exponential(%p0), "
        f"{meta(NORM + '/exp')}",
        "%bitcast.4 = f32[8,128]{1,0:T(8,128)} bitcast(%exp.4)",
        "%copy.4 = f32[8,128]{0,1:T(8,128)} copy(%bitcast.4)",
        f"%neg.4 = f32[8,128]{{1,0:T(8,128)}} negate(%copy.4), "
        f"{meta(PROJ + '/neg')}",
        f"ROOT %add.4 = f32[8,128]{{1,0:T(8,128)}} add(%copy.4, %neg.4), "
        f"{meta('jit(step_fn)/optimizer/add')}",
    ]), {"%copy.4": [NORM + "/exp", "operand"]}),
    # a loop's body and condition run as operations of their own
    "while": (module([
        "%zero = s32[]{:T(128)} constant(0)",
        "%tuple.6 = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)}) "
        "tuple(%zero, %p0)",
        f"%while.6 = (s32[]{{:T(128)}}, f32[8,128]{{1,0:T(8,128)}}) "
        f"while(%tuple.6), condition=%cond.1, body=%body.1, "
        f"{meta(NORM + '/while')}",
        "ROOT %out = f32[8,128]{1,0:T(8,128)} get-tuple-element(%while.6), "
        "index=1",
    ], *LOOP), {"%copy.7": [NORM + "/while/body/tanh", "user"]}),
    # a copy into a loop's carry has no user but the body's root,
    # which its container uses: the loop's own stack
    "user, the root's container": (module([
        "%zero = s32[]{:T(128)} constant(0)",
        "%tuple.6 = (s32[]{:T(128)}, f32[8,128]{1,0:T(8,128)}) "
        "tuple(%zero, %p0)",
        f"%while.6 = (s32[]{{:T(128)}}, f32[8,128]{{1,0:T(8,128)}}) "
        f"while(%tuple.6), condition=%cond.1, body=%body.1, "
        f"{meta(NORM + '/while')}",
        "ROOT %out = f32[8,128]{1,0:T(8,128)} get-tuple-element(%while.6), "
        "index=1",
    ], *CARRY), {"%copy.9": [NORM + "/while", "user"]}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_rule_names_what_the_compiler_made(case):
    text, inherited = CASES[case]
    got = aot_cache.op_names(text)
    assert got["inherited"] == inherited
    assert got["unnamed"] == {}
    assert got["containers"] == (["%while.6"] if "%while.6" in text else [])
    # nothing the map names elsewhere is named here again
    assert not set(got["inherited"]) & set(got["op_names"])


def test_what_no_rule_reaches_is_listed_with_opcode_and_shape():
    """A parameter's copy whose users sit in two layers, and whose
    producer is no instruction: no one layer's."""
    text = module([
        "%copy.5 = f32[8,128]{0,1:T(8,128)S(1)} copy(%p0)",
        f"%neg.5 = f32[8,128]{{1,0:T(8,128)}} negate(%copy.5), "
        f"{meta(PROJ + '/neg')}",
        f"ROOT %add.5 = f32[8,128]{{1,0:T(8,128)}} add(%copy.5, %neg.5), "
        f"{meta('jit(step_fn)/optimizer/add')}",
    ])
    got = aot_cache.op_names(text)
    assert got["inherited"] == {}
    assert got["unnamed"] == {"%copy.5": "copy f32[8,128]"}


def test_a_parameters_own_name_is_a_stack_too():
    """A weight's layout copy is named after the weight: the entry's
    parameters carry their tree path, which has no jit at its root."""
    weight = "state.params['block_0']['experts_w_in']"
    text = module([
        "%copy.8 = f32[8,128]{0,1:T(8,128)} copy(%w)",
        f"ROOT %add.8 = f32[8,128]{{1,0:T(8,128)}} add(%copy.8, %p1), "
        f"{meta('jit(step_fn)/optimizer/add')}",
    ]).replace(
        "  %p1 = ", f'  %w = f32[8,128]{{1,0}} parameter(2), '
        f'metadata={{op_name="{weight}"}}\n  %p1 = ',
    )
    got = aot_cache.op_names(text)
    assert got["inherited"] == {
        "%copy.8": ["jit(step_fn)/optimizer/add", "user"],
    }
    alone = text.replace(meta("jit(step_fn)/optimizer/add"), "ignored=1")
    assert aot_cache.op_names(alone)["inherited"]["%copy.8"] == [
        weight, "operand",
    ]


# -- ``op_names`` is what it was --------------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%?[\w\-.]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def op_names_until_pr_54(hlo_text):
    names = {}
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if found:
            stack = _OP_NAME.search(line)
            if stack:
                names[found.group(1)] = stack.group(1)
    return names


def toy_step(config, seq=32):
    """``(jitted step, abstract state, abstract batch)`` of a toy
    configuration of the benchmark's, nothing on a device."""
    cfg = loader.load_json(
        os.path.join(REPO, "benchmarks", "configs", config + ".json")
    )
    family = loader.load_module("models", cfg["model_type"])
    model, optimizer, loss_fn = family.build(cfg)
    state = jax.eval_shape(
        lambda key: TrainState.create(
            model.init_params(key, seq_len=seq), optimizer
        ), jax.random.PRNGKey(0),
    )
    tokens = jax.ShapeDtypeStruct((2, seq), jax.numpy.int32)
    return (
        make_train_step(loss_fn, optimizer), state,
        {"x": tokens, "y": tokens},
    )


FAMILIES = {
    "toy": (),
    "toy_ouro": ("ut", "exit_gate"),
    "toy_olmoe": ("moe_router", "moe_dispatch", "moe_experts",
                  "moe_combine"),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def compiled_toy(request, tmp_path_factory):
    step, state, batch = toy_step(request.param)
    tracing.DEVICE_SCOPES.clear()
    compiled = step.lower(state, batch).compile()
    directory = str(tmp_path_factory.mktemp("aot"))
    assert aot_cache.save_op_names("k", compiled, directory)
    return request.param, compiled.as_text(), loader.load_json(
        aot_cache.op_names_path("k", directory)
    )


def test_op_names_is_key_for_key_what_it_was(compiled_toy):
    _, text, saved = compiled_toy
    assert saved["op_names"] == op_names_until_pr_54(text)
    for hand_made, _ in CASES.values():
        assert aot_cache.op_names(hand_made)["op_names"] == (
            op_names_until_pr_54(hand_made)
        )


def test_every_instruction_that_runs_has_a_stack_or_inherits_one(
    compiled_toy,
):
    """The toy steps compiled for the CPU (fusions, loops and the CPU
    compiler's own ``call``s): nothing is left unnamed, a container
    is no operation, and the file lists the scopes the trace opened."""
    family, text, saved = compiled_toy
    assert saved["unnamed"] == {}
    assert saved["inherited"]
    assert {rule for _, rule in saved["inherited"].values()} <= {
        "start", "body", "user", "operand",
    }
    assert not set(saved["containers"]) & set(saved["inherited"])
    for name in saved["containers"]:
        line = next(
            ln for ln in text.splitlines() if f" {name} = " in ln
        )
        assert re.search(r" (while|conditional|call)\(", line), line
    if family == "toy_ouro":
        # the passes are scans
        assert any(n.startswith("%while") for n in saved["containers"])
    assert set(saved["scopes"]) >= {
        "forward_backward", "loss_head", "optimizer", *FAMILIES[family]
    }
    # a scope is a component of some instruction's stack; a flax
    # module's name is one too and is no scope
    components = {
        c for s in saved["op_names"].values() for c in s.split("/")
    }
    assert "optimizer" in components
    assert any(c.startswith("block_") for c in components)
    assert not any(s.startswith("block_") for s in saved["scopes"])


def test_the_lowered_step_is_the_same_program_under_device_scope(
    monkeypatch,
):
    """``device_scope`` is ``jax.named_scope`` and a set: the lowered
    text of a step is what plain ``jax.named_scope`` gives."""
    step, state, batch = toy_step("toy")
    ours = step.lower(state, batch).as_text()
    for name, mod in list(sys.modules.items()):
        if name.startswith("dlrover_tpu.") and hasattr(mod, "device_scope"):
            monkeypatch.setattr(mod, "device_scope", jax.named_scope)
    step, state, batch = toy_step("toy")
    assert step.lower(state, batch).as_text() == ours


def test_no_device_scope_is_opened_past_device_scope():
    """One way to open a device scope: ``jax.named_scope(`` stands in
    ``telemetry/tracing.py`` alone."""
    found = subprocess.run(
        ["grep", "-rlE", r"\bnamed_scope\(", "--include=*.py",
         os.path.join(REPO, "dlrover_tpu")],
        capture_output=True, text=True,
    ).stdout.split()
    assert [os.path.relpath(f, REPO) for f in found] == [
        "dlrover_tpu/telemetry/tracing.py"
    ]


def test_device_scope_registers_its_name_and_names_the_operations():
    import jax.numpy as jnp

    def f(x):
        with tracing.device_scope("a_scope_of_this_test"):
            return jnp.sin(x) * 2

    text = jax.jit(f).lower(jnp.ones(4)).as_text(debug_info=True)
    assert "a_scope_of_this_test" in tracing.DEVICE_SCOPES
    assert "a_scope_of_this_test/sin" in text


def test_a_warm_resolve_walks_no_text(tmp_path, monkeypatch):
    """The map is the cold path's, inside ``save_s``: a resolve that
    hits the AOT entry reads no program text."""
    step, state, batch = toy_step("toy")
    cold = aot_cache.resolve_step(
        step, (state, batch), label="t", cache_dir=str(tmp_path)
    )
    if not cold.wrote:
        pytest.skip("this backend serializes no executable")
    assert os.path.exists(aot_cache.op_names_path(cold.key, str(tmp_path)))
    assert cold.save_s > 0

    def walked(text):
        raise AssertionError("a warm resolve walked the program's text")

    monkeypatch.setattr(aot_cache, "op_names", walked)
    warm = aot_cache.resolve_step(
        step, (state, batch), label="t", cache_dir=str(tmp_path)
    )
    assert warm.hit and warm.source == "aot" and warm.save_s == 0
