"""AOT executable cache: recovery deserializes instead of re-tracing.

The PR 10 budget proved the recovery cycle is tracing-bound: with the
persistent XLA compile cache HIT, the respawned trainer still pays
~1.1 s of pure Python tracing to rebuild the jitted step before the
cache can even answer.  This module removes tracing from the critical
path: the first incarnation serializes its compiled step executable
(``jax.jit(...).lower(...).compile()`` through the
``jax.experimental.serialize_executable`` pair), and every later incarnation *deserializes* it — no trace, no lowering, no
XLA compile, ~10 ms instead of seconds.

Keyed like the persistent compile cache (same sharing contract: every
incarnation of a job resolves the same directory), with the entry key
derived from everything that could invalidate the binary:

- jax / jaxlib version strings (a binary compiled by one jax must
  never load under another);
- backend platform + local device count + process count + world size
  (the mesh/topology half of the key — a resized world re-traces);
- the abstract avals (shape / dtype / weak_type) and shardings of
  every flattened input, plus the input treedef;
- a caller-supplied label (two different step functions with equal
  avals stay distinct);
- a code-identity fingerprint of the step function — bytecode,
  literal constants and closure contents, recursively
  (:func:`fn_fingerprint`) — so editing the loss or an optimizer
  hyperparameter invalidates the entry even though the avals and
  label did not change.

**Strict fall-back-to-trace**: any key mismatch, corrupt entry,
unpicklable treedef or deserialization error returns "miss" and the
caller traces exactly as before — a cache problem can cost time,
never correctness and never a crash.  Entries are written atomically
(tmp + rename) so a killed writer can't leave a torn entry a later
incarnation trips over.

The forkserver template (``DLROVER_AOT_PRETRACE``) calls
:func:`preload_entries` after its module preload: entry BYTES are read
into this module's memory, and every forked worker inherits them —
the child's :func:`load_entry` deserializes from the inherited buffer
without touching disk.  (The template itself never deserializes: that
would initialize an XLA client whose threads do not survive the fork.)
"""

import hashlib
import json
import os
import pickle
import re
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from dlrover_tpu.common import env_utils
from dlrover_tpu.common.log import default_logger as logger

AOT_PRETRACE_ENV = "DLROVER_AOT_PRETRACE"
ENTRY_SUFFIX = ".aotx"
# pickle framing of one entry file; bumped when the layout changes so
# an old entry reads as a miss, not an unpickling surprise
_ENTRY_VERSION = 1

# template-preloaded entry bytes (filename -> blob): populated by
# preload_entries() in the forkserver template, inherited by every
# forked worker — load_entry() serves from here before touching disk
_PRELOADED: Dict[str, bytes] = {}


def aot_cache_dir() -> str:
    """The AOT entry directory every incarnation of this job shares:
    ``aot/`` under the persistent compile cache's directory, so the
    two caches ride the same sharing contract (including the
    cross-host case where ``JAX_COMPILATION_CACHE_DIR`` points at
    job-shared storage)."""
    from dlrover_tpu.common.compile_cache import job_cache_dir

    return os.path.join(job_cache_dir(), "aot")


def _leaf_desc(leaf: Any) -> List:
    """[shape, dtype, weak_type, sharding] of one abstract input leaf
    — works for concrete ``jax.Array``s, ``ShapeDtypeStruct``s and
    anything else carrying shape/dtype.  JSON-safe types only (lists,
    not tuples): descriptors round-trip through the label index's
    JSON, and equality against the pickled copy must survive it."""
    shape = [int(d) for d in getattr(leaf, "shape", ())]
    dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
    weak = bool(getattr(leaf, "weak_type", False))
    sharding = getattr(leaf, "sharding", None)
    return [shape, dtype, weak, repr(sharding) if sharding else ""]


# the packages a step program is built from beyond its own closure: a
# flax module's methods, the layers and kernels they call, the
# optimizer's update
SOURCE_PACKAGES = ("models", "ops", "parallel", "optim")
_SOURCE_DIGESTS: Dict[str, str] = {}


def source_digest(root: Optional[str] = None) -> str:
    """A hash over the bytes of every ``.py`` file of
    ``dlrover_tpu/{models,ops,parallel,optim}`` (``root``: the package
    directory; this installation's by default), read once a process.
    :func:`fn_fingerprint` feeds it where the closure holds an object
    whose class defines ``__call__`` (a flax module): its methods'
    code, and the layers and kernels they call, are not reachable
    through the closure, so without this an edit to ``models/olmoe.py``
    or ``parallel/moe.py`` alone was served a stale executable."""
    root = root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))
    )
    if root not in _SOURCE_DIGESTS:
        h = hashlib.sha256()
        for package in SOURCE_PACKAGES:
            for folder, dirs, files in os.walk(
                os.path.join(root, package)
            ):
                dirs.sort()
                for name in sorted(files):
                    if not name.endswith(".py"):
                        continue
                    path = os.path.join(folder, name)
                    h.update(os.path.relpath(path, root).encode())
                    with open(path, "rb") as f:
                        h.update(f.read())
        _SOURCE_DIGESTS[root] = h.hexdigest()
    return _SOURCE_DIGESTS[root]


def fn_fingerprint(fn: Any) -> str:
    """Code-identity component of the key: a hash over the function's
    bytecode, literal constants, and (recursively, bounded) the same
    for every function reachable through its closure — so editing the
    loss, changing an optimizer hyperparameter captured in a closure,
    or swapping the model config invalidates the entry even though
    label, avals and topology are unchanged.  Avals can't see code;
    without this, a persistent cache dir could silently serve an
    executable compiled from DIFFERENT code.  Deliberately
    conservative the other way too: values whose ``repr`` embeds a
    memory address contribute only their type name, so structurally
    identical closures hash identically across processes (the
    cross-process hit this cache exists for).  Unhashable oddities
    degrade to a sentinel — a stale-hit risk narrowed, never a crash.
    An object whose class defines ``__call__`` in Python (a flax
    module) adds :func:`source_digest`.
    """
    h = hashlib.sha256()
    seen: set = set()

    def feed_callable(obj, depth):
        if depth > 8 or id(obj) in seen:
            return
        seen.add(id(obj))
        wrapped = getattr(obj, "__wrapped__", None)
        code = getattr(obj, "__code__", None)
        if code is None and wrapped is not None:
            feed_callable(wrapped, depth)
            return
        if code is None:
            feed_value(getattr(obj, "__call__", obj), depth + 1)
            return
        h.update(code.co_code)
        for const in code.co_consts:
            if isinstance(
                const, (int, float, str, bytes, bool, type(None))
            ):
                h.update(repr(const).encode("utf-8"))
            elif hasattr(const, "co_code"):
                h.update(const.co_code)
        for cell in getattr(obj, "__closure__", None) or ():
            try:
                feed_value(cell.cell_contents, depth + 1)
            except ValueError:  # empty cell
                continue

    def feed_value(v, depth):
        if depth > 8 or id(v) in seen:
            return
        if callable(v) and (
            hasattr(v, "__code__") or hasattr(v, "__wrapped__")
        ):
            feed_callable(v, depth)
            return
        if isinstance(v, (tuple, list)):
            seen.add(id(v))
            for item in v[:32]:
                feed_value(item, depth + 1)
            return
        if isinstance(v, dict):
            seen.add(id(v))
            for k in sorted(map(repr, v))[:32]:
                h.update(k.encode("utf-8"))
            for item in list(v.values())[:32]:
                feed_value(item, depth + 1)
            return
        if hasattr(getattr(type(v), "__call__", None), "__code__"):
            h.update(source_digest().encode("utf-8"))
        try:
            r = repr(v)
        except Exception:  # noqa: BLE001 - repr is best-effort
            r = ""
        if " at 0x" in r:
            # address-bearing default repr: unstable across
            # processes — identity reduces to the type
            h.update(type(v).__name__.encode("utf-8"))
        else:
            h.update(r[:512].encode("utf-8"))

    try:
        feed_callable(fn, 0)
        return h.hexdigest()[:16]
    except Exception:  # noqa: BLE001 - never crash the resolve
        return "unhashable"


def describe(
    example_args: Tuple, label: str = "step", fn: Any = None
) -> Dict:
    """The invalidation descriptor an entry is keyed by (see module
    docstring).  ``example_args`` is the positional-argument tuple the
    step will be called with — concrete arrays or
    ``jax.ShapeDtypeStruct`` trees both work; ``fn`` contributes the
    code-identity component (see :func:`fn_fingerprint`)."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(example_args)
    try:
        import jaxlib

        jaxlib_version = getattr(jaxlib, "__version__", "")
    except ImportError:  # pragma: no cover - jaxlib rides with jax
        jaxlib_version = ""
    return {
        "v": _ENTRY_VERSION,
        "label": str(label),
        "fn": fn_fingerprint(fn) if fn is not None else "",
        "jax": jax.__version__,
        "jaxlib": jaxlib_version,
        "platform": jax.default_backend(),
        "devices": jax.local_device_count(),
        "processes": int(os.getenv("DLROVER_NUM_PROCESSES", "1")),
        "world_size": env_utils.get_world_size(),
        "in_tree": str(treedef),
        "avals": [_leaf_desc(x) for x in leaves],
    }


def key_of(desc: Dict) -> str:
    blob = json.dumps(desc, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:32]


def entry_path(key: str, cache_dir: Optional[str] = None) -> str:
    cache_dir = cache_dir or aot_cache_dir()
    return os.path.join(cache_dir, key + ENTRY_SUFFIX)


OP_NAMES_SUFFIX = ".opnames.json"
_MODULE = re.compile(r"^HloModule ([\w\-.]+)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%?[\w\-.]+) = ")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def op_names_path(key: str, cache_dir: Optional[str] = None) -> str:
    cache_dir = cache_dir or aot_cache_dir()
    return os.path.join(cache_dir, key + OP_NAMES_SUFFIX)


# -- the op-name map -----------------------------------------------------------
#
# What the COMPILER made (a layout copy, a pad, the halves of an
# asynchronous copy or slice, a fusion of such) carries no metadata.
# Each such instruction is work some layer of the program asked for:
# the map says whose, by four rules over the optimized text.

_COMPUTATION = re.compile(r"^(ENTRY )?(%[\w\-.]+) \(.*\{$")
_OPERAND = re.compile(r"%[\w\-.]+")
_CALLED = re.compile(
    r"\b(?:body|condition|to_apply|calls|true_computation|"
    r"false_computation)=(%[\w\-.]+)|branch_computations=\{([^}]*)\}"
)
_LAYOUT = re.compile(r"\{[^{}]*\}")
# instructions that hold others and run none of their own
CONTAINER_OPCODES = ("while", "conditional", "call")
# instructions a value passes through untouched: a user or a producer
# is looked for beyond them
_VIEWS = ("get-tuple-element", "bitcast", "tuple")
# what is no operation of the device's (a container's time is its
# body's)
_NOT_RUN = _VIEWS + CONTAINER_OPCODES + ("parameter", "constant")


def _closing(text: str, at: int) -> int:
    """Index of the parenthesis that closes the one at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if not depth:
                return i
    return len(text) - 1


def _parse_instruction(line: str, at: int):
    """``(opcode, shape, [operand], [called computation])`` of an
    instruction's line, read from the end of ``%name = `` on."""
    end = _closing(line, at) + 1 if line[at] == "(" else line.index(
        " ", at
    )
    paren = line.index("(", end)
    close = _closing(line, paren)
    opcode = line[end + 1:paren]
    called = []
    if opcode == "fusion" or opcode in CONTAINER_OPCODES:
        for one, many in _CALLED.findall(line, close):
            called += [one] if one else _OPERAND.findall(many)
    return (
        opcode, line[at:end], _OPERAND.findall(line, paren, close),
        called,
    )


def _common_stack(stacks) -> Optional[str]:
    """The deepest prefix, by ``/`` component, that ``stacks`` share,
    where it holds a component below the jit's root or is the one
    stack all of them are (a parameter's own name has no root); else
    None."""
    if len(set(stacks)) == 1:
        return stacks[0]
    shared = os.path.commonprefix([s.split("/") for s in stacks])
    return "/".join(shared) if len(shared) >= 2 else None


def _inherit(computations, entry, names):
    """``(inherited, containers, unnamed)`` of :func:`op_names`.
    ``computations`` is ``{name: [(instruction, line, where its text
    starts)]}``, a computation's root last."""
    parsed, containers, queue = {}, [], [entry]
    # a computation's root -> the container whose result it is
    returns_to = {}
    while queue:
        computation = queue.pop()
        if computation in parsed or computation not in computations:
            continue
        rows = parsed[computation] = {}
        for name, line, at in computations[computation]:
            rows[name] = _parse_instruction(line, at)
            if rows[name][0] in CONTAINER_OPCODES:
                containers.append(name)
                queue += rows[name][3]
                for called in rows[name][3]:
                    if computations.get(called):
                        returns_to[computations[called][-1][0]] = name
    users_in = {}
    for computation, rows in parsed.items():
        users = users_in[computation] = {}
        for name, (_, _, operands, _) in rows.items():
            for operand in operands:
                users.setdefault(operand, []).append(name)
    inherited: Dict[str, List[str]] = {}

    def stack_of(name):
        return names.get(name) or inherited.get(name, (None,))[0]

    def beyond(rows, users, name, forward, seen):
        """The stacks of ``name``'s users (``forward``) or of its
        operands' producers, past the instructions that only pass a
        value on; a computation's root is used by its container."""
        found = []
        if forward and stack_of(returns_to.get(name)):
            found.append(stack_of(returns_to[name]))
        for other in users.get(name, ()) if forward else rows[name][2]:
            if other in seen or other not in rows:
                continue
            seen.add(other)
            if rows[other][0] in _VIEWS:
                found += beyond(rows, users, other, forward, seen)
            elif stack_of(other):
                found.append(stack_of(other))
        return found

    body_stacks: Dict[str, Optional[str]] = {}

    def body_stack(fused):
        """The stack of a fused computation's root or, where the root
        is the compiler's own (a convert, a copy of the result), what
        the nearest named instructions it is made from share; else
        what the whole body's stacks share.  (The same in every
        round: it reads the text's own stacks alone.)"""
        if fused not in body_stacks:
            body_stacks[fused] = fused_stack(computations.get(fused))
        return body_stacks[fused]

    def fused_stack(body):
        if not body:
            return None
        lines = {name: (line, at) for name, line, at in body}
        level, seen = [body[-1][0]], set()
        while level:
            stacks = [names[n] for n in level if n in names]
            if stacks:
                found = _common_stack(stacks)
                if found:
                    return found
                break
            fresh = [n for n in level if n in lines and n not in seen]
            seen.update(fresh)
            level = [
                operand for n in fresh
                for operand in _parse_instruction(*lines[n])[2]
            ]
        stacks = [names[n] for n in lines if n in names]
        return _common_stack(stacks) if stacks else None

    # (computation, instruction) of what still has no stack
    pending = [
        (computation, name) for computation, rows in parsed.items()
        for name, row in rows.items()
        if row[0] not in _NOT_RUN and name not in names
    ]

    def one_round(rules):
        left = []
        for computation, name in pending:
            rows, users = parsed[computation], users_in[computation]
            opcode, _, operands, called = rows[name]
            for rule in rules:
                stack = None
                if rule == "start" and opcode.endswith("-done"):
                    stack = operands and stack_of(operands[0])
                elif rule == "start" and opcode.endswith("-start"):
                    stack = next((
                        stack_of(u) for u in users.get(name, ())
                        if rows[u][0].endswith("-done") and stack_of(u)
                    ), None)
                elif rule == "body" and opcode == "fusion":
                    stack = called and body_stack(called[0])
                elif rule in ("user", "operand"):
                    stacks = beyond(
                        rows, users, name, rule == "user", {name}
                    )
                    stack = stacks and _common_stack(stacks)
                if stack:
                    inherited[name] = [stack, rule]
                    break
            else:
                left.append((computation, name))
        took = len(pending) - len(left)
        pending[:] = left
        return took

    # the users' word counts before the operands': a copy made to
    # feed a kernel belongs to that kernel's layer
    while one_round(("start", "body", "user")) or one_round(
        ("operand",)
    ):
        pass
    unnamed = {}
    for computation, name in pending:
        opcode, shape = parsed[computation][name][:2]
        unnamed[name] = f"{opcode} {_LAYOUT.sub('', shape)}"
    return inherited, containers, unnamed


def op_names(hlo_text: str) -> Dict[str, Any]:
    """What a device trace, which names an operation by its HLO
    instruction alone, is joined with.  Of an optimized module's text:

    ``module``      its name;
    ``op_names``    ``{instruction: jax name stack}``: each
                    instruction's ``op_name`` metadata, which holds
                    the device scopes it was lowered under
                    (``jit(step_fn)/optimizer/mul``);
    ``inherited``   ``{instruction: [stack, rule]}`` for the
                    instructions WITHOUT metadata that run as
                    operations of their own (the entry computation's
                    and those of every computation a ``while``,
                    ``conditional`` or ``call`` runs; not a fusion's
                    body, not a reducer).  Rules, in this order, to a
                    fixed point: ``start``, the two halves of a
                    ``*-start`` / ``*-done`` pair are one operation
                    and share what either has; ``body``, a fusion
                    takes its fused computation's root's stack, else
                    that of what the root is made from, else what its
                    body's stacks share; ``user``, what the
                    stacks of its users share, past
                    ``get-tuple-element``, ``bitcast`` and ``tuple``
                    (a computation's root is used by its container),
                    where that holds a component below the jit's
                    root; ``operand``, where a round of those three
                    names nothing more, the same over its operands'
                    producers;
    ``containers``  the ``while`` / ``conditional`` / ``call``
                    instructions: a reader counts their bodies, not
                    them a second time;
    ``unnamed``     ``{instruction: "<opcode> <shape>"}``: what no
                    rule reached.
    """
    module = _MODULE.match(hlo_text)
    names = {}
    computations: Dict[str, List] = {}
    entry = body = None
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            header = _COMPUTATION.match(line)
            if header:
                body = computations[header.group(2)] = []
                if header.group(1):
                    entry = header.group(2)
            continue
        # the metadata follows the operands; a custom call's
        # backend_config (a kernel's whole body) comes after it
        stack = _OP_NAME.search(line)
        if stack:
            names[found.group(1)] = stack.group(1)
        if body is not None:
            body.append((found.group(1), line, found.end()))
    inherited, containers, unnamed = _inherit(
        computations, entry, names
    )
    return {
        "module": module.group(1) if module else None,
        "op_names": names,
        "inherited": inherited,
        "containers": containers,
        "unnamed": unnamed,
    }


def save_op_names(
    key: str, compiled: Any, cache_dir: Optional[str] = None,
) -> bool:
    """Write the executable's instruction -> name-stack map beside
    its entry.  A device trace names an operation by its instruction
    (``%fusion.13``) and, taken without the HLO proto, carries no
    name stack: with this map a reader can sum device time by the
    program's own scope names, which it lists under ``scopes`` (the
    trace that registered them has just run in this process).
    Written once, with the entry, on the cold path; optional like
    the entry itself."""
    from dlrover_tpu.telemetry.tracing import DEVICE_SCOPES

    path = op_names_path(key, cache_dir)
    try:
        names = op_names(compiled.as_text())
        names["scopes"] = sorted(DEVICE_SCOPES)
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w") as f:
            json.dump(names, f)
        os.replace(tmp, path)
        return True
    except Exception as e:  # noqa: BLE001 - the map is optional
        logger.warning("op-name map not written (%s): %s", path, e)
        return False


def aot_entries(cache_dir: Optional[str] = None) -> int:
    """Number of serialized executables in the cache — the AOT half
    of the compile-cache hit witness."""
    cache_dir = cache_dir or aot_cache_dir()
    try:
        return sum(
            1 for f in os.listdir(cache_dir)
            if f.endswith(ENTRY_SUFFIX)
        )
    except OSError:
        return 0


# descriptor fields that do NOT need the example avals — the label
# index validates these cheaply on the warm fast path; the aval half
# is enforced by the loaded executable's own input validation at
# first call (with _GuardedCall falling back to trace on mismatch)
_ENV_FIELDS = (
    "v", "label", "jax", "jaxlib", "platform", "devices",
    "processes", "world_size",
)


def _index_path(label: str, cache_dir: str) -> str:
    safe = "".join(
        c if c.isalnum() or c in "._-" else "_" for c in label
    )
    return os.path.join(cache_dir, safe + ".idx")


def _write_index(label: str, key: str, desc: Dict, cache_dir: str):
    """Label → (key, descriptor) sidecar: the warm fast path resolves
    by LABEL without re-deriving the avals (the ``eval_shape`` that
    would otherwise cost ~1 s of the recovery critical path)."""
    path = _index_path(label, cache_dir)
    try:
        fd, tmp = tempfile.mkstemp(dir=cache_dir, suffix=".idx.tmp")
        with os.fdopen(fd, "w") as f:
            json.dump({"key": key, "desc": desc}, f, default=str)
        os.replace(tmp, path)
    except OSError as e:
        logger.debug("aot index write failed (%s): %s", path, e)


def _read_index(label: str, cache_dir: str) -> Optional[Dict]:
    name = os.path.basename(_index_path(label, cache_dir))
    blob = _PRELOADED.get(name)
    if blob is None:
        try:
            with open(_index_path(label, cache_dir), "rb") as f:
                blob = f.read()
        except OSError:
            return None
    try:
        idx = json.loads(blob.decode("utf-8"))
        if not isinstance(idx.get("key"), str) or not isinstance(
            idx.get("desc"), dict
        ):
            return None
        return idx
    except (ValueError, UnicodeDecodeError):
        return None


def env_desc() -> Dict:
    """The aval-free half of :func:`describe` — everything cheap to
    compute on the warm fast path (backend init is the only cost)."""
    full = describe((), label="")
    return {
        k: full[k] for k in _ENV_FIELDS if k not in ("label",)
    }


def save_entry(
    key: str,
    desc: Dict,
    compiled: Any,
    cache_dir: Optional[str] = None,
) -> bool:
    """Serialize ``compiled`` (a ``Lowered.compile()`` result) under
    ``key``.  Atomic (tmp + rename) and non-fatal: any failure logs
    and returns False — the next incarnation traces, nothing worse."""
    # imported here, not at the top: the agent and the forkserver
    # template import this module for its paths and byte preloads
    from jax.experimental.serialize_executable import serialize

    cache_dir = cache_dir or aot_cache_dir()
    path = entry_path(key, cache_dir)
    try:
        payload, in_tree, out_tree = serialize(compiled)
        blob = pickle.dumps({
            "v": _ENTRY_VERSION,
            "desc": desc,
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
        })
        os.makedirs(cache_dir, exist_ok=True)
        fd, tmp = tempfile.mkstemp(
            dir=cache_dir, suffix=ENTRY_SUFFIX + ".tmp"
        )
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return True
    except Exception as e:  # noqa: BLE001 - cache write is optional
        logger.warning("aot cache write failed (%s): %s", path, e)
        return False


def load_entry(
    key: str,
    desc: Dict,
    cache_dir: Optional[str] = None,
    timings: Optional[Dict[str, float]] = None,
) -> Optional[Any]:
    """Deserialize the entry under ``key`` into a ready-to-call
    loaded executable, or None on ANY problem (absent, corrupt,
    descriptor mismatch, unknown pytree nodes, deserializer error) —
    the caller falls back to tracing.  ``timings`` (optional dict)
    receives the read/unpickle/deserialize breakdown."""
    from jax.experimental.serialize_executable import (
        deserialize_and_load,
    )

    cache_dir = cache_dir or aot_cache_dir()
    name = key + ENTRY_SUFFIX
    t0 = time.perf_counter()
    blob = _PRELOADED.get(name)
    if blob is None:
        try:
            with open(entry_path(key, cache_dir), "rb") as f:
                blob = f.read()
        except OSError:
            return None
    if timings is not None:
        timings["read_s"] = time.perf_counter() - t0
    try:
        t0 = time.perf_counter()
        entry = pickle.loads(blob)
        if timings is not None:
            timings["unpickle_s"] = time.perf_counter() - t0
        if entry.get("v") != _ENTRY_VERSION:
            return None
        if entry.get("desc") != desc:
            # filename collisions are cryptographically unlikely; a
            # mismatch here means a hand-copied or stale entry — the
            # binary must not run against the wrong avals/topology
            return None
        t0 = time.perf_counter()
        c0 = time.thread_time()
        loaded = deserialize_and_load(
            entry["payload"], entry["in_tree"], entry["out_tree"]
        )
        if timings is not None:
            timings["deserialize_s"] = time.perf_counter() - t0
            # wall ≫ cpu here means the deserialize was CPU-starved
            # by the rest of the recovery, not slow by itself
            timings["deserialize_cpu_s"] = time.thread_time() - c0
        return loaded
    except Exception as e:  # noqa: BLE001 - strict fall-back-to-trace
        logger.warning("aot cache entry %s unusable: %s", name, e)
        return None


def preload_entries(
    cache_dir: Optional[str] = None,
    max_bytes: int = 512 * 2**20,
) -> Tuple[int, int]:
    """Read every entry's BYTES into module memory (forkserver
    template path: forked workers inherit the buffers and skip the
    disk read).  Incremental — already-preloaded names are skipped,
    so the template can re-scan cheaply before every fork and pick up
    the entry the PREVIOUS incarnation wrote.  Bounded by
    ``max_bytes`` total; returns ``(new_entries, new_bytes)``.
    Never raises and never touches jax — the template must not
    initialize an XLA client."""
    cache_dir = cache_dir or aot_cache_dir()
    count = total = 0
    try:
        names = sorted(os.listdir(cache_dir))
    except OSError:
        return (0, 0)
    for name in names:
        if not name.endswith((ENTRY_SUFFIX, ".idx")):
            continue
        if name.endswith(ENTRY_SUFFIX) and name in _PRELOADED:
            # entries are content-keyed and immutable: cache by name.
            # Index files are MUTATED in place (os.replace on every
            # miss) — always re-read them, or a resize/retrace would
            # leave every later fork resolving through stale bytes
            continue
        try:
            with open(os.path.join(cache_dir, name), "rb") as f:
                blob = f.read(max_bytes - total + 1)
        except OSError:
            continue
        if total + len(blob) > max_bytes:
            logger.warning(
                "aot preload budget (%d MB) reached; %s and later "
                "entries stay on disk", max_bytes >> 20, name,
            )
            break
        _PRELOADED[name] = blob
        count += 1
        total += len(blob)
    return (count, total)


def preloaded_entries() -> int:
    """How many entries the template preloaded (inherited over
    fork) — the pre-trace path's witness."""
    return len(_PRELOADED)


def pretrace_enabled() -> bool:
    return os.getenv(AOT_PRETRACE_ENV, "").strip().lower() in (
        "1", "true", "yes", "on",
    )


@dataclass
class Resolution:
    """What :func:`resolve_step` decided.

    ``fn`` is always callable with the original arguments.  ``source``
    is ``"aot"`` (deserialized executable — no trace anywhere),
    ``"trace"`` (traced+compiled, either eagerly inside the resolve
    when ``deferred`` is False, or at first call when True) or
    ``"off"`` (the resolve could not run — plain jit semantics)."""

    fn: Any
    source: str
    key: str = ""
    dir: str = ""
    hit: bool = False
    wrote: bool = False
    deferred: bool = False
    load_s: float = 0.0
    trace_s: float = 0.0
    save_s: float = 0.0
    reason: str = ""
    preloaded: bool = False
    extra: Dict = field(default_factory=dict)


class _GuardedCall:
    """First-call safety net over a deserialized executable: if the
    very first invocation fails (an aval drift the key missed, a
    backend refusing the binary), fall back to the original traced
    path PERMANENTLY instead of crashing the recovery.  After one
    success the guard is a single attribute check per step."""

    __slots__ = ("_primary", "_fallback", "_proven")

    def __init__(self, primary, fallback):
        self._primary = primary
        self._fallback = fallback
        self._proven = False

    def __call__(self, *args, **kwargs):
        if self._primary is None:
            return self._fallback(*args, **kwargs)
        try:
            out = self._primary(*args, **kwargs)
            self._proven = True
            return out
        except Exception as e:  # noqa: BLE001 - never crash recovery
            if self._proven:
                raise  # a mid-training failure is not a cache problem
            logger.warning(
                "aot executable rejected at first call (%s); "
                "falling back to trace", e,
            )
            self._primary = None
            return self._fallback(*args, **kwargs)


# XLA orders a WHOLE module by one of three schedulers (list, dfs,
# post order), whichever ESTIMATES the least peak memory.  The
# estimate is no buffer assignment (what a ``while`` carries counts in
# the caller and again in the body; no fragmentation), and dfs and
# post order leave every weight gradient of a scan's body to the
# body's end, each holding its block's activations until then.  Every
# step of the benchmark is ordered by list (OLMoE's by 1.4% of the
# estimate) but Ouro's, which lost the draw when its passes' scans
# carried 2.4 GB more: 9.63 GB reserved for 6.99, 27 ms a step
# (PERF.md section 6, PR 45).  So the order is asked for by name.
COMPILER_OPTIONS = {"xla_memory_scheduler": "list"}


def compile_lowered(lowered: Any) -> Any:
    """``lowered.compile()`` under :data:`COMPILER_OPTIONS`; a backend
    that has no such option (the CPU's) compiles as it would."""
    try:
        return lowered.compile(compiler_options=COMPILER_OPTIONS)
    except Exception as e:  # noqa: BLE001 - jax's runtime error type
        if "No such compile option" not in str(e):
            raise
        return lowered.compile()


def resolve_step(
    fn: Any,
    example_args,
    label: str = "step",
    cache_dir: Optional[str] = None,
) -> Resolution:
    """Resolve a jitted step function through the AOT cache.

    ``fn`` is the ``jax.jit`` wrapper (anything with ``.lower``);
    ``example_args`` the positional tuple it will be called with
    (concrete arrays or ``ShapeDtypeStruct`` trees) — or a ZERO-ARG
    CALLABLE returning that tuple, which arms the warm fast path:
    the label index resolves straight to an entry, the aval-free
    descriptor fields are validated, and the example build (the
    ``eval_shape`` that costs real critical-path time in a respawn)
    never runs; the aval half of the key is enforced by the loaded
    executable's own input validation at first call, with
    :class:`_GuardedCall` falling back to trace on mismatch.

    HIT: returns the deserialized executable (guarded).  MISS:
    traces+compiles NOW (``trace_s`` is the measured retrace) and
    WRITES the entry + label index so incarnation N+1 hits.
    Off/error: returns ``fn`` untouched with ``deferred=True`` — the
    first call traces exactly as without this module."""
    cache_dir = cache_dir or aot_cache_dir()
    if callable(example_args) and not isinstance(
        example_args, (list, tuple)
    ):
        builder = example_args
        fast = _resolve_fast(fn, label, cache_dir)
        if fast is not None:
            return fast
        try:
            example_args = builder()
        except Exception as e:  # noqa: BLE001 - builder failed
            return Resolution(
                fn=fn, source="off", deferred=True, dir=cache_dir,
                reason=f"example builder failed: {e}",
            )
    try:
        desc = describe(example_args, label=label, fn=fn)
        key = key_of(desc)
    except Exception as e:  # noqa: BLE001 - odd example trees
        return Resolution(
            fn=fn, source="off", deferred=True, dir=cache_dir,
            reason=f"descriptor failed: {e}",
        )
    preloaded = (key + ENTRY_SUFFIX) in _PRELOADED
    t0 = time.perf_counter()
    loaded = load_entry(key, desc, cache_dir)
    load_s = time.perf_counter() - t0
    if loaded is not None:
        return Resolution(
            fn=_GuardedCall(loaded, fn), source="aot", key=key,
            dir=cache_dir, hit=True, load_s=load_s,
            preloaded=preloaded,
        )
    if not hasattr(fn, "lower"):
        return Resolution(
            fn=fn, source="off", key=key, dir=cache_dir,
            deferred=True, load_s=load_s,
            reason="fn has no .lower (not a jit wrapper)",
        )
    try:
        t0 = time.perf_counter()
        compiled = compile_lowered(fn.lower(*example_args))
        trace_s = time.perf_counter() - t0
    except Exception as e:  # noqa: BLE001 - abstract lowering failed
        return Resolution(
            fn=fn, source="trace", key=key, dir=cache_dir,
            deferred=True, load_s=load_s,
            reason=f"lower/compile failed: {e}",
        )
    t0 = time.perf_counter()
    wrote = save_entry(key, desc, compiled, cache_dir)
    if wrote:
        _write_index(label, key, desc, cache_dir)
        save_op_names(key, compiled, cache_dir)
    save_s = time.perf_counter() - t0
    return Resolution(
        # guarded like the hit path: the compile ran against the
        # ABSTRACT examples — if the real first-call avals drift from
        # them, fall back to the plain jit (which traces against the
        # actual arguments) instead of crashing the cold recovery
        fn=_GuardedCall(compiled, fn), source="trace", key=key,
        dir=cache_dir, wrote=wrote, load_s=load_s, trace_s=trace_s,
        save_s=save_s,
    )


def _resolve_fast(
    fn: Any, label: str, cache_dir: str
) -> Optional[Resolution]:
    """The warm fast path: label index → entry, no example build.
    Returns None when anything falls short (no index, env drift,
    unusable entry) — the caller runs the full keyed path."""
    idx = _read_index(label, cache_dir)
    if idx is None:
        return None
    try:
        env = env_desc()
    except Exception:  # noqa: BLE001 - no backend yet / odd jax
        return None
    desc = idx["desc"]
    if desc.get("label") != label:
        return None
    if desc.get("fn") != fn_fingerprint(fn):
        # the code changed since the entry was written: the binary
        # must not run, however well the avals would have matched
        return None
    for field_name in _ENV_FIELDS:
        if field_name == "label":
            continue
        if desc.get(field_name) != env.get(field_name):
            return None
    t0 = time.perf_counter()
    timings: Dict[str, float] = {}
    loaded = load_entry(idx["key"], desc, cache_dir, timings=timings)
    load_s = time.perf_counter() - t0
    if loaded is None:
        return None
    return Resolution(
        fn=_GuardedCall(loaded, fn), source="aot", key=idx["key"],
        dir=cache_dir, hit=True, load_s=load_s,
        preloaded=(idx["key"] + ENTRY_SUFFIX) in _PRELOADED,
        extra={"fast": True, **timings},
    )
