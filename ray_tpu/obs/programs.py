"""The program's record of a compiled program it runs: what is needed to
find that program again, and what the compiler says of it.

A profiler trace names a device operation as XLA does (`fusion.166`,
`closed_call.7`); which block of the model it belongs to stands in the
compiled module's text alone, in each instruction's `op_name`
(`jit(step)/.../dense.ffn/dot_general`: the `jax.named_scope`s it was
traced under). `note(name, jitted)` wraps a jitted function in a
callable that keeps, at its FIRST call, the shapes, dtypes and shardings
of its arguments (abstract values: no array is kept alive, nothing is
lowered). Only when asked (`op_names`) is the function lowered and
compiled at those values, once, through the persistent cache (the
program that ran is loaded, not built again), and the result kept.

The wrapper is also the one place every call of the program passes, so
it is where the HOST's part of a call is timed: each call runs under
the layer span of the noted name (`train.step`).
"""

from __future__ import annotations

import re
import threading
from typing import Any, Optional

from ray_tpu.obs.recorder import layer_span

_NOTED: dict = {}  # name -> the newest NotedProgram noted under it


class NotedProgram:
    """`jitted` with its first call's abstract arguments noted. Calls,
    `.lower` and every other attribute are the jitted function's own.

    A call runs under the layer span `name`: the HOST's part of it, the
    arguments flattened and the program dispatched (and, at a first
    call, traced, compiled or loaded). On an asynchronous backend the
    span ends when the program is enqueued, long before the device is
    done: it is no measure of the step, only of what the caller's
    thread paid to start it."""

    def __init__(self, jitted, name: str):
        self._jitted = jitted
        self._name = name
        self._abstract: Optional[tuple] = None
        self._compiled = None
        self._lock = threading.Lock()  # two readers, one compile

    def __call__(self, *args):
        if self._abstract is None:  # all that a call pays
            self._abstract = _abstract_values(args)
        with layer_span(self._name):
            return self._jitted(*args)

    def __getattr__(self, attr: str) -> Any:
        jitted = self.__dict__.get("_jitted")  # absent while copy or pickle rebuild the object
        if jitted is None:
            raise AttributeError(attr)
        return getattr(jitted, attr)

    def compiled(self):
        """The compiled program of the first call's shapes; None before
        any call. One lowering and one compile (a load from the
        persistent cache where the call itself stored it), then kept."""
        with self._lock:
            if self._compiled is None and self._abstract is not None:
                self._compiled = self._jitted.lower(*self._abstract).compile()
            return self._compiled


def _abstract_values(args: tuple) -> tuple:
    import jax
    import jax.numpy as jnp

    def one(x):
        # an array nobody placed goes where the computation goes, on the
        # second lowering as on the first (a tracer, where the step is
        # called inside another program, has no placement to ask for)
        placed = None
        if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer) and x.committed:
            placed = x.sharding
        return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x), sharding=placed)

    return jax.tree.map(one, args)


def note(name: str, jitted) -> NotedProgram:
    """Note `jitted` as this process's newest program of that name."""
    noted = _NOTED[name] = NotedProgram(jitted, name)
    return noted


def op_names(name: str = "train.step") -> Optional[dict]:
    """{instruction: [(opcode, op_name path, operands), ...]} for every
    instruction of the noted program's compiled module, whichever
    computation holds it (the entry, a `while`'s body, a call's): first
    the instruction's own, then, for a fusion, those of the instructions
    of its fused computation. A Pallas kernel stands under its
    instruction's name (a trace writes `kernel:<instruction>`). The path
    is "" where the compiler made the instruction itself (a convert
    hoisted out of a layer scan, a layout copy, a zero fill); `operands`
    are the instructions it reads, seen through a loop's tuple: an
    element a `while`'s body takes from its parameter reads what the
    loop was given for it. What such an instruction belongs to is the
    reader's to say. None where no program of that name has run."""
    noted = _NOTED.get(name)
    compiled = None if noted is None else noted.compiled()
    return None if compiled is None else parse_op_names(compiled.as_text())


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"(?:^|[\s)])([a-z][\w\-]*)\(([^)]*)\)")
_OP_NAME = re.compile(r"op_name=\"([^\"]*)\"")
_CALLED = re.compile(r"\b(calls|body|to_apply)=%?([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
_REFERENCE = re.compile(r"%([\w.\-]+)")


def parse_op_names(hlo_text: str) -> dict:
    """`op_names` of a compiled module's text."""
    by_computation: dict = {}   # computation -> [instruction] in the text's order
    own: dict = {}              # instruction -> [opcode, path, operands]
    called: dict = {}           # instruction -> the computation it calls
    numbered: dict = {}         # a get-tuple-element -> its index, a parameter -> its number
    inside = None
    for line in hlo_text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            inside = by_computation.setdefault(m.group(1), []) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or inside is None:
            continue
        instruction, rest = m.groups()
        op = _OPCODE.search(rest)
        path = _OP_NAME.search(rest)
        opcode, operands = (op.group(1), tuple(_REFERENCE.findall(op.group(2)))) if op else ("", ())
        own[instruction] = [opcode, path.group(1) if path else "", operands]
        inside.append(instruction)
        calls = _CALLED.search(rest)
        if calls and opcode in ("fusion", "while", "call"):
            called[instruction] = calls.group(2)
        if opcode == "get-tuple-element":
            numbered[instruction] = int(_INDEX.search(rest).group(1))
        elif opcode == "parameter":
            numbered[instruction] = int(op.group(2))
    # a called computation's parameter reads what its caller was given
    for instruction, computation in called.items():
        opcode, _, given = own[instruction]
        if opcode == "fusion":
            continue
        for inner in by_computation.get(computation, ()):
            if own[inner][0] == "parameter" and numbered[inner] < len(given):
                own[inner][2] = (given[numbered[inner]],)
    # and an element taken from a tuple reads that element alone
    for instruction, index in numbered.items():
        if own[instruction][0] != "get-tuple-element" or not own[instruction][2]:
            continue
        source = own[instruction][2][0]
        while source in own and own[source][0] == "parameter" and own[source][2]:
            source = own[source][2][0]  # a loop's body: its parameter is the loop's tuple
        if source in own and own[source][0] == "tuple" and index < len(own[source][2]):
            own[instruction][2] = (own[source][2][index],)
    out = {instruction: [tuple(entry)] for instruction, entry in own.items()}
    for instruction, computation in called.items():
        if own[instruction][0] == "fusion":
            out[instruction].extend(tuple(own[i]) for i in by_computation.get(computation, ()))
    return out
