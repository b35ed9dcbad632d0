"""The rule of the step files (tests/v5e_steps.py's header, ROADMAP D8 rule
(a)), held for the next model's file too: a case of a
`tests/test_*_step(s)_compile.py` file that is not marked `slow` reaches no
full-width COMPILE. Read from the files' sources: a function that is not
marked slow neither reads what `Step` makes of its compiled step (`.compiled`,
`.hlo`, `.memory`, `.kernels`, `.computations`, `.op_names`), nor asks
`has_scope` without `lowered=True`, nor calls a helper that does."""

import ast
import glob
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
STEP_FILES = sorted(os.path.basename(p) for p in
                    glob.glob(os.path.join(HERE, "test_*_step_compile.py"))
                    + glob.glob(os.path.join(HERE, "test_*_steps_compile.py")))
# what `Step` makes from `Step.compiled`, and v5e_steps' helpers that are handed a compiled text
OF_THE_COMPILE = {"compiled", "hlo", "memory", "kernels", "computations", "op_names"}
READ_THE_COMPILE = {"scopes_lost", "matmul_tiles", "called_from"}


def _is_slow(function: ast.FunctionDef) -> bool:
    return any(ast.unparse(d) == "pytest.mark.slow" for d in function.decorator_list)


def _compiles(node: ast.AST) -> list:
    """What under `node` reaches the compiled step, as the source spells it."""
    found = []
    for at in ast.walk(node):
        if isinstance(at, ast.Attribute) and at.attr in OF_THE_COMPILE:
            found.append(f"line {at.lineno}: .{at.attr}")
        if isinstance(at, ast.Call):
            name = getattr(at.func, "attr", getattr(at.func, "id", None))
            lowered = [k for k in at.keywords if k.arg == "lowered"]
            if name == "has_scope" and not (lowered and ast.unparse(lowered[0].value) == "True"):
                found.append(f"line {at.lineno}: has_scope(..) without lowered=True")
            if name in READ_THE_COMPILE:
                found.append(f"line {at.lineno}: {name}(..)")
    return found


def _functions(source: str) -> dict:
    return {f.name: f for f in ast.parse(source).body if isinstance(f, ast.FunctionDef)}


def cases_that_compile(source: str) -> dict:
    """{a function that is not marked slow: what of the compile it reaches},
    a helper's reach counted for whoever calls it."""
    functions = _functions(source)
    reach = {name: _compiles(f) for name, f in functions.items()}
    moved = True
    while moved:   # a helper that calls a helper
        moved = False
        for name, f in functions.items():
            for at in ast.walk(f):
                callee = getattr(at.func, "id", None) if isinstance(at, ast.Call) else None
                via = f"line {at.lineno}: {callee}(), which compiles" if callee else None
                if callee in reach and reach[callee] and callee != name and via not in reach[name]:
                    reach[name].append(via)
                    moved = True
    return {name: found for name, found in reach.items()
            if found and name.startswith("test_") and not _is_slow(functions[name])}


def test_the_seven_step_files_are_found():
    assert len(STEP_FILES) >= 7 and "test_m7b_steps_compile.py" in STEP_FILES
    assert "test_kimi_linear_step_compile.py" in STEP_FILES


@pytest.mark.parametrize("file", STEP_FILES)
def test_no_case_of_the_lane_reaches_a_full_width_compile(file):
    with open(os.path.join(HERE, file)) as f:
        source = f.read()
    assert cases_that_compile(source) == {}
    # and what only a compile shows is not dropped: the file has its slow cases
    slow = [name for name, f in _functions(source).items()
            if name.startswith("test_") and _is_slow(f)]
    assert slow, "no case marked slow: what only the compile shows is held nowhere"


@pytest.mark.parametrize("body,reached", [
    ("step.hlo", True), ("step.memory.temp_size_in_bytes", True), ("step.kernels", True),
    ("step.has_scope('optim')", True), ("step.has_scope('optim', lowered=False)", True),
    ("helper(step)", True), ("outer(step)", True),
    ("step.has_scope('optim', lowered=True)", False), ("step.lowered_kernels", False),
    ("step.argument_bytes", False), ("step.lowered_op_names", False),
], ids=lambda v: str(v).replace(" ", ""))
def test_the_reader_tells_a_lane_case_that_compiles_from_one_that_does_not(body, reached):
    source = ("def helper(step):\n    return scopes_lost(step, S)\n"
              "def outer(step):\n    return helper(step)\n"
              f"def test_lane(v5e):\n    step = train_step(v5e)\n    assert {body}\n"
              f"@pytest.mark.slow\ndef test_slow(v5e):\n    assert {body}\n")
    assert set(cases_that_compile(source)) == ({"test_lane"} if reached else set())
