"""Pass 4: dtype-packing lint over the step jaxpr and the grid wrappers.

The scan carry is deliberately packed (DESIGN.md "Macro-stepping &
state packing"): categorical columns in int8, barrier counts in int16,
time columns pinned to the time words of ``engine.timebase`` (the one
definition of the time type; this pass reads it from there).  Three
silent regressions this pass catches statically:

  * **packed-column widening** — an init or handler change that
    promotes ``state``/``owner``/... to int32 quietly triples the scan
    carry (the packing registry below is the contract; the check runs
    ``jax.eval_shape`` over a full cell so a widened carry column is
    caught wherever it happens);
  * **float64 -> float32 demotion** — the statistics accumulate
    ns-scale latencies into float64 sums; any f64->f32
    ``convert_element_type`` in the traced program quantizes them
    (the legitimate narrow directions, powers of two built from
    float32 bits widening to f64, are f32->f64 and do not match);
  * **un-donated grid buffers** — the jitted grid wrappers must donate
    the freshly-staged trace buffers (``ops``/``addrs``/``gaps``/
    ``mlen``) so XLA reuses them for the carry instead of allocating.
"""
from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from repro.analysis.common import (Finding, find_line, read_source, rel,
                                   REPO_ROOT)
from repro.core.engine.timebase import DTYPE_NAME as TIME

_STATE = REPO_ROOT / "src" / "repro" / "core" / "engine" / "state.py"
_GRID = REPO_ROOT / "src" / "repro" / "core" / "engine" / "grid.py"

# The packing contract: MachineState column -> dtype it must keep
# through a full cell run.  Mirrors the docstring table in
# engine.state.MachineState — this registry is the machine-checked
# form.
EXPECTED_DTYPES: Dict[str, str] = {
    "clock": TIME, "ptr": "int32",
    "tag": "int32", "state": "int8", "lru": TIME, "dd": TIME,
    "ver": "int32", "owner": "int8",
    "aver": "int32", "pm_ver": "int32",
    "pm_busy": TIME, "pbc_busy": TIME,
    "blocked": "bool", "bcount": "int16",
    "stats": "float64",
    "dtag": "int32", "dstate": "int8", "dlru": TIME,
    "ddd": TIME, "dver": "int32", "downer": "int8",
    "dwt": TIME, "hpbc": TIME, "hop_stats": "float64",
    "lpbc": TIME,
}

REQUIRED_DONATED = ("ops", "addrs", "gaps", "mlen")


def check_packing(shapes: Optional[Dict[str, Tuple[str, tuple]]] = None,
                  expected: Optional[Dict[str, str]] = None,
                  anchor_file: Optional[Path] = None) -> List[Finding]:
    """Diff actual carry dtypes against the packing registry."""
    if shapes is None:
        from repro.analysis._engine import final_state_shapes
        shapes = final_state_shapes()
    expected = EXPECTED_DTYPES if expected is None else expected
    anchor_file = _STATE if anchor_file is None else anchor_file
    _, lines = read_source(anchor_file)
    findings = []
    for col, want in expected.items():
        got = shapes.get(col)
        line = find_line(lines, rf"^\s*{col}\s*[:=]") or 1
        if got is None:
            findings.append(Finding(
                file=rel(anchor_file), line=line, rule="dtype-packing",
                message=f"carry column {col!r} is registered but absent "
                        "from the traced state",
                suggestion="update EXPECTED_DTYPES in "
                           "repro.analysis.dtypes"))
            continue
        if got[0] != want:
            findings.append(Finding(
                file=rel(anchor_file), line=line, rule="dtype-packing",
                message=f"carry column {col!r} is {got[0]} after a full "
                        f"cell run; the packing contract pins {want}",
                suggestion="keep literal compares/selects weakly typed "
                           "so the packed dtype survives the handlers"))
    for col in sorted(set(shapes) - set(expected)):
        line = find_line(lines, rf"^\s*{col}\s*[:=]") or 1
        findings.append(Finding(
            file=rel(anchor_file), line=line, rule="dtype-packing",
            message=f"carry column {col!r} is not in the packing "
                    "registry",
            suggestion="register its dtype in EXPECTED_DTYPES "
                       "(repro.analysis.dtypes)"))
    return findings


def _walk_eqns(jaxpr):
    """Yield every eqn of a jaxpr, recursing into sub-jaxprs (scan,
    while, cond, pjit, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _sub_jaxprs(v):
                yield from _walk_eqns(sub)


def _sub_jaxprs(v):
    from jax.extend.core import ClosedJaxpr, Jaxpr
    if isinstance(v, ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, Jaxpr):
        yield v
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _sub_jaxprs(x)


def check_f32_leaks(closed=None, fn=None, args: tuple = ()
                    ) -> List[Finding]:
    """Any f64 -> f32 ``convert_element_type`` is a time-column leak."""
    import numpy as np

    if closed is None and fn is not None:
        import jax
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(fn)(*args)
    if closed is None:
        from repro.analysis._engine import trace_engine
        closed, _ = trace_engine(return_state=False)
    findings = []
    for eqn in _walk_eqns(closed.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        new = np.dtype(eqn.params.get("new_dtype"))
        old = eqn.invars[0].aval.dtype
        if old == np.float64 and new == np.float32:
            file, line = _eqn_location(eqn)
            findings.append(Finding(
                file=file, line=line, rule="dtype-f32-leak",
                message="float64 value demoted to float32 in the traced "
                        "step: latency sums quantize at ~100 ns at "
                        "clock scale",
                suggestion="keep time in time words and statistics in "
                           "f64 (widen the f32 operand instead)"))
    return findings


def _eqn_location(eqn) -> Tuple[str, int]:
    from jax._src import source_info_util
    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is not None:
        return rel(frame.file_name), frame.start_line
    return "<traced>", 0


def check_donation(path: Optional[Path] = None,
                   required: tuple = REQUIRED_DONATED) -> List[Finding]:
    """The grid's donation tuple must cover the staged trace buffers and
    every jitted wrapper must pass it."""
    path = _GRID if path is None else path
    text, lines = read_source(path)
    tree = ast.parse(text)
    findings = []
    donated: set = set()
    donated_line = 1
    for node in tree.body:
        if (isinstance(node, ast.Assign)
                and any(isinstance(t, ast.Name) and t.id == "_DONATED"
                        for t in node.targets)
                and isinstance(node.value, (ast.Tuple, ast.List))):
            donated = {e.value for e in node.value.elts
                       if isinstance(e, ast.Constant)}
            donated_line = node.lineno
    missing = sorted(set(required) - donated)
    if missing:
        findings.append(Finding(
            file=rel(path), line=donated_line, rule="dtype-undonated",
            message=f"_DONATED misses staged buffer(s) "
                    f"{', '.join(missing)}: XLA re-allocates instead of "
                    "reusing them for the scan carry",
            suggestion="add the buffer name(s) to _DONATED"))
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        for dec in node.decorator_list:
            if not (isinstance(dec, ast.Call) and _is_jit_partial(dec)):
                continue
            kwargs = {kw.arg for kw in dec.keywords}
            if "donate_argnames" not in kwargs:
                findings.append(Finding(
                    file=rel(path), line=dec.lineno,
                    rule="dtype-undonated",
                    message=f"jitted wrapper {node.name} does not "
                            "donate its input buffers",
                    suggestion="pass donate_argnames=_DONATED to the "
                               "jit partial"))
    return findings


def _is_jit_partial(call: ast.Call) -> bool:
    """Matches ``functools.partial(jax.jit, ...)`` / ``partial(jit,
    ...)`` decorator calls."""
    f = call.func
    is_partial = (isinstance(f, ast.Attribute) and f.attr == "partial") \
        or (isinstance(f, ast.Name) and f.id == "partial")
    if not is_partial or not call.args:
        return False
    a0 = call.args[0]
    return (isinstance(a0, ast.Attribute) and a0.attr == "jit") \
        or (isinstance(a0, ast.Name) and a0.id == "jit")


def check() -> List[Finding]:
    return check_packing() + check_f32_leaks() + check_donation()
