#!/usr/bin/env python
"""fflint — project-level AST lints distilled from real shipped bugs.

Each rule encodes a bug class this repo actually shipped and fixed; the
linter makes the fix mechanical instead of tribal knowledge. Stdlib
only (ast) so CI can run it before any heavy install.

Rules
-----
FFL001  bare `except:`
        Swallows KeyboardInterrupt/SystemExit too. Never shipped here,
        banned so it never is.
FFL002  silent `except Exception` (handler body is only pass/continue)
        Historical: silent except-Exception blocks in the checkpoint
        restore path masked corrupted tensors until PR 3 narrowed them
        to typed exceptions with logged warnings. A handler must raise,
        log, warn, or produce a fallback value — not just swallow.
FFL101  `np.asarray(jax.device_get(...))` (or np.array without copy)
        Historical: on the CPU backend device_get returns a ZERO-COPY
        view into the live buffer; with donated train steps the next
        dispatch reuses that memory and the "snapshot" silently mutates
        — PR 2's checkpoint-corruption bug. Use
        `np.array(..., copy=True)` (or `.copy()`).
FFL102  reuse of a donated state after a donated step call
        Historical: the same PR 2 class — a variable passed into a
        `build_train_step()` callable (donating by default) is dead
        after the call; reading it again observes reused buffers.
        Rebind it from the step's return value first. The decode step
        is the same: `init, step = ....build_decode(...)` consumes what
        it is handed as `caches` (its second argument), on the chip
        where nobody is watching; `logits, caches = step(params,
        caches, ...)` rebinds. A step kept on `self` is followed
        through the module.
FFL103  host-sync call inside a step-path function of parallel/ or
        kernels/ modules
        The per-step dispatch path (the traced `step`/`loss_of`/...
        closures and the `*_kernel` bodies) must never synchronize with
        the host: `block_until_ready` / `jax.device_get` stall the
        async dispatch queue (the Perfetto traces show the step pipeline
        draining), and `np.asarray`/`np.array` on a traced value either
        raises under jit or, on concrete per-step values, forces a
        device->host round-trip per step. Hoist host reads out of the
        step path, or pragma genuinely host-side helpers.
FFL301  float64 creep inside a step-path function of parallel/ or
        kernels/ modules
        An `np.float64`/`jnp.float64` reference, a `dtype="float64"`
        keyword, or a dtype-less `np.array(...)` (which defaults to
        float64 for Python floats) inside the traced per-step closures
        silently widens the whole downstream flow to fp64 — the TPU
        has no fp64 MXU path, so XLA either software-emulates it
        (order-of-magnitude slowdown) or demotes it, and either way the
        static precision story (analysis/precision.py FFA7xx) no longer
        matches the executed math. Pin an explicit narrow dtype, or
        pragma genuinely host-side float64 math (e.g. accumulating
        telemetry counters).
FFL201  bare `print()` inside flexflow_tpu/ library code
        Historical: fit/eval reported progress via bare print()s —
        invisible to telemetry, unredirectable, and uncapturable. Route
        output through the structured sink (flexflow_tpu.obs.progress:
        same human-readable line, plus a structured event when a
        telemetry session is active). Only applies to files under a
        `flexflow_tpu` package directory; `__main__.py` CLI modules are
        allowlisted automatically, other CLI entry points via the
        file-level pragma below.

Suppression: append `# fflint: disable=FFL002` (comma-list) to the
offending line (for except-handlers: to the `except` line). A module
whose job is terminal output (CLIs, debug dumpers) can opt out of a
rule wholesale with `# fflint: disable-file=FFL201` on any line.

Usage:  python tools/fflint.py [--list-rules] PATH [PATH...]
Exit codes: 0 clean, 1 findings, 2 usage error.
"""
from __future__ import annotations

import argparse
import ast
import os
import re
import sys
from typing import Dict, List, Set

RULES = {
    "FFL001": "bare `except:` clause",
    "FFL002": "silent `except Exception:` handler (body only "
              "pass/continue)",
    "FFL101": "np.asarray/np.array without copy=True on "
              "jax.device_get(...) output",
    "FFL102": "donated input of a train step (its state) or a decode step "
              "(its caches) read again after the step call",
    "FFL103": "host-sync call (block_until_ready / jax.device_get / "
              "np.asarray) inside a step-path function of parallel/ or "
              "kernels/",
    "FFL201": "bare print() in flexflow_tpu/ library code (use "
              "flexflow_tpu.obs.progress; __main__ modules exempt)",
    "FFL301": "float64 creep (np.float64 / dtype='float64' / dtype-less "
              "np.array) inside a step-path function of parallel/ or "
              "kernels/",
}

_PRAGMA = re.compile(r"#\s*fflint:\s*disable=([A-Z0-9,\s]+)")
_FILE_PRAGMA = re.compile(r"#\s*fflint:\s*disable-file=([A-Z0-9,\s]+)")


class Finding:
    def __init__(self, path: str, line: int, col: int, code: str, msg: str):
        self.path, self.line, self.col = path, line, col
        self.code, self.msg = code, msg

    def format(self) -> str:
        return f"{self.path}:{self.line}:{self.col}: {self.code} {self.msg}"


def _pragmas(source: str) -> Dict[int, Set[str]]:
    out: Dict[int, Set[str]] = {}
    for i, text in enumerate(source.splitlines(), 1):
        m = _PRAGMA.search(text)
        if m:
            out[i] = {c.strip() for c in m.group(1).split(",") if c.strip()}
    return out


def _dotted(node: ast.AST) -> str:
    """best-effort dotted-name rendering of Name/Attribute chains."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


# ----------------------------------------------------------------------
# FFL001 / FFL002 — exception-handler rules
# ----------------------------------------------------------------------
def _check_excepts(tree: ast.AST, path: str, findings: List[Finding]) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.ExceptHandler):
            continue
        if node.type is None:
            findings.append(Finding(
                path, node.lineno, node.col_offset, "FFL001",
                "bare `except:` also catches KeyboardInterrupt/SystemExit; "
                "catch a concrete exception type",
            ))
            continue
        names = []
        if isinstance(node.type, (ast.Name, ast.Attribute)):
            names = [_dotted(node.type)]
        elif isinstance(node.type, ast.Tuple):
            names = [_dotted(e) for e in node.type.elts]
        if not any(n in ("Exception", "BaseException") for n in names):
            continue
        if all(isinstance(s, (ast.Pass, ast.Continue)) for s in node.body):
            findings.append(Finding(
                path, node.lineno, node.col_offset, "FFL002",
                "except Exception that only swallows (pass/continue): "
                "raise a typed error, log, or produce a fallback "
                "(historical: silent restore-path excepts masked "
                "checkpoint corruption)",
            ))


# ----------------------------------------------------------------------
# FFL101 — zero-copy view of device memory
# ----------------------------------------------------------------------
def _is_device_get(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and \
        _dotted(node.func).split(".")[-1] == "device_get"


def _check_asarray(tree: ast.AST, path: str, findings: List[Finding]) -> None:
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        fn = _dotted(node.func)
        leaf = fn.split(".")[-1]
        if leaf not in ("asarray", "array") or not node.args:
            continue
        if not _is_device_get(node.args[0]):
            continue
        if leaf == "array":
            copy_kw = next((k for k in node.keywords if k.arg == "copy"),
                           None)
            if copy_kw is not None and \
                    getattr(copy_kw.value, "value", None) is True:
                continue
        findings.append(Finding(
            path, node.lineno, node.col_offset, "FFL101",
            f"{fn}(jax.device_get(...)) may be a zero-copy view of a "
            "live (donatable) device buffer; use np.array(..., copy=True) "
            "(historical: donated-step aliasing corrupted checkpoints)",
        ))


# ----------------------------------------------------------------------
# FFL102 — donated buffer reused after the step
# ----------------------------------------------------------------------
# which argument each builder's callable consumes: (position, keyword)
_DONATED_ARG = {"build_train_step": (0, "state"),
                "build_decode": (1, "caches")}


def _assigned_pairs(node: ast.Assign):
    """(target, value) of an assignment, a tuple assigned to a tuple
    element by element."""
    for tgt in node.targets:
        if (isinstance(tgt, ast.Tuple) and isinstance(node.value, ast.Tuple)
                and len(tgt.elts) == len(node.value.elts)):
            yield from zip(tgt.elts, node.value.elts)
        else:
            yield tgt, node.value


def _built_step(t: ast.AST, v: ast.Call):
    """(target, builder) where `t = v` binds a donating step, else
    (t, None): `x = <...>.build_train_step(...)` without donate=False,
    the second of `init, step = <...>.build_decode(...)`."""
    callee = _dotted(v.func).rsplit(".", 1)[-1]
    if callee == "build_train_step":
        # donate=(expr) that may be False at runtime: trust it only when
        # literally False
        donate_off = any(
            k.arg == "donate" and getattr(k.value, "value", None) is False
            for k in v.keywords)
        return t, None if donate_off else callee
    if (callee == "build_decode" and isinstance(t, ast.Tuple)
            and len(t.elts) == 2):
        return t.elts[1], callee
    return t, None


def _donating_steps(scope: ast.AST, known: Dict[str, str]) -> Dict[str, str]:
    """{dotted name: builder} of the donating step callables assigned in
    `scope`, and of any name such a callable is assigned on to
    (`self._init, self._step = init, step`). `known` holds the names that
    are steps already (the module's `self.` attributes)."""
    steps = dict(known)
    pairs = [pair for n in ast.walk(scope) if isinstance(n, ast.Assign)
             for pair in _assigned_pairs(n)]
    for _ in range(3):  # a chain of hand-ons is short
        for t, v in pairs:
            if isinstance(v, ast.Call):
                t, builder = _built_step(t, v)
            else:
                builder = steps.get(_dotted(v))
            if builder and isinstance(t, (ast.Name, ast.Attribute)):
                steps[_dotted(t)] = builder
    return steps


def _check_donated_reuse(tree: ast.AST, path: str,
                         findings: List[Finding]) -> None:
    fns = [fn for fn in ast.walk(tree)
           if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))]
    # a step kept on `self` is called from other methods than the one
    # that built it; a plain name means its own function's step only
    kept: Dict[str, str] = {}
    for fn in fns:
        kept.update((name, b) for name, b in _donating_steps(fn, {}).items()
                    if name.startswith("self."))
    seen = set()  # a nested function is walked with its outer one too
    for fn in fns:
        step_fns = _donating_steps(fn, kept)
        if not step_fns:
            continue
        # calls step(..., arg, ...): arg is donated; flag loads of arg's
        # expression after the call line and before a re-store of it
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and _dotted(node.func) in step_fns):
                continue
            pos, kw = _DONATED_ARG[step_fns[_dotted(node.func)]]
            donated = (node.args[pos] if len(node.args) > pos else next(
                (k.value for k in node.keywords if k.arg == kw), None))
            target = _dotted(donated) if donated is not None else ""
            if not target:
                continue
            stores = [
                n.lineno for n in ast.walk(fn)
                if isinstance(n, (ast.Name, ast.Attribute))
                and isinstance(getattr(n, "ctx", None), ast.Store)
                and _dotted(n) == target and n.lineno >= node.lineno
            ]  # >=: `state, out = step_fn(state, ...)` rebinds in place
            rebound = min(stores) if stores else None
            for n in ast.walk(fn):
                if not isinstance(n, (ast.Name, ast.Attribute)):
                    continue
                if not isinstance(getattr(n, "ctx", None), ast.Load):
                    continue
                # past the call's last line: its own arguments are no
                # second read
                if _dotted(n) != target or \
                        n.lineno <= (node.end_lineno or node.lineno):
                    continue
                if rebound is not None and n.lineno >= rebound:
                    continue
                if (n.lineno, n.col_offset) in seen:
                    break
                seen.add((n.lineno, n.col_offset))
                findings.append(Finding(
                    path, n.lineno, n.col_offset, "FFL102",
                    f"`{target}` was donated to `{_dotted(node.func)}(...)` "
                    f"on line {node.lineno} and is read again before being "
                    "rebound — donated buffers are reused by the next "
                    "dispatch (historical: stale-state reads after "
                    "donation)",
                ))
                break  # one finding per donated call is enough


# ----------------------------------------------------------------------
# FFL103 — host sync on the step path
# ----------------------------------------------------------------------
# The traced / per-step-dispatch closures of the executor and the Pallas
# kernel bodies. A call is attributed to its INNERMOST enclosing
# function: build-time code in `build_decode` stays exempt while the
# `step` closure it returns is covered.
_STEP_PATH_NAMES = frozenset({
    "step", "loss_of", "grad_of", "fwd", "body", "run", "multi",
})


def _is_step_path_fn(name: str) -> bool:
    return (name in _STEP_PATH_NAMES or name.endswith("_step")
            or name.startswith("step_") or name.endswith("_kernel"))


def _in_step_path_module(path: str) -> bool:
    parts = os.path.normpath(os.path.abspath(path)).split(os.sep)
    if "flexflow_tpu" not in parts[:-1]:
        return False
    return "parallel" in parts[:-1] or "kernels" in parts[:-1]


def _walk_innermost_fn(node: ast.AST, fn_name: str = ""):
    """Yield (node, innermost enclosing function name) pairs."""
    for child in ast.iter_child_nodes(node):
        if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield child, fn_name
            yield from _walk_innermost_fn(child, child.name)
        else:
            yield child, fn_name
            yield from _walk_innermost_fn(child, fn_name)


def _host_sync_reason(call: ast.Call) -> str:
    fn = _dotted(call.func)
    leaf = fn.split(".")[-1]
    root = fn.split(".")[0]
    if leaf == "block_until_ready":
        return f"{fn}() blocks the host until the device drains"
    if leaf == "device_get":
        return f"{fn}() is a device->host transfer"
    if leaf in ("asarray", "array") and root in ("np", "numpy"):
        return (f"{fn}() on a device value forces a host round-trip "
                "(or raises under jit)")
    return ""


def _check_step_path_sync(tree: ast.AST, path: str,
                          findings: List[Finding]) -> None:
    if not _in_step_path_module(path):
        return
    for node, fn_name in _walk_innermost_fn(tree):
        if not isinstance(node, ast.Call) or not _is_step_path_fn(fn_name):
            continue
        reason = _host_sync_reason(node)
        if reason:
            findings.append(Finding(
                path, node.lineno, node.col_offset, "FFL103",
                f"host sync inside step-path function `{fn_name}`: "
                f"{reason}; hoist it out of the per-step path "
                "(historical: per-step host syncs serialized async "
                "dispatch and flattened bench throughput)",
            ))


# ----------------------------------------------------------------------
# FFL301 — float64 creep on the step path
# ----------------------------------------------------------------------
_F64_NAMES = frozenset({
    "np.float64", "numpy.float64", "jnp.float64", "jax.numpy.float64",
})


def _check_float64(tree: ast.AST, path: str,
                   findings: List[Finding]) -> None:
    if not _in_step_path_module(path):
        return
    for node, fn_name in _walk_innermost_fn(tree):
        if not _is_step_path_fn(fn_name):
            continue
        if isinstance(node, ast.Attribute) and _dotted(node) in _F64_NAMES:
            findings.append(Finding(
                path, node.lineno, node.col_offset, "FFL301",
                f"`{_dotted(node)}` inside step-path function "
                f"`{fn_name}` widens the traced flow to fp64 (no TPU "
                "fp64 MXU path, and the FFA7xx static precision story "
                "no longer matches the executed math); pin bf16/f32",
            ))
            continue
        if not isinstance(node, ast.Call):
            continue
        fn = _dotted(node.func)
        leaf = fn.split(".")[-1]
        root = fn.split(".")[0]
        for kw in node.keywords:
            if kw.arg == "dtype" and \
                    getattr(kw.value, "value", None) in ("float64",
                                                         "double"):
                findings.append(Finding(
                    path, kw.value.lineno, kw.value.col_offset, "FFL301",
                    f"dtype='float64' inside step-path function "
                    f"`{fn_name}`: fp64 has no TPU MXU path; pin "
                    "bf16/f32 or pragma host-side math",
                ))
        if leaf in ("array", "asarray") and root in ("np", "numpy") \
                and not any(k.arg == "dtype" for k in node.keywords):
            findings.append(Finding(
                path, node.lineno, node.col_offset, "FFL301",
                f"dtype-less {fn}() inside step-path function "
                f"`{fn_name}` defaults Python floats to float64; pass "
                "an explicit dtype",
            ))


# ----------------------------------------------------------------------
# FFL201 — bare print() in library code
# ----------------------------------------------------------------------
def _in_flexflow_tpu(path: str) -> bool:
    parts = os.path.normpath(os.path.abspath(path)).split(os.sep)
    return "flexflow_tpu" in parts[:-1]


def _check_prints(tree: ast.AST, path: str,
                  findings: List[Finding]) -> None:
    if not _in_flexflow_tpu(path):
        return  # tools/, tests/, examples/ may print freely
    if os.path.basename(path) == "__main__.py":
        return  # CLI entry points: printing is the job
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "print"):
            findings.append(Finding(
                path, node.lineno, node.col_offset, "FFL201",
                "bare print() in library code bypasses the structured "
                "logger/telemetry sink; use flexflow_tpu.obs.progress "
                "(same human-readable line + an event when telemetry is "
                "on), or pragma-allowlist genuine CLI/dump modules",
            ))


# ----------------------------------------------------------------------
def lint_source(source: str, path: str) -> List[Finding]:
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [Finding(path, e.lineno or 0, 0, "FFL000",
                        f"syntax error: {e.msg}")]
    findings: List[Finding] = []
    _check_excepts(tree, path, findings)
    _check_asarray(tree, path, findings)
    _check_donated_reuse(tree, path, findings)
    _check_step_path_sync(tree, path, findings)
    _check_float64(tree, path, findings)
    _check_prints(tree, path, findings)
    pragmas = _pragmas(source)
    file_off: Set[str] = set()
    for m in _FILE_PRAGMA.finditer(source):
        file_off |= {c.strip() for c in m.group(1).split(",") if c.strip()}
    return [
        f for f in findings
        if f.code not in pragmas.get(f.line, set())
        and f.code not in file_off
    ]


def lint_path(path: str) -> List[Finding]:
    findings: List[Finding] = []
    if os.path.isfile(path):
        files = [path]
    else:
        files = []
        for root, dirs, names in os.walk(path):
            dirs[:] = [d for d in dirs
                       if d not in (".git", "__pycache__", ".jax_cache")]
            files.extend(os.path.join(root, n) for n in sorted(names)
                         if n.endswith(".py"))
    for f in files:
        try:
            with open(f, encoding="utf-8") as fh:
                src = fh.read()
        except OSError as e:
            findings.append(Finding(f, 0, 0, "FFL000", f"unreadable: {e}"))
            continue
        findings.extend(lint_source(src, f))
    return findings


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="fflint", description=__doc__.split("\n\n")[0])
    p.add_argument("paths", nargs="*", help="files or directories to lint")
    p.add_argument("--list-rules", action="store_true",
                   help="print the rule catalog and exit")
    args = p.parse_args(argv)
    if args.list_rules:
        for code, desc in sorted(RULES.items()):
            print(f"{code}  {desc}")
        return 0
    if not args.paths:
        p.print_usage()
        return 2
    findings: List[Finding] = []
    for path in args.paths:
        if not os.path.exists(path):
            print(f"fflint: no such path: {path}", file=sys.stderr)
            return 2
        findings.extend(lint_path(path))
    for f in findings:
        print(f.format())
    if findings:
        print(f"fflint: {len(findings)} finding(s)")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
