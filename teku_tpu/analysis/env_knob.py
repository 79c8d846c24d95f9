"""env-knob: every TEKU_TPU_* environment read goes through
``infra/env.py``.

The mechanized bug class (PR 11's ledger-capacity fix, PR 7's three
private ``_env_float`` copies, and the seed run of this checker): a
knob read raw as ``float(os.environ.get("TEKU_TPU_X", "5"))`` turns an
operator's typo into a boot-killing ValueError, and a raw
``os.environ.get`` with local parsing re-invents the degrade contract
differently at every site.  The ``infra/env.py`` helpers are the ONE
definition: malformed values degrade to the default with one WARN,
bounds clamp, and every read lands in the knob registry this module
also extracts (the input to the ``knob-doc`` drift checker and
``cli lint --knobs``).

The checker resolves key expressions through module-level string
constants (``ENV_VAR = "TEKU_TPU_MESH"``), f-strings, and ``+``
concatenation, so neither the knob-module idiom nor a dynamically
assembled prefix read can hide a raw access.
"""

import ast
from typing import Dict, List, Optional

from .astutil import ModuleIndex, Project, dotted
from .findings import Finding

CHECKER = "env-knob"
PREFIX = "TEKU_TPU_"
ENV_MODULE = "teku_tpu.infra.env"
# the sanctioned read helpers (env_knob findings say "use one of these")
HELPERS = ("env_float", "env_int", "env_str", "env_bool", "env_choice",
           "env_raw")
FIX_HINT = ("read the knob through teku_tpu/infra/env.py "
            f"({'/'.join(HELPERS)}; env_override for save/set/restore) "
            "so a typo degrades with one WARN instead of raising")


def _knob_in_key(idx: ModuleIndex, expr: ast.AST) -> Optional[str]:
    """The TEKU_TPU_* name (or name prefix) a key expression reads, or
    None when the expression cannot touch the knob namespace."""
    parts = idx.str_parts(expr)
    if parts is not None:
        prefix, _suffix, exact = parts
        if prefix.startswith(PREFIX):
            return prefix
        if exact:
            return None
    # opaque expression: does any Name inside resolve to a TEKU_TPU_
    # constant (the `ENV_PREFIX + name.upper()` layering idiom)?
    for node in ast.walk(expr):
        if isinstance(node, ast.Name):
            value = idx.consts.get(node.id)
            if value is not None and value.startswith(PREFIX):
                return value + "*"
    return None


def _raw_read_key(node: ast.Call) -> Optional[ast.AST]:
    """The key expression of a raw environ READ call, else None.
    Mutations (pop / setdefault-as-write / __setitem__) are the CLI's
    legitimate seam for handing choices to subprocess-visible state."""
    chain = dotted(node.func)
    if chain is None:
        return None
    if chain.endswith("os.environ.get") or chain.endswith("os.getenv") \
            or chain == "environ.get" or chain == "getenv":
        return node.args[0] if node.args else None
    return None


def check(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for idx in project.modules.values():
        if idx.modname == ENV_MODULE:
            continue        # the helpers themselves own raw access
        for node in ast.walk(idx.tree):
            key_expr = None
            if isinstance(node, ast.Call):
                key_expr = _raw_read_key(node)
            elif isinstance(node, ast.Subscript) \
                    and isinstance(node.ctx, ast.Load) \
                    and dotted(node.value) in ("os.environ", "environ"):
                key_expr = node.slice
            if key_expr is None:
                continue
            knob = _knob_in_key(idx, key_expr)
            if knob is None:
                continue
            findings.append(Finding(
                checker=CHECKER, path=idx.relpath, line=node.lineno,
                message=f"raw os.environ read of {knob} outside "
                        "infra/env.py",
                evidence=ast.get_source_segment(idx.source, node)
                or knob, fix_hint=FIX_HINT, token=knob))
    return findings


# --------------------------------------------------------------------------
# knob-registry extraction (cli lint --knobs + the knob-doc checker)
# --------------------------------------------------------------------------

def _pattern_from_parts(prefix: str, suffix: str) -> str:
    return f"{prefix}*{suffix}"


def _default_repr(expr: Optional[ast.AST]) -> str:
    if expr is None:
        return ""
    if isinstance(expr, ast.Constant):
        return repr(expr.value)
    chain = dotted(expr)
    if chain is not None:
        return chain
    return "<expr>"


def collect_knobs(project: Project) -> List[Dict[str, object]]:
    """Every TEKU_TPU_* knob the tree reads, auto-extracted: env-helper
    calls (name resolved through constants / f-string patterns) plus
    the CLI's ``layered_value`` seam, whose env name derives from the
    literal flag name.  Sorted, de-duplicated on (name, path)."""
    knobs: Dict[tuple, Dict[str, object]] = {}

    def add(name: str, helper: str, default: str, idx: ModuleIndex,
            line: int) -> None:
        key = (name, idx.relpath)
        entry = knobs.get(key)
        if entry is None:
            knobs[key] = {"name": name, "helper": helper,
                          "default": default, "path": idx.relpath,
                          "line": line}

    for idx in project.modules.values():
        for node in ast.walk(idx.tree):
            if not isinstance(node, ast.Call):
                continue
            target = None
            if isinstance(node.func, ast.Name):
                target = idx.imports.get(node.func.id)
                if target is None and idx.modname == ENV_MODULE:
                    target = f"{ENV_MODULE}.{node.func.id}"
            elif isinstance(node.func, ast.Attribute):
                chain = dotted(node.func)
                if chain is not None:
                    root_name = chain.split(".")[0]
                    base = idx.imports.get(root_name)
                    if base is not None:
                        target = base + chain[len(root_name):]
            if target is not None and target.startswith(ENV_MODULE + ".") \
                    and target.rsplit(".", 1)[1] in HELPERS + (
                        "env_override",):
                helper = target.rsplit(".", 1)[1]
                if not node.args:
                    continue
                parts = idx.str_parts(node.args[0])
                if parts is None:
                    continue
                prefix, suffix, exact = parts
                name = prefix if exact else _pattern_from_parts(
                    prefix, suffix)
                if not name.startswith(PREFIX):
                    continue
                if name == PREFIX + "*":
                    # the CLI layering seam reads the whole namespace
                    # dynamically; its per-flag layered_value rows
                    # below carry the real registry entries
                    continue
                default = _default_repr(
                    node.args[1] if len(node.args) > 1 else next(
                        (kw.value for kw in node.keywords
                         if kw.arg == "default"), None))
                add(name, helper, default, idx, node.lineno)
            # the CLI layering seam: layered_value("flag-name", ...)
            # reads TEKU_TPU_FLAG_NAME (cli.py derives it exactly so)
            elif isinstance(node.func, ast.Name) \
                    and node.func.id == "layered_value" and node.args \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                flag = node.args[0].value
                name = PREFIX + flag.upper().replace("-", "_")
                default = _default_repr(
                    node.args[3] if len(node.args) > 3 else next(
                        (kw.value for kw in node.keywords
                         if kw.arg == "default"), None))
                add(name, "layered_value", default, idx, node.lineno)
    return sorted(knobs.values(),
                  key=lambda k: (k["name"], k["path"]))  # type: ignore


def render_knob_table(knob_list: List[Dict[str, object]]) -> str:
    """The knob registry as a markdown table (``cli lint --knobs``) —
    the same rows the README knob section is checked against."""
    lines = ["| Knob | Reader | Default | Where |",
             "| --- | --- | --- | --- |"]
    for k in knob_list:
        lines.append(f"| `{k['name']}` | {k['helper']} | "
                     f"`{k['default'] or '-'}` | "
                     f"`{k['path']}:{k['line']}` |")
    return "\n".join(lines)
