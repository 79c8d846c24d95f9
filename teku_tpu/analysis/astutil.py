"""Shared AST plumbing for the checkers.

One parse per file, one ModuleIndex per module, and the handful of
resolution helpers every checker needs: module-level string constants
(``ENV_VAR = "TEKU_TPU_MESH"`` — the idiom the knob modules use, which a
literal-only scanner would miss), import maps including relative
imports (``from ..infra.env import env_float``), dotted call chains,
and a scope model precise enough to resolve a bare-name call inside a
jitted kernel to the helper it actually invokes — same function, nested
function, same class, same module, or another module in the scanned
tree.
"""

import ast
from typing import Dict, Iterator, List, Optional, Tuple

FuncNode = ast.AST          # FunctionDef | AsyncFunctionDef | Lambda


def module_name(relpath: str) -> str:
    """'teku_tpu/ops/verify.py' -> 'teku_tpu.ops.verify';
    '__init__.py' files name the package itself."""
    parts = relpath.replace("\\", "/").split("/")
    parts[-1] = parts[-1][:-3]          # strip .py
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


class ModuleIndex:
    """Everything the checkers ask of one parsed module."""

    def __init__(self, path: str, relpath: str, tree: ast.Module,
                 source: str):
        self.path = path
        self.relpath = relpath
        self.modname = module_name(relpath)
        self.tree = tree
        self.source = source
        self.consts: Dict[str, str] = {}
        # local name -> fully dotted target.  Module imports map to the
        # module ('np' -> 'numpy'); from-imports map to the symbol
        # ('env_float' -> 'teku_tpu.infra.env.env_float').
        self.imports: Dict[str, str] = {}
        self.functions: Dict[str, ast.AST] = {}           # module level
        self.classes: Dict[str, Dict[str, ast.AST]] = {}  # cls -> methods
        self.enclosing_class: Dict[ast.AST, str] = {}
        self.parent_func: Dict[ast.AST, Optional[ast.AST]] = {}
        self.local_funcs: Dict[ast.AST, Dict[str, ast.AST]] = {}
        self._index()

    # ------------------------------------------------------------------
    def _index(self) -> None:
        for node in self.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str):
                self.consts[node.targets[0].id] = node.value.value
        self._index_imports()
        self._index_scopes(self.tree, parent=None, cls=None)

    def _index_imports(self) -> None:
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    self.imports[alias.asname
                                 or alias.name.split(".")[0]] = alias.name
            elif isinstance(node, ast.ImportFrom):
                base = self._resolve_from_base(node)
                if base is None:
                    continue
                for alias in node.names:
                    if alias.name == "*":
                        continue
                    self.imports[alias.asname or alias.name] = \
                        f"{base}.{alias.name}" if base else alias.name

    def _resolve_from_base(self, node: ast.ImportFrom) -> Optional[str]:
        if node.level == 0:
            return node.module or ""
        parts = self.modname.split(".")
        if node.level > len(parts):
            return None
        # level 1 = the containing package: for a plain module that is
        # modname minus the leaf, for an __init__.py modname IS it
        drop = node.level if not self.relpath.endswith("__init__.py") \
            else node.level - 1
        base_parts = parts[:len(parts) - drop]
        if node.module:
            base_parts = base_parts + node.module.split(".")
        return ".".join(base_parts)

    def _index_scopes(self, node: ast.AST, parent: Optional[ast.AST],
                      cls: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.parent_func[child] = parent
                self.local_funcs.setdefault(child, {})
                if cls is not None and parent is None:
                    self.enclosing_class[child] = cls
                    self.classes.setdefault(cls, {})[child.name] = child
                elif parent is None:
                    self.functions[child.name] = child
                else:
                    self.local_funcs.setdefault(parent, {})[
                        child.name] = child
                    if cls is not None:
                        self.enclosing_class[child] = cls
                self._index_scopes(child, parent=child, cls=cls)
            elif isinstance(child, ast.ClassDef):
                self.classes.setdefault(child.name, {})
                self._index_scopes(child, parent=parent,
                                   cls=child.name if parent is None
                                   else cls)
            else:
                self._index_scopes(child, parent=parent, cls=cls)

    # ------------------------------------------------------------------
    def resolve_str(self, expr: ast.AST) -> Optional[str]:
        """Exact string value of an expression, following module-level
        Name constants one hop."""
        if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
            return expr.value
        if isinstance(expr, ast.Name):
            return self.consts.get(expr.id)
        return None

    def str_parts(self, expr: ast.AST) -> Optional[Tuple[str, str, bool]]:
        """(prefix, suffix, exact) of a string-ish expression.  Handles
        literals, Name constants, f-strings (constant head/tail), and
        `+` concatenation whose ends resolve.  None = not string-ish."""
        exact = self.resolve_str(expr)
        if exact is not None:
            return exact, exact, True
        if isinstance(expr, ast.JoinedStr) and expr.values:
            head = expr.values[0]
            tail = expr.values[-1]
            prefix = head.value if isinstance(head, ast.Constant) \
                and isinstance(head.value, str) else ""
            suffix = tail.value if isinstance(tail, ast.Constant) \
                and isinstance(tail.value, str) else ""
            return prefix, suffix, False
        if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
            left = self.str_parts(expr.left)
            right = self.str_parts(expr.right)
            if left is not None or right is not None:
                prefix = left[0] if left is not None and (
                    left[2] or left[0]) else ""
                suffix = right[1] if right is not None and (
                    right[2] or right[1]) else ""
                return prefix, suffix, False
        return None


def dotted(expr: ast.AST) -> Optional[str]:
    """'a.b.c' for a Name/Attribute chain; None for anything else."""
    parts: List[str] = []
    while isinstance(expr, ast.Attribute):
        parts.append(expr.attr)
        expr = expr.value
    if isinstance(expr, ast.Name):
        parts.append(expr.id)
        return ".".join(reversed(parts))
    return None


def iter_scope(func: ast.AST) -> Iterator[ast.AST]:
    """Nodes in `func`'s own body, NOT descending into nested
    function/class scopes (each scope is its own unit of analysis)."""
    stack = list(ast.iter_child_nodes(func))
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def all_functions(idx: ModuleIndex) -> Iterator[Tuple[str, ast.AST]]:
    """Every (qualified name, function node) in the module, any depth."""
    for node in ast.walk(idx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            cls = idx.enclosing_class.get(node)
            name = f"{cls}.{node.name}" if cls else node.name
            yield name, node


class Project:
    """The scanned tree: {module name: ModuleIndex} + the repo root.

    Cross-module resolution: `resolve_function('teku_tpu.ops.limbs',
    'mont_mul')` finds the def wherever the dotted target lands inside
    the scanned set (functions only — the purity walker treats
    unresolvable targets as opaque leaves, not errors)."""

    def __init__(self, root: str, modules: Dict[str, ModuleIndex]):
        self.root = root
        self.modules = modules

    def resolve_str(self, idx: ModuleIndex, expr: ast.AST
                    ) -> Optional[str]:
        """Like ModuleIndex.resolve_str, but also follows one
        cross-module hop: `selfheal.FAULT_SITE` through an imported
        module, or a Name imported with `from mod import CONST`."""
        value = idx.resolve_str(expr)
        if value is not None:
            return value
        target = None
        if isinstance(expr, ast.Name) and expr.id in idx.imports:
            target = idx.imports[expr.id]
        else:
            chain = dotted(expr)
            if chain is not None and "." in chain:
                root_name = chain.split(".")[0]
                base = idx.imports.get(root_name)
                if base is not None:
                    target = base + chain[len(root_name):]
        if target is not None and "." in target:
            modpart, _, leaf = target.rpartition(".")
            mod = self.modules.get(modpart)
            if mod is not None:
                return mod.consts.get(leaf)
        return None

    def resolve_target(self, target: str
                       ) -> Optional[Tuple[ModuleIndex, ast.AST]]:
        """A dotted import target -> (module, function node), when the
        target is a function defined in the scanned tree."""
        if "." in target:
            modpart, _, leaf = target.rpartition(".")
            mod = self.modules.get(modpart)
            if mod is not None and leaf in mod.functions:
                return mod, mod.functions[leaf]
        mod = self.modules.get(target)
        return None

    def resolve_call(self, idx: ModuleIndex, scope: Optional[ast.AST],
                     func_expr: ast.AST
                     ) -> Optional[Tuple[ModuleIndex, ast.AST]]:
        """Resolve a call's func expression to a function def in the
        scanned tree: nested defs outward, same class (self.X), module
        functions, imported symbols, imported-module attributes."""
        if isinstance(func_expr, ast.Name):
            name = func_expr.id
            f = scope
            while f is not None:
                local = idx.local_funcs.get(f, {})
                if name in local:
                    return idx, local[name]
                f = idx.parent_func.get(f)
            if name in idx.functions:
                return idx, idx.functions[name]
            if name in idx.imports:
                return self.resolve_target(idx.imports[name])
            return None
        if isinstance(func_expr, ast.Attribute):
            base = func_expr.value
            if isinstance(base, ast.Name) and base.id in ("self", "cls") \
                    and scope is not None:
                f = scope
                while f is not None and f not in idx.enclosing_class:
                    f = idx.parent_func.get(f)
                cls = idx.enclosing_class.get(f) if f is not None else None
                if cls is not None:
                    method = idx.classes.get(cls, {}).get(func_expr.attr)
                    if method is not None:
                        return idx, method
                return None
            chain = dotted(func_expr)
            if chain is None:
                return None
            root_name = chain.split(".")[0]
            if root_name in idx.imports:
                resolved = idx.imports[root_name] + chain[len(root_name):]
                return self.resolve_target(resolved)
        return None
