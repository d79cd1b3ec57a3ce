"""Jit-purity lint: host side effects inside traced bodies.

A ``jax.jit``-traced function body runs ONCE per trace, not once per
call — host-side effects inside it are silent correctness bugs of two
shapes: (1) side effects that fire at trace time and then never again
(``print``, ``time.*``, RNG, mutation of module globals), so steady
state silently diverges from the first call; (2) host ops applied to
TRACED values (``np.*`` on a tracer, bare ``float()`` / ``bool()``
coercions), which either raise ``TracerConversionError`` on an
untested path or — worse — silently constant-fold a value that should
be data-dependent.  The sync lint (check_syncs) already polices
``device_get``-style transfers tree-wide; this pass complements it by
walking every function REACHABLE inside a traced body and flagging
host-effect constructs there specifically.

Mechanics (AST, best-effort by design — a discipline gate, not a
verifier):

1. **Roots.**  Every ``jax.jit`` site in the package: ``@jax.jit`` /
   ``@functools.partial(jax.jit, ...)`` decorators, and ``jax.jit(f)``
   call arguments resolved through ``functools.partial(g, ...)``,
   ``shard_map(g, ...)`` wrappers, local ``f = ...`` assignments,
   ``self._method`` references and cross-module imports.
2. **Reachability.**  From the roots, any name referenced in a
   reachable function that resolves to a package-internal function
   (direct call, ``lax.fori_loop``/``scan``/``cond`` callback, nested
   closure) is reachable too; so is what a local name stands for: a
   method it aliases, or the inner functions of the builder whose
   result it holds.
3. **Findings** inside reachable functions: ``np.*`` calls (dtype
   constructors and ``iinfo``/``finfo`` excepted), ``time.*`` /
   ``random.*`` / ``np.random.*`` / ``os.*`` / ``open`` / ``print``
   calls, bare ``float()`` / ``bool()`` on non-literals, ``.item()`` /
   ``jax.device_get`` / ``block_until_ready`` (a sync INSIDE a traced
   body escapes the tracer, strictly worse than the tree-wide sync
   lint's concern), and mutation of module-level state (``global``
   declarations, subscript/attribute stores to module globals).
4. **Sanctioned trace-time accounting** is never flagged:
   ``utils.compile_cache.trace_event`` and ``obs.flops.note_traced``
   are DESIGNED to fire once per fresh trace (idempotent on retrace;
   the retrace lint counts on the former).
5. **Allowlist** ``tools/purity_allowlist.txt``:
   ``path | function.qualname | token | rationale`` (rationale
   MANDATORY — e.g. the module-level trace counters that exist to be
   a once-per-trace side effect).  Stale entries are errors.

Run via ``python tools/lint.py`` (tier-1), or standalone
(``python tools/analyze/check_purity.py``; exit 1 on findings).
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List, Optional, Set, Tuple

if __package__:
    from . import lintlib
else:                                        # standalone execution
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import lintlib

REPO = lintlib.REPO
PACKAGE = lintlib.PACKAGE
ALLOWLIST = os.path.join(REPO, "tools", "purity_allowlist.txt")

# numpy attributes that are pure dtype/metadata constructors — fine at
# trace time (np.float32(0.5) makes a weakly-typed scalar constant)
_NP_ALLOWED = {"float16", "float32", "float64", "int8", "int16",
               "int32", "int64", "uint8", "uint16", "uint32", "uint64",
               "bool_", "dtype", "iinfo", "finfo"}

# modules whose CALLS inside a traced body are host effects
_EFFECT_MODULES = {"time", "random", "os", "shutil", "subprocess"}

# designed trace-time accounting: fires once per fresh trace on purpose
_SANCTIONED_CALLS = {"trace_event", "note_traced"}

_JIT_WRAPPERS = {"partial", "shard_map"}


def _dotted(rel: str) -> str:
    """Module file path (``pkg/sub/mod.py``) -> dotted module path."""
    mod = rel[:-3].replace(os.sep, ".").replace("/", ".")
    if mod.endswith(".__init__"):
        mod = mod[:-len(".__init__")]
    return mod


class _Func:
    __slots__ = ("rel", "qual", "node", "env", "cls")

    def __init__(self, rel: str, qual: str, node, env: Dict[str, tuple],
                 cls: Optional[str]):
        self.rel, self.qual, self.node = rel, qual, node
        self.env = env          # visible name -> resolution target
        self.cls = cls          # enclosing class name (for self.X)


class _Index:
    def __init__(self) -> None:
        self.funcs: Dict[Tuple[str, str], _Func] = {}   # (dotted, qual)
        self.by_key: Dict[Tuple[str, str], _Func] = {}  # (rel, qual)
        self.module_globals: Dict[str, Set[str]] = {}
        # unresolved jit targets: (rel, name-to-resolve, env, cls)
        self.pending: List[Tuple[str, str, Dict[str, tuple],
                                 Optional[str]]] = []
        self.roots: List[_Func] = []


def _jit_ref(node: ast.AST) -> bool:
    """Whether ``node`` references jax.jit / jit."""
    if isinstance(node, ast.Attribute) and node.attr == "jit":
        return True
    return isinstance(node, ast.Name) and node.id == "jit"


def _is_jit_decorator(dec: ast.AST) -> bool:
    if _jit_ref(dec):
        return True
    if isinstance(dec, ast.Call):
        f = dec.func
        fname = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None)
        if _jit_ref(f):
            return True
        if fname == "partial" and dec.args and _jit_ref(dec.args[0]):
            return True
    return False


def _jit_arg_name(arg: ast.AST) -> Optional[str]:
    """The name to resolve for a ``jax.jit(<arg>)`` target: 'f',
    'self.f', 'mod.f', unwrapping partial(...)/shard_map(...)."""
    if isinstance(arg, ast.Name):
        return arg.id
    if isinstance(arg, ast.Attribute) and isinstance(arg.value,
                                                     ast.Name):
        return f"{arg.value.id}.{arg.attr}"
    if isinstance(arg, ast.Call):
        f = arg.func
        fname = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None)
        if fname in _JIT_WRAPPERS and arg.args:
            return _jit_arg_name(arg.args[0])
    return None


_DEFS = (ast.FunctionDef, ast.AsyncFunctionDef, ast.ClassDef)


def _scope_stmts(body):
    """Statements belonging to this scope: descends into compound
    statements (if/for/while/with/try) but not into nested functions or
    classes — those are yielded, and open scopes of their own."""
    stack = list(body)
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, _DEFS):
            stack.extend(
                child for child in ast.iter_child_nodes(n)
                if isinstance(child, (ast.stmt, ast.excepthandler)))


def _scope_defs(body) -> List[ast.AST]:
    """Function/class definitions belonging to this scope."""
    return [n for n in _scope_stmts(body) if isinstance(n, _DEFS)]


def _scope_bindings(body) -> Dict[str, List[Tuple[str, str]]]:
    """``name = value`` statements of this scope (not of nested defs),
    as what the name may stand for: ``("alias", "self.f")`` for ``name =
    self.f`` (either arm of a conditional too), ``("result", "self.build")``
    for ``name = self.build(...)``, the value a builder hands back."""
    def ref(node: ast.AST) -> Optional[str]:
        if isinstance(node, ast.Name):
            return node.id
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name):
            return f"{node.value.id}.{node.attr}"
        return None

    def refs(value: ast.AST) -> List[Tuple[str, str]]:
        if isinstance(value, ast.IfExp):
            return refs(value.body) + refs(value.orelse)
        kind, node = ("result", value.func) \
            if isinstance(value, ast.Call) else ("alias", value)
        name = ref(node)
        return [(kind, name)] if name is not None else []

    out: Dict[str, List[Tuple[str, str]]] = {}
    for n in _scope_stmts(body):
        if isinstance(n, ast.Assign) and len(n.targets) == 1 \
                and isinstance(n.targets[0], ast.Name):
            out.setdefault(n.targets[0].id, []).extend(refs(n.value))
    return out


def _index_module(idx: _Index, root: str, path: str) -> None:
    rel = lintlib.rel_to_root(path, root)
    mod = _dotted(rel)
    is_init = os.path.basename(path) == "__init__.py"
    try:
        with open(path, "rb") as f:
            tree = ast.parse(f.read(), filename=path)
    except SyntaxError:
        return
    idx.module_globals[rel] = {
        t.id
        for n in tree.body if isinstance(n, (ast.Assign, ast.AnnAssign))
        for t in (n.targets if isinstance(n, ast.Assign)
                  else [n.target])
        if isinstance(t, ast.Name)}

    env: Dict[str, tuple] = {}

    def note_import(node: ast.AST) -> None:
        if isinstance(node, ast.ImportFrom):
            parts = mod.split(".")
            if node.level:
                # level 1 = current package, 2 = its parent, ...
                keep = len(parts) - node.level + (1 if is_init else 0)
                anchor = parts[:max(keep, 0)]
                target = ".".join(anchor + ([node.module]
                                            if node.module else []))
            else:
                target = node.module or ""
            for alias in node.names:
                env[alias.asname or alias.name] = \
                    ("import", target, alias.name)
        elif isinstance(node, ast.Import):
            for alias in node.names:
                env[alias.asname or alias.name.split(".")[0]] = \
                    ("module", alias.name, "")

    # imports anywhere in the module (function-level imports become
    # visible module-wide — an over-approximation we accept)
    for n in ast.walk(tree):
        note_import(n)

    def register(body, prefix: str, cls: Optional[str],
                 scope_env: Dict[str, tuple]) -> Dict[str, tuple]:
        """Register this scope's defs; returns the scope's env (outer
        env + this scope's function names) so a function's stored env
        sees its OWN nested defs — the ``lax.fori_loop(0, n, body, x)``
        callback pattern resolves through it."""
        defs = _scope_defs(body)
        local = dict(scope_env)
        for n in defs:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                local[n.name] = ("func", rel, f"{prefix}{n.name}")
        if prefix:
            # a function's local names that stand for package functions:
            # the closure a jitted body calls through (``body =
            # self._build_body(...)``; ``draw = self._draw if on else None``)
            for name, bound in _scope_bindings(body).items():
                if bound:
                    local.setdefault(name, ("bound", bound))
        for n in defs:
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                qual = f"{prefix}{n.name}"
                inner = register(n.body, qual + ".", cls, local)
                fn = _Func(rel, qual, n, inner, cls)
                idx.funcs[(mod, qual)] = fn
                idx.by_key[(rel, qual)] = fn
                if any(_is_jit_decorator(d) for d in n.decorator_list):
                    idx.roots.append(fn)
            elif isinstance(n, ast.ClassDef):
                register(n.body, f"{n.name}.", n.name, local)
        return local

    module_env = register(tree.body, "", None, env)

    # jit(...) CALL roots: scan each scope with ITS env, with alias
    # tracking (`f = shard_map(g, ...)` then `jax.jit(f)`)
    def alias_targets(value: ast.AST) -> List[str]:
        """Names a bound value may refer to: ``f = g``, ``f =
        shard_map(g, ...)``, ``f = a if cond else b``."""
        if isinstance(value, ast.Name):
            return [value.id]
        if isinstance(value, ast.IfExp):
            return alias_targets(value.body) \
                + alias_targets(value.orelse)
        if isinstance(value, ast.Call):
            t = _jit_arg_name(value)
            return [t] if t is not None else []
        return []

    def scan_jit_calls(scope_node, scope_env: Dict[str, tuple],
                       cls: Optional[str]) -> None:
        aliases: Dict[str, List[str]] = {}
        subs = list(ast.walk(scope_node)) if not isinstance(
            scope_node, ast.Module) else [
            s for n in scope_node.body for s in ast.walk(n)]
        for sub in subs:
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.targets[0], ast.Name):
                ts = alias_targets(sub.value)
                if ts:
                    aliases.setdefault(sub.targets[0].id,
                                       []).extend(ts)
        for sub in subs:
            if isinstance(sub, ast.Call) and _jit_ref(sub.func) \
                    and sub.args:
                tgt = _jit_arg_name(sub.args[0])
                if tgt is None:
                    continue
                frontier, resolved = [tgt], []
                for _ in range(4):
                    nxt = []
                    for t in frontier:
                        if t in aliases:
                            nxt.extend(aliases[t])
                        else:
                            resolved.append(t)
                    frontier = nxt
                    if not frontier:
                        break
                for t in resolved + frontier:
                    idx.pending.append((rel, t, scope_env, cls))

    scan_jit_calls(tree, module_env, None)
    for (r, _q), fn in list(idx.by_key.items()):
        if r == rel and fn.node is not None:
            scan_jit_calls(fn.node, fn.env, fn.cls)


def _lookup(idx: _Index, rel: str, env: Dict[str, tuple],
            cls: Optional[str], name: str) -> Optional[_Func]:
    """Resolve 'x' / 'self.x' / 'mod.x' to a package function."""
    if name.startswith("self."):
        if cls:
            return idx.by_key.get((rel, f"{cls}.{name[5:]}"))
        return None
    if "." in name:
        head, _, tail = name.partition(".")
        e = env.get(head)
        if e is None:
            return None
        if e[0] == "module":
            return idx.funcs.get((e[1], tail))
        if e[0] == "import":
            # `from . import predict_device` -> head names a module
            return idx.funcs.get((f"{e[1]}.{e[2]}".lstrip("."), tail))
        return None
    e = env.get(name)
    if e is None:
        return None
    if e[0] == "func":
        return idx.by_key.get((e[1], e[2]))
    if e[0] == "import":
        return idx.funcs.get((e[1], e[2]))
    return None


def _reachable(idx: _Index) -> Dict[Tuple[str, str], _Func]:
    work: List[_Func] = list(idx.roots)
    for rel, tgt, env, cls in idx.pending:
        got = _lookup(idx, rel, env, cls, tgt)
        if got is not None:
            work.append(got)
    seen: Dict[Tuple[str, str], _Func] = {}
    while work:
        fn = work.pop()
        key = (fn.rel, fn.qual)
        if key in seen or fn.node is None:
            continue
        seen[key] = fn
        for sub in ast.walk(fn.node):
            name = None
            if isinstance(sub, ast.Name) \
                    and isinstance(sub.ctx, ast.Load):
                name = sub.id
            elif isinstance(sub, ast.Attribute) \
                    and isinstance(sub.ctx, ast.Load) \
                    and isinstance(sub.value, ast.Name):
                base = sub.value.id
                name = f"self.{sub.attr}" if base == "self" \
                    else f"{base}.{sub.attr}"
            if name is None:
                continue
            got = _lookup(idx, fn.rel, fn.env, fn.cls, name)
            if got is not None and (got.rel, got.qual) not in seen:
                work.append(got)
            e = fn.env.get(name)
            if e is not None and e[0] == "bound":
                for kind, ref in e[1]:
                    tgt = _lookup(idx, fn.rel, fn.env, fn.cls, ref)
                    if tgt is None:
                        continue
                    if kind == "alias":
                        work.append(tgt)
                        continue
                    # a builder's result: what runs under the trace are
                    # the functions it defines, not its own host code
                    inner = tgt.qual + "."
                    work.extend(
                        f for (r, q), f in idx.by_key.items()
                        if r == tgt.rel and q.startswith(inner)
                        and "." not in q[len(inner):])
    return seen


# ---------------------------------------------------------------------------
# findings inside a reachable function
# ---------------------------------------------------------------------------

def _call_name(f: ast.AST) -> Tuple[Optional[str], Optional[str]]:
    """('np', 'sum') for np.sum(...), (None, 'print') for print(...)."""
    if isinstance(f, ast.Name):
        return None, f.id
    if isinstance(f, ast.Attribute):
        if isinstance(f.value, ast.Name):
            return f.value.id, f.attr
        if isinstance(f.value, ast.Attribute) \
                and isinstance(f.value.value, ast.Name):
            return f"{f.value.value.id}.{f.value.attr}", f.attr
    return None, None


def _scan_function(fn: _Func, module_globals: Set[str]
                   ) -> List[Tuple[int, str, str]]:
    """(lineno, token, message) findings in one reachable function.
    The function's OWN body only — nested defs are their own reachable
    entries, so findings carry the precise qualname."""
    out: List[Tuple[int, str, str]] = []
    node = fn.node
    locals_: Set[str] = set()
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and isinstance(sub.ctx, ast.Store):
            locals_.add(sub.id)

    skip: Set[ast.AST] = set()
    for sub in ast.walk(node):
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and sub is not node:
            for inner in ast.walk(sub):
                skip.add(inner)

    for sub in ast.walk(node):
        if sub in skip:
            continue
        if isinstance(sub, ast.Global):
            for g in sub.names:
                out.append((sub.lineno, f"global:{g}",
                            f"mutates module global '{g}'"))
            continue
        if isinstance(sub, (ast.Assign, ast.AugAssign)):
            tgts = sub.targets if isinstance(sub, ast.Assign) \
                else [sub.target]
            for t in tgts:
                base = t
                while isinstance(base, (ast.Subscript, ast.Attribute)):
                    base = base.value
                if isinstance(base, ast.Name) and base is not t \
                        and base.id not in locals_ \
                        and base.id in module_globals:
                    out.append((sub.lineno, f"global:{base.id}",
                                f"mutates module global "
                                f"'{base.id}' in place"))
            continue
        if not isinstance(sub, ast.Call):
            continue
        mod, name = _call_name(sub.func)
        if name is None:
            if isinstance(sub.func, ast.Attribute) \
                    and sub.func.attr == "item":
                out.append((sub.lineno, ".item()",
                            "host sync .item() in traced body"))
            continue
        if name in _SANCTIONED_CALLS:
            continue
        if name == "item" and not sub.args:
            out.append((sub.lineno, ".item()",
                        "host sync .item() in traced body"))
        elif name in ("device_get", "block_until_ready"):
            out.append((sub.lineno, name,
                        f"host sync {name} in traced body"))
        elif mod in ("np", "numpy"):
            if name not in _NP_ALLOWED:
                out.append((sub.lineno, f"np.{name}",
                            f"numpy call np.{name} on (potentially) "
                            "traced values"))
        elif mod in ("np.random", "numpy.random"):
            out.append((sub.lineno, f"np.random.{name}",
                        f"host RNG np.random.{name} in traced body"))
        elif mod in _EFFECT_MODULES:
            out.append((sub.lineno, f"{mod}.{name}",
                        f"host side effect {mod}.{name}() in traced "
                        "body"))
        elif mod is None and name == "print":
            out.append((sub.lineno, "print",
                        "print() in traced body (fires once per "
                        "trace, then never again)"))
        elif mod is None and name == "open":
            out.append((sub.lineno, "open",
                        "file I/O in traced body"))
        elif mod is None and name in ("float", "bool") and sub.args:
            if not isinstance(sub.args[0], ast.Constant):
                out.append((sub.lineno, f"{name}()",
                            f"bare {name}() coercion — escapes the "
                            "tracer on traced values"))
    return out


def run(root: str = PACKAGE,
        allowlist_path: str = ALLOWLIST) -> List[str]:
    idx = _Index()
    for path in lintlib.iter_py(root):
        _index_module(idx, root, path)
    reach = _reachable(idx)
    allow = lintlib.load_pin_keys(allowlist_path)
    used: Set[Tuple[str, str, str]] = set()
    findings: List[str] = []
    for (rel, qual), fn in sorted(reach.items()):
        if qual.rsplit(".", 1)[-1] in _SANCTIONED_CALLS:
            continue     # the sanctioned primitives ARE the allowed
            #              trace-time effect; their bodies are exempt
        for lineno, token, msg in sorted(
                _scan_function(fn, idx.module_globals.get(rel, set()))):
            key = (rel, qual, token)
            if key in allow:
                used.add(key)
                continue
            findings.append(f"{rel}:{lineno}: {qual}: {msg}")
    findings.extend(lintlib.stale_pins(allow, used, "purity allowlist"))
    return findings


def reachable_functions(root: str = PACKAGE) -> List[str]:
    """Debug surface: the functions the lint considers traced."""
    idx = _Index()
    for path in lintlib.iter_py(root):
        _index_module(idx, root, path)
    return sorted(f"{rel}:{qual}" for (rel, qual) in _reachable(idx))


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", default=PACKAGE)
    ap.add_argument("--allowlist", default=ALLOWLIST)
    ap.add_argument("--list-reachable", action="store_true",
                    help="print the inferred traced-function set")
    args = ap.parse_args(argv)
    if args.list_reachable:
        for f in reachable_functions(args.root):
            print(f)
        return 0
    findings = run(args.root, args.allowlist)
    if findings:
        print("purity lint: host side effects inside traced bodies:",
              file=sys.stderr)
        for f in findings:
            print(f"  {f}", file=sys.stderr)
        print(f"\n{len(findings)} finding(s).  Move the effect out of "
              "the traced body, or pin a deliberate trace-time effect "
              "in tools/purity_allowlist.txt (rationale required)",
              file=sys.stderr)
        return 1
    print("purity lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
