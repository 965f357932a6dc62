"""Layer 1: AST lint rules over the ``src/repro`` tree.

Rule catalog (see docs/staticcheck.md):

* **SC101** — ``.item()`` on a traced value inside a jitted body
  (host sync + tracer leak).
* **SC102** — ``float()`` / ``int()`` / ``bool()`` on a traced value
  inside a jitted body (concretization error at trace time, or a
  silent host sync outside it).
* **SC103** — ``np.*`` call on a traced value inside a jitted body
  (implicit device-to-host transfer; numpy on closure constants or
  static shapes is fine and not flagged).
* **SC104** — Python ``if`` / ``while`` branching on a traced value
  inside a jitted body (recompile per boolean or TracerBoolError;
  branching on ``.shape``-derived ints and ``static_argnames`` is
  static and not flagged).
* **SC105** — ``jax.device_get`` / ``.block_until_ready()`` inside the
  engine step paths (``src/repro/serve``): the engine's host boundary
  is one named wait per dispatch (``block_until_ready`` inside a
  ``with span(WAIT)`` block, the trace's ``engine.wait``) followed by
  ``np.asarray`` on the stage's outputs; ad-hoc syncs hide dispatch
  stalls.
* **SC201** — a cache-carrying jit site (the wrapped function has a
  ``caches``-like parameter) that does not pass ``donate_argnums``
  covering it: the pool is then copied every dispatch.
* **SC202** — ``jax``/``jnp`` import in ``serve/paging.py``: the page
  table is host-side numpy by contract (O(1) bookkeeping, never
  traced).

Traced-ness is a per-function taint pass: the jitted body's parameters
(minus ``static_argnames``) are traced, assignments propagate taint,
and attribute reads of ``.shape`` / ``.ndim`` / ``.dtype`` / ``.size``
*stop* it (those are static at trace time).  Jit sites are discovered
from ``jax.jit(fn)`` calls, ``_CountingJit(fn)`` calls,
``@jax.jit`` / ``@partial(jax.jit, ...)`` decorators, and
``self._build_*()`` stage builders (whose nested ``def``s are the
jitted closures).
"""

from __future__ import annotations

import ast
from pathlib import Path

from repro.staticcheck.findings import Finding

# attribute reads that yield static (trace-time Python) values
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval"}
# callables whose result on a traced array is a host-side cast
_CAST_BUILTINS = {"float", "int", "bool"}
_NUMPY_ALIASES = {"np", "numpy", "onp"}


def _dotted(node: ast.AST) -> str:
    """'jax.jit' for Attribute(Name('jax'), 'jit'); '' if not a plain
    dotted name."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _tainted_names(node: ast.AST) -> set:
    """Names read by ``node``, excluding any inside a static-attribute
    access (``x.shape[...]`` reads no traced value)."""
    out: set = set()

    class V(ast.NodeVisitor):
        def visit_Attribute(self, n):
            if n.attr in _STATIC_ATTRS:
                return  # do not descend: static at trace time
            self.generic_visit(n)

        def visit_Compare(self, n):
            # `x is None` / `x is not None` yields a static Python bool
            # even when x is traced (tracers are never None), and
            # `"key" in batch` is dict-key membership — both are
            # idiomatic static branches, not tracer reads
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in n.ops) \
                    and all(isinstance(c, ast.Constant)
                            and c.value is None for c in n.comparators):
                return
            if all(isinstance(op, (ast.In, ast.NotIn)) for op in n.ops) \
                    and isinstance(n.left, ast.Constant):
                return
            self.generic_visit(n)

        def visit_Name(self, n):
            out.add(n.id)

    V().visit(node)
    return out


class _JitSite:
    def __init__(self, node, body, static_names, donate, qual, has_donate):
        self.node = node              # the Call / FunctionDef site
        self.body = body              # resolved FunctionDef or None
        self.static_names = static_names
        self.donate = donate          # set of donated arg indices
        self.qual = qual              # qualname of the site
        self.has_donate = has_donate  # donate kwarg present at all


class _ModuleIndex(ast.NodeVisitor):
    """Qualname index of every function/method def in a module."""

    def __init__(self):
        self.funcs: dict[str, ast.FunctionDef] = {}
        self.parents: dict[ast.AST, str] = {}
        self._stack: list[str] = []

    def _visit_scoped(self, node):
        qual = ".".join(self._stack + [node.name])
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.funcs.setdefault(qual, node)
            # also index by bare name for intra-module resolution
            self.funcs.setdefault(node.name, node)
        self._stack.append(node.name)
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_scoped
    visit_AsyncFunctionDef = _visit_scoped
    visit_ClassDef = _visit_scoped


def _const_indices(node: ast.AST) -> set:
    """Constant int / tuple-of-int value of a donate_argnums kwarg."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        out = set()
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.add(e.value)
        return out
    return set()


def _const_strs(node: ast.AST) -> set:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return {node.value}
    if isinstance(node, (ast.Tuple, ast.List)):
        return {e.value for e in node.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)}
    return set()


def _jit_kwargs(call: ast.Call):
    static, donate, has_donate = set(), set(), False
    for kw in call.keywords:
        if kw.arg in ("static_argnames", "static_argnums"):
            static |= _const_strs(kw.value)
        elif kw.arg in ("donate_argnums", "donate_argnames"):
            has_donate = True
            donate |= _const_indices(kw.value)
            # donate_argnames contributes names, map later via body
            donate |= {s for s in _const_strs(kw.value)}
    return static, donate, has_donate


def _nested_defs(fn: ast.FunctionDef) -> list:
    out = []
    for node in ast.walk(fn):
        if node is fn:
            continue
        if isinstance(node, ast.FunctionDef):
            out.append(node)
    return out


def _resolve_builder(index: _ModuleIndex, qual_prefix: str,
                     name: str, seen: set) -> list:
    """``self._build_X()`` -> the nested defs of method ``_build_X``
    (following one ``return self._build_Y()`` level of indirection)."""
    if name in seen:
        return []
    seen.add(name)
    fn = index.funcs.get(name)
    if fn is None:
        return []
    bodies = _nested_defs(fn)
    for node in ast.walk(fn):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "self"
                and node.func.attr.startswith("_build_")):
            bodies += _resolve_builder(index, qual_prefix,
                                       node.func.attr, seen)
    return bodies


def _find_jit_sites(tree: ast.Module, index: _ModuleIndex) -> list:
    sites = []
    for node in ast.walk(tree):
        # decorator form: @jax.jit / @partial(jax.jit, ...)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                static, donate, has_donate = set(), set(), False
                is_jit = False
                if _dotted(dec) in ("jax.jit", "jit"):
                    is_jit = True
                elif isinstance(dec, ast.Call):
                    fname = _dotted(dec.func)
                    if fname in ("jax.jit", "jit"):
                        is_jit = True
                        static, donate, has_donate = _jit_kwargs(dec)
                    elif (fname in ("functools.partial", "partial")
                          and dec.args
                          and _dotted(dec.args[0]) in ("jax.jit", "jit")):
                        is_jit = True
                        static, donate, has_donate = _jit_kwargs(dec)
                if is_jit:
                    sites.append(_JitSite(dec, node, static, donate,
                                          node.name, has_donate))
        # call form: jax.jit(fn, ...) / _CountingJit(fn, ...)
        if isinstance(node, ast.Call):
            fname = _dotted(node.func)
            if fname in ("jax.jit", "jit", "_CountingJit"):
                static, donate, has_donate = _jit_kwargs(node)
                bodies: list = []
                qual = fname
                if node.args:
                    arg = node.args[0]
                    if isinstance(arg, ast.Name):
                        body = index.funcs.get(arg.id)
                        if body is not None:
                            bodies = [body] + _nested_defs(body)
                        qual = arg.id
                    elif (isinstance(arg, ast.Call)
                          and isinstance(arg.func, ast.Attribute)
                          and isinstance(arg.func.value, ast.Name)
                          and arg.func.value.id == "self"):
                        qual = arg.func.attr
                        bodies = _resolve_builder(index, "", arg.func.attr,
                                                  set())
                    elif isinstance(arg, ast.Call):
                        callee = _dotted(arg.func)
                        body = index.funcs.get(callee)
                        qual = callee or qual
                        if body is not None:
                            bodies = _nested_defs(body)
                for body in bodies or [None]:
                    sites.append(_JitSite(node, body, static, donate,
                                          qual, has_donate))
    return sites


def _param_names(fn: ast.FunctionDef) -> list:
    a = fn.args
    return ([p.arg for p in a.posonlyargs] + [p.arg for p in a.args])


def _lint_jitted_body(fn: ast.FunctionDef, static_names: set,
                      relpath: str, qual: str) -> list:
    """SC101-SC104 over one jitted closure (incl. nested scan/loop
    bodies, whose params are traced too)."""
    findings = []
    traced = {p for p in _param_names(fn) if p not in static_names}
    for nested in _nested_defs(fn):
        traced |= set(_param_names(nested))

    # forward taint propagation through assignments, in source order
    for node in ast.walk(fn):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            value = node.value
            if value is None:
                continue
            if _tainted_names(value) & traced:
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            traced.add(n.id)

    def flag(rule, node, msg):
        findings.append(Finding(rule, relpath, qual, msg,
                                getattr(node, "lineno", 0)))

    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            # SC101: traced.item()
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and _tainted_names(node.func.value) & traced):
                flag("SC101", node, ".item() on a traced value forces a "
                     "host sync inside a jitted body")
            fname = _dotted(node.func)
            # SC102: float(traced) / int(traced) / bool(traced)
            if (fname in _CAST_BUILTINS and node.args
                    and _tainted_names(node.args[0]) & traced):
                flag("SC102", node, f"{fname}() on a traced value "
                     "concretizes (or host-syncs) inside a jitted body")
            # SC103: np.f(traced)
            root = fname.split(".", 1)[0] if fname else ""
            if (root in _NUMPY_ALIASES and "." in fname):
                args_tainted = any(_tainted_names(a) & traced
                                   for a in node.args)
                if args_tainted:
                    flag("SC103", node, f"{fname}() on a traced value "
                         "is an implicit device transfer inside a "
                         "jitted body")
        # SC104: if/while on a traced predicate
        if isinstance(node, (ast.If, ast.While, ast.IfExp)):
            if _tainted_names(node.test) & traced:
                kind = type(node).__name__.lower()
                flag("SC104", node, f"python {kind} on a traced value "
                     "inside a jitted body (recompile per value or "
                     "TracerBoolError)")
    return findings


def _named_waits(tree: ast.Module) -> set:
    """The calls inside a ``with span(WAIT)`` block: a stage's one wait
    on its outputs, which the trace names ``engine.wait`` (see
    ``repro.serve.spans``), right before the host copies them."""
    calls = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.With) and any(
                isinstance(item.context_expr, ast.Call)
                and item.context_expr.args
                and _dotted(item.context_expr.args[0]).split(".")[-1]
                == "WAIT" for item in node.items):
            calls |= {id(sub) for stmt in node.body
                      for sub in ast.walk(stmt)
                      if isinstance(sub, ast.Call)}
    return calls


def _lint_serve_host_sync(tree: ast.Module, relpath: str) -> list:
    """SC105 over a serve/ module (whole file, not just jitted
    bodies).  A ``block_until_ready`` inside a ``with span(WAIT)``
    block is the stage's named wait, not an ad-hoc sync."""
    findings = []
    waits = _named_waits(tree)
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            if _dotted(node.func) in ("jax.device_get", "device_get"):
                findings.append(Finding(
                    "SC105", relpath, "module",
                    "jax.device_get in an engine step path; the "
                    "engine's host boundary is np.asarray on stage "
                    "outputs", node.lineno))
            elif (isinstance(node.func, ast.Attribute)
                  and node.func.attr == "block_until_ready"
                  and id(node) not in waits):
                findings.append(Finding(
                    "SC105", relpath, "module",
                    ".block_until_ready() in an engine step path "
                    "serializes dispatch", node.lineno))
    return findings


def _lint_paging_numpy_only(tree: ast.Module, relpath: str) -> list:
    """SC202: serve/paging.py must not import jax."""
    findings = []
    for node in ast.walk(tree):
        bad = None
        if isinstance(node, ast.Import):
            for alias in node.names:
                if alias.name == "jax" or alias.name.startswith("jax."):
                    bad = alias.name
        elif isinstance(node, ast.ImportFrom):
            if node.module and (node.module == "jax"
                                or node.module.startswith("jax.")):
                bad = node.module
        if bad:
            findings.append(Finding(
                "SC202", relpath, "module",
                f"import of {bad!r}: page-table logic is host-side "
                "numpy by contract", node.lineno))
    return findings


def _lint_donation(sites: list, relpath: str) -> list:
    """SC201 over the module's jit sites."""
    findings = []
    seen = set()
    for site in sites:
        if site.body is None:
            continue
        params = _param_names(site.body)
        cache_idx = [i for i, p in enumerate(params)
                     if "cache" in p or p == "pools"]
        if not cache_idx:
            continue
        key = (site.qual, tuple(cache_idx))
        if key in seen:
            continue
        seen.add(key)
        donated = set()
        for d in site.donate:
            if isinstance(d, int):
                donated.add(d)
            elif isinstance(d, str) and d in params:
                donated.add(params.index(d))
        missing = [params[i] for i in cache_idx if i not in donated]
        if not site.has_donate or missing:
            names = ", ".join(missing or [params[i] for i in cache_idx])
            findings.append(Finding(
                "SC201", relpath, site.qual,
                f"cache-carrying jit site does not donate {names!r}: "
                "the pool is copied on every dispatch",
                getattr(site.node, "lineno", 0)))
    return findings


def run_ast_rules(root: str | Path, repo_root: str | Path | None = None
                  ) -> list:
    """Run every AST rule over the ``.py`` files under ``root``.

    ``repo_root`` anchors the repo-relative paths used in finding keys
    (defaults to the directory containing ``src``, inferred from
    ``root``)."""
    root = Path(root).resolve()
    if repo_root is None:
        repo_root = root
        while repo_root.name not in ("", "repo") and \
                not (repo_root / ".git").exists():
            if repo_root.parent == repo_root:
                break
            repo_root = repo_root.parent
    repo_root = Path(repo_root).resolve()

    findings: list = []
    for path in sorted(root.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        try:
            rel = path.relative_to(repo_root).as_posix()
        except ValueError:
            rel = path.as_posix()
        try:
            tree = ast.parse(path.read_text())
        except SyntaxError as e:
            findings.append(Finding("SC000", rel, "module",
                                    f"syntax error: {e}", e.lineno or 0))
            continue
        index = _ModuleIndex()
        index.visit(tree)
        sites = _find_jit_sites(tree, index)

        linted = set()
        for site in sites:
            if site.body is None or id(site.body) in linted:
                continue
            linted.add(id(site.body))
            findings += _lint_jitted_body(site.body, site.static_names,
                                          rel, site.body.name)
        findings += _lint_donation(sites, rel)

        parts = path.parts
        if "serve" in parts:
            findings += _lint_serve_host_sync(tree, rel)
            if path.name == "paging.py":
                findings += _lint_paging_numpy_only(tree, rel)
    return findings
