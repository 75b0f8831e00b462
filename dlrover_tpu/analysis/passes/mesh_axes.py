"""mesh-axes: every SPMD axis-name literal names a registered axis.

Incident (ROADMAP item 1 prep): PartitionSpec/NamedSharding axis names,
``shard_map`` in/out specs, ``param_with_axes`` annotations and
collective axis names live as bare string literals across ~56 sites in
``parallel/``, ``models/``, ``ops/``, ``trainer/`` and
``checkpoint/meta.py``. A
typo'd or drifted name does not error — flax's logical-rules fallback
silently *stops constraining* (``RulesFallback.NO_CONSTRAINT``), so the
leaf quietly replicates and the job trains slower or OOMs at a bigger
scale, with nothing pointing at the one character that changed. The
elastic DP×TP×PP resharding refactor will rewrite exactly these sites.

Rule: ``parallel/mesh.py::MESH_AXIS_REGISTRY`` is the single source of
truth (the ENV_KNOBS idiom) — a pure-literal dict so this pass can read
it by AST without importing jax. Per file:

- every string literal inside a ``PartitionSpec``/``P(...)`` call
  (aliases resolved through the file's imports) must be a registered
  axis (mesh or logical — both legitimately appear in specs);
- ``param_with_axes(..., axes=...)`` and
  ``with_logical_constraint``/``constrain`` string arguments must be
  registered *logical* axes (a mesh axis there is exactly the
  silent-no-constraint drift);
- ``axis_name=``/``*_axis`` keyword values and string parameter
  defaults, ``jax.lax`` collective axis arguments, and
  ``mesh.shape["..."]`` subscripts must be registered *mesh* axes;
- module-level ``*_AXES`` tuple constants must contain only registered
  names.

Repo-wide, the registry is cross-checked against the mesh construction
sites and the logical-rule table:

- ``MESH_AXES`` must equal the registry's kind-"mesh" entries, in
  order (``build_mesh``'s reshape order is load-bearing);
- every ``Mesh(...)`` construction must take ``MESH_AXES`` (or a
  literal tuple of registered mesh axes);
- ``sharding.DEFAULT_RULES`` keys must be registered logical axes and
  its targets registered mesh axes; every registered logical axis must
  be mapped by a rule;
- a registered axis referenced nowhere is a stale entry (the registry
  must not rot).
"""

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from ..core import FileContext, Violation, call_name, dotted_name, keyword_map

PASS_ID = "mesh-axes"

_MESH_REL = os.path.join("dlrover_tpu", "parallel", "mesh.py")
_MESH_POSIX = "dlrover_tpu/parallel/mesh.py"
_SHARDING_REL = os.path.join("dlrover_tpu", "parallel", "sharding.py")
_SHARDING_POSIX = "dlrover_tpu/parallel/sharding.py"

# dirs whose files carry spec literals (the staleness scan's scope)
_SCAN_DIRS = ("parallel", "models", "ops", "trainer")
_SCAN_FILES = ("checkpoint/meta.py",)

_LOGICAL_CALLS = {"param_with_axes", "with_logical_constraint", "constrain"}
_COLLECTIVE_CALLS = {
    "psum", "pmean", "pmax", "pmin", "axis_index", "ppermute",
    "all_gather", "psum_scatter", "all_to_all",
}
_AXIS_KWARG_RE = re.compile(r"^(axis_name|seq_axis|[a-z_]*_axis)$")
_AXIS_PARAM_RE = re.compile(r"^(axis|axis_name|seq_axis|[a-z_]*_axis)$")
_AXES_CONST_RE = re.compile(r"^_?[A-Z0-9_]*AXES$")


def _stamp(path: str) -> Optional[Tuple[int, int]]:
    """(mtime_ns, size) cache key so a stateful pass re-parses its
    source tables when they are edited within one process (watch modes,
    harnesses looping over a tmp root)."""
    try:
        st = os.stat(path)
    except OSError:
        return None
    return st.st_mtime_ns, st.st_size


def _literal_assign(tree: ast.AST, name: str) -> Optional[ast.AST]:
    """The value node of a module-level ``name = <literal>`` (or
    annotated) assignment."""
    for node in tree.body if isinstance(tree, ast.Module) else []:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == name:
                    return node.value
        elif isinstance(node, ast.AnnAssign):
            if isinstance(node.target, ast.Name) and node.target.id == name:
                return node.value
    return None


def load_axis_registry(
    mesh_path: str,
) -> Tuple[Optional[Dict[str, str]], Optional[Tuple[str, ...]], str]:
    """(axis name -> kind, MESH_AXES tuple, error) parsed from
    ``parallel/mesh.py`` WITHOUT importing it (the module imports jax)."""
    try:
        tree = ast.parse(open(mesh_path, encoding="utf-8").read())
    except (OSError, SyntaxError) as e:
        return None, None, f"cannot parse {mesh_path}: {e}"
    reg_node = _literal_assign(tree, "MESH_AXIS_REGISTRY")
    if reg_node is None:
        return None, None, "MESH_AXIS_REGISTRY not assigned at module level"
    try:
        raw = ast.literal_eval(reg_node)
        registry = {
            str(name): str(entry[0]) for name, entry in raw.items()
        }
    except (ValueError, TypeError, IndexError, KeyError):
        return None, None, (
            "MESH_AXIS_REGISTRY is not a pure literal dict of "
            "name -> (kind, doc) — computed entries are invisible to "
            "the AST lint"
        )
    axes_node = _literal_assign(tree, "MESH_AXES")
    mesh_axes: Optional[Tuple[str, ...]] = None
    if axes_node is not None:
        try:
            mesh_axes = tuple(ast.literal_eval(axes_node))
        except (ValueError, TypeError):
            mesh_axes = None
    return registry, mesh_axes, ""


def _spec_call_names(tree: ast.AST) -> Set[str]:
    """Local names bound to ``jax.sharding.PartitionSpec`` in this file
    (``PartitionSpec``, ``P``, …) via imports or simple aliasing."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and (
            node.module.startswith("jax")
        ):
            for alias in node.names:
                if alias.name == "PartitionSpec":
                    names.add(alias.asname or alias.name)
        elif isinstance(node, ast.Assign) and isinstance(
            node.value, (ast.Name, ast.Attribute)
        ):
            src = dotted_name(node.value)
            if src.split(".")[-1] == "PartitionSpec":
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
    return names


def _str_entries(expr: ast.AST) -> Iterable[str]:
    """String literals in a spec entry: "dp", ("dp", "fsdp"), None…"""
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        yield expr.value
    elif isinstance(expr, (ast.Tuple, ast.List)):
        for e in expr.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                yield e.value


def iter_axis_sites(
    ctx: FileContext,
) -> Iterable[Tuple[str, str, int, str]]:
    """(axis_literal, required_kind, line, where) for every axis-name
    site in the file. ``required_kind`` is "mesh", "logical" or "any"."""
    spec_names = _spec_call_names(ctx.tree)
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            name = call_name(node)
            dn = dotted_name(node.func)
            if name in spec_names:
                for arg in node.args:
                    for s in _str_entries(arg):
                        yield s, "any", node.lineno, f"{name}(...) spec"
            elif name in _LOGICAL_CALLS:
                for arg in node.args[1:] if name != "param_with_axes" else []:
                    for s in _str_entries(arg):
                        yield s, "logical", node.lineno, f"{name}(...)"
                axes_kw = keyword_map(node).get("axes")
                if axes_kw is not None:
                    for s in _str_entries(axes_kw):
                        yield s, "logical", node.lineno, f"{name}(axes=...)"
            elif name in _COLLECTIVE_CALLS and (
                dn.startswith("jax.lax.") or dn.startswith("lax.")
            ):
                for arg in node.args:
                    for s in _str_entries(arg):
                        yield s, "mesh", node.lineno, f"{name}(...) collective"
            # axis-name keywords on ANY call (shard_map wrappers,
            # partial(ring_attention, axis_name=...), …)
            for kw, val in keyword_map(node).items():
                if _AXIS_KWARG_RE.match(kw or ""):
                    if isinstance(val, ast.Constant) and isinstance(
                        val.value, str
                    ):
                        yield val.value, "mesh", node.lineno, f"{kw}= keyword"
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            a = node.args
            params = a.posonlyargs + a.args
            defaults = a.defaults
            for arg, default in zip(params[len(params) - len(defaults):], defaults):
                if _AXIS_PARAM_RE.match(arg.arg) and isinstance(
                    default, ast.Constant
                ) and isinstance(default.value, str):
                    yield (
                        default.value, "mesh", node.lineno,
                        f"default of parameter {arg.arg!r}",
                    )
            for arg, default in zip(a.kwonlyargs, a.kw_defaults):
                if default is not None and _AXIS_PARAM_RE.match(
                    arg.arg
                ) and isinstance(default, ast.Constant) and isinstance(
                    default.value, str
                ):
                    yield (
                        default.value, "mesh", node.lineno,
                        f"default of parameter {arg.arg!r}",
                    )
        elif isinstance(node, ast.Subscript):
            v = node.value
            if isinstance(v, ast.Attribute) and v.attr == "shape":
                sl = node.slice
                if isinstance(sl, ast.Constant) and isinstance(sl.value, str):
                    yield sl.value, "mesh", node.lineno, ".shape[...] subscript"
        elif isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and _AXES_CONST_RE.match(t.id):
                    for s in _str_entries(node.value):
                        yield s, "any", node.lineno, f"{t.id} constant"


class MeshAxesPass:
    """Stateful so the registry is parsed once per run."""

    pass_id = PASS_ID

    def __init__(self):
        self._key = None
        self._registry: Optional[Dict[str, str]] = None
        self._mesh_axes: Optional[Tuple[str, ...]] = None
        self._error = ""

    def _ensure(self, root: str):
        mesh_path = os.path.join(root, _MESH_REL)
        key = (root, _stamp(mesh_path))
        if self._key == key:
            return
        self._key = key
        self._registry, self._mesh_axes, self._error = load_axis_registry(
            mesh_path
        )

    def _root_of(self, ctx: FileContext) -> Optional[str]:
        suffix = ctx.rel.replace("/", os.sep)
        if ctx.path.endswith(suffix):
            root = ctx.path[: -len(suffix) - 1]
            if os.path.exists(os.path.join(root, _MESH_REL)):
                return root
        return None

    # -- per-file ----------------------------------------------------------

    def check_file(self, ctx: FileContext) -> Iterable[Violation]:
        root = self._root_of(ctx)
        if root is None:
            return
        self._ensure(root)
        if self._registry is None:
            return  # the registry parse failure is reported repo-level
        for axis, required, line, where in iter_axis_sites(ctx):
            kind = self._registry.get(axis)
            if kind is None:
                yield Violation(
                    PASS_ID,
                    ctx.rel,
                    line,
                    f"axis name {axis!r} ({where}) is not in "
                    "parallel/mesh.py MESH_AXIS_REGISTRY — a typo'd axis "
                    "silently stops constraining (flax NO_CONSTRAINT "
                    "fallback); register it or fix the name",
                    code=ctx.code_at(line),
                )
            elif required != "any" and kind != required:
                yield Violation(
                    PASS_ID,
                    ctx.rel,
                    line,
                    f"axis {axis!r} ({where}) is registered as a {kind} "
                    f"axis but this site requires a {required} axis — "
                    + (
                        "a mesh axis in a logical annotation is exactly "
                        "the silent-no-constraint drift"
                        if required == "logical"
                        else "collectives/mesh lookups ride physical "
                        "mesh axes, not logical names"
                    ),
                    code=ctx.code_at(line),
                )

    # -- repo-level --------------------------------------------------------

    def repo_check(
        self, root: str, contexts: List[FileContext]
    ) -> Iterable[Violation]:
        mesh_path = os.path.join(root, _MESH_REL)
        if not os.path.exists(mesh_path):
            return
        self._ensure(root)
        if self._registry is None:
            yield Violation(
                PASS_ID, _MESH_POSIX, 0,
                f"mesh-axis registry unreadable: {self._error}",
                code="registry-parse",
            )
            return
        registry = self._registry
        mesh_kind = tuple(k for k, v in registry.items() if v == "mesh")
        logical_kind = {k for k, v in registry.items() if v == "logical"}

        # 1. MESH_AXES must equal the registry's mesh entries, in order
        if self._mesh_axes is None or self._mesh_axes != mesh_kind:
            yield Violation(
                PASS_ID, _MESH_POSIX, 0,
                f"MESH_AXES {self._mesh_axes!r} != registry mesh axes "
                f"{mesh_kind!r} — build_mesh's reshape order is "
                "load-bearing; keep the tuple and the registry in sync",
                code="mesh-axes-drift",
            )

        # collect sites + Mesh() constructions over the scanned tree —
        # reusing run_lint's already-parsed contexts; disk parses only
        # for scan files outside the lint scope (subset runs)
        by_rel = {ctx.rel: ctx for ctx in contexts}
        referenced: Set[str] = set()
        scan_paths: List[str] = []
        pkg = os.path.join(root, "dlrover_tpu")
        for d in _SCAN_DIRS:
            base = os.path.join(pkg, d)
            if os.path.isdir(base):
                for dirpath, dirnames, filenames in os.walk(base):
                    dirnames[:] = [x for x in dirnames if x != "__pycache__"]
                    scan_paths.extend(
                        os.path.join(dirpath, fn)
                        for fn in sorted(filenames)
                        if fn.endswith(".py")
                    )
        scan_paths.extend(
            p
            for f in _SCAN_FILES
            if os.path.exists(p := os.path.join(pkg, f.replace("/", os.sep)))
        )
        for path in scan_paths:
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            fctx = by_rel.get(rel) or FileContext.parse(path, rel)
            if fctx is None:
                continue
            for axis, _req, _line, _where in iter_axis_sites(fctx):
                referenced.add(axis)
            # 2. Mesh construction sites take MESH_AXES or registered
            #    literal tuples
            for node in ast.walk(fctx.tree):
                if not (
                    isinstance(node, ast.Call)
                    and call_name(node) == "Mesh"
                ):
                    continue
                # positional or keyword form: Mesh(devs, axes) /
                # Mesh(devs, axis_names=axes)
                axes_arg = (
                    node.args[1]
                    if len(node.args) >= 2
                    else keyword_map(node).get("axis_names")
                )
                if axes_arg is None:
                    continue  # not a jax Mesh construction
                if isinstance(axes_arg, ast.Name) and axes_arg.id == "MESH_AXES":
                    referenced.update(mesh_kind)
                    continue
                literals = list(_str_entries(axes_arg))
                if literals:
                    referenced.update(literals)
                    bad = [a for a in literals if a not in mesh_kind]
                    if bad:
                        yield Violation(
                            PASS_ID, rel, node.lineno,
                            f"Mesh(...) constructed with unregistered "
                            f"axes {bad!r} — mesh construction and the "
                            "registry must agree",
                            code=fctx.code_at(node.lineno),
                        )
                else:
                    yield Violation(
                        PASS_ID, rel, node.lineno,
                        "Mesh(...) constructed with axes that are "
                        "neither MESH_AXES nor a literal tuple — the "
                        "registry cross-check cannot see this mesh; "
                        "route it through MESH_AXES",
                        code=fctx.code_at(node.lineno),
                    )

        # 3. DEFAULT_RULES conformance
        rules_keys: Set[str] = set()
        sharding_path = os.path.join(root, _SHARDING_REL)
        if os.path.exists(sharding_path):
            sctx = by_rel.get(_SHARDING_POSIX)
            if sctx is not None:
                stree = sctx.tree
            else:
                try:
                    stree = ast.parse(
                        open(sharding_path, encoding="utf-8").read()
                    )
                except (OSError, SyntaxError):
                    stree = None
            rules_node = (
                _literal_assign(stree, "DEFAULT_RULES") if stree else None
            )
            rules = None
            if rules_node is not None:
                try:
                    rules = ast.literal_eval(rules_node)
                except (ValueError, TypeError):
                    rules = None
            if rules is None:
                yield Violation(
                    PASS_ID, _SHARDING_POSIX, 0,
                    "DEFAULT_RULES is not a pure-literal list — the "
                    "logical→mesh cross-check cannot see it",
                    code="rules-parse",
                )
            else:
                for entry in rules:
                    logical, target = entry[0], entry[1]
                    rules_keys.add(logical)
                    referenced.add(logical)
                    targets = (
                        tuple(target)
                        if isinstance(target, (tuple, list))
                        else (target,)
                    )
                    for t in targets:
                        if t is None:
                            continue
                        referenced.add(t)
                        if t not in mesh_kind:
                            yield Violation(
                                PASS_ID, _SHARDING_POSIX, 0,
                                f"DEFAULT_RULES maps {logical!r} onto "
                                f"{t!r}, which is not a registered mesh "
                                "axis",
                                code=f"rule-target:{logical}:{t}",
                            )
                    if logical not in logical_kind:
                        yield Violation(
                            PASS_ID, _SHARDING_POSIX, 0,
                            f"DEFAULT_RULES key {logical!r} is not a "
                            "registered logical axis",
                            code=f"rule-key:{logical}",
                        )
                for name in sorted(logical_kind - rules_keys):
                    yield Violation(
                        PASS_ID, _SHARDING_POSIX, 0,
                        f"logical axis {name!r} is registered but "
                        "DEFAULT_RULES does not map it — add a rule or "
                        "delete the entry",
                        code=f"unmapped:{name}",
                    )

        # 4. staleness: registered axes nobody references
        for name in sorted(set(registry) - referenced):
            yield Violation(
                PASS_ID, _MESH_POSIX, 0,
                f"registered axis {name!r} is referenced by no spec "
                "site, rule or mesh construction — delete the entry "
                "(the registry must not rot)",
                code=f"stale:{name}",
            )


PASS = MeshAxesPass()
check_file = PASS.check_file
repo_check = PASS.repo_check
