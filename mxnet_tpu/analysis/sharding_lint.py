"""mxshard — static SPMD partition-spec propagation and collective-cost lint.

The spd pass (``tools/mxlint.py --passes spd``) is the sharding analog of
mxflow's host-sync pass: it parses every mesh construction, ``P(...)`` /
``partition_specs()`` literal, and ``shard_map`` region boundary across
``mxnet_tpu/parallel/`` and ``mxnet_tpu/serving/decode/``, attributes every
collective call site (raw ``jax.lax`` or the instrumented wrappers in
``parallel/collectives.py``) to its axis and region, and refuses
un-sanctioned cross-device data movement.  Its runtime twin is the
per-(kind, axis) counter table in ``parallel/collectives.py`` — the static
per-region site counts and the runtime trace-time counter deltas are pinned
to one ground truth in tests/test_mxshard.py.

Abstract-sharding model
-----------------------
* **Axis universe** — every literal mesh construction (``Mesh(devs,
  ("tp", "sp"))``, via ``decode_mesh``/``make_mesh``) plus the
  ``MeshConfig`` field names declares axes; an axis named by a collective
  or a ``P(...)`` entry must come from this universe.  (Meshes threaded
  through parameters are not resolved per-region — the universe check is
  the sound static relaxation; see docs/LINT.md.)
* **Sites** — a collective site is a call to a known collective name with
  a resolved ``kind`` (psum / all_gather / reduce_scatter / ppermute /
  all_to_all) and a best-effort axis (string literal, parameter default,
  or single local string assignment, walking lexical ancestors).
  ``axis_size`` / ``psum(1, ax)`` is a trace-time constant, not a
  collective.  The wrapper definitions in ``parallel/collectives.py`` are
  the instrumentation layer and are exempt.
* **Regions** — a ``shard_map(body, mesh=..., in_specs=...)`` call or a
  ``@functools.partial(shard_map, ...)`` decorator opens a region; the
  body's call closure (including sibling nested defs the generic call
  graph cannot resolve) is the traced block collective budgets count.

Rules (empty baseline; fix or tag, never suppress)
--------------------------------------------------
SPD001  un-sanctioned ``all_gather`` (compute-on-replicated when it
        provably feeds a matmul/attention in-function — the measured
        gather tax); sanctioned only by ``# mxshard: gather-ok(<reason>)``
        or a region ``all_gather`` budget.
SPD002  collective-budget breach (sites per kind in a region's closure vs
        its declared ``# mxshard: budget(psum=1, ...)``) and any other
        un-sanctioned collective.
SPD003  axis-name errors: collective axis or ``P(...)`` entry absent from
        the axis universe; declared mesh axis never used anywhere.
SPD004  divisibility-demanding construct (tiled ``all_to_all``;
        ``shard_map`` whose in_specs shard a named axis) with no eager
        extent-naming guard (a ``check_*`` call or an if/raise naming the
        extents) in the function, its lexical ancestors, or its class.
SPD005  psum-family collective on a bitwise-gated path (anything under
        ``serving/decode/`` or marked ``# mxshard: bitwise``) without a
        ``# mxshard: allclose-ok(<reason>)`` sanction (reduction-order
        nondeterminism breaks the bitwise contract).
SPD006  collective inside a ``lax.scan`` / ``fori_loop`` / ``while_loop``
        body (a hidden collective per step) without
        ``# mxshard: reshard-ok(<reason>)``.
SPD007  tag hygiene: malformed/empty-reason/kind-mismatched ``mxshard:``
        annotations, stale tags on non-collective lines, budgets attached
        to non-region defs.

Every sanctioned site is cataloged in docs/COLLECTIVE_MAP.md
(``tools/mxlint.py --collective-map``; freshness-gated in tier-1).
"""
from __future__ import annotations

import ast
import re

from .common import Finding
from . import dataflow
from .dataflow import _own_nodes, _unparse

__all__ = ["run", "analyze_source", "collective_sites",
           "source_collective_sites", "site_counts",
           "region_collective_counts", "collective_map_entries",
           "render_collective_map", "predict_decode_step_collectives",
           "SCAN_PREFIXES"]

#: repo-relative path prefixes the pass scans (and --since triggers on)
SCAN_PREFIXES = ("mxnet_tpu/parallel/", "mxnet_tpu/serving/decode/",
                 "mxnet_tpu/serving/disagg/", "mxnet_tpu/serving/deploy.py")
#: the wrapper/instrumentation module — definitions, not uses
_WRAPPER_MODULE = "mxnet_tpu/parallel/collectives.py"
#: paths on the bitwise-gated serving contract (SPD005)
_BITWISE_PREFIX = "mxnet_tpu/serving/decode/"

# collective callee name -> canonical kind (matches the runtime counter
# kinds in parallel/collectives.py)
_KINDS = {
    "psum": "psum", "allreduce": "psum", "pmean": "psum",
    "all_gather": "all_gather", "allgather": "all_gather",
    "psum_scatter": "reduce_scatter", "reduce_scatter": "reduce_scatter",
    "ppermute": "ppermute", "ppermute_ring": "ppermute",
    "all_to_all": "all_to_all",
}
_KIND_NAMES = ("psum", "all_gather", "reduce_scatter", "ppermute",
               "all_to_all")
_REDUCE_KINDS = {"psum", "reduce_scatter"}

# sanction verb -> kinds it may sanction
_VERB_KINDS = {
    "gather-ok": {"all_gather"},
    "reduce-ok": {"psum", "reduce_scatter"},
    "reshard-ok": {"ppermute", "all_to_all"},
    "allclose-ok": {"psum", "reduce_scatter"},
}

_TAG_RE = re.compile(r"mxshard:\s*([a-z]+-ok)\s*\(([^()]*)\)")
_BUDGET_RE = re.compile(r"mxshard:\s*budget\s*\(([^()]*)\)")
_BITWISE_RE = re.compile(r"mxshard:\s*bitwise\b")
_ANY_MXSHARD_RE = re.compile(r"mxshard:")
_BUDGET_ITEM_RE = re.compile(r"^\s*([a-z_]+)\s*=\s*(\d+)\s*$")

_LOOP_NAMES = {"fori_loop", "scan", "while_loop"}
_COMPUTE_CALLS = {"einsum", "dot", "matmul", "tensordot", "dot_general",
                  "conv_general_dilated"}
# calls a gathered operand may flow through without counting as compute
_SHAPE_ONLY_CALLS = {"reshape", "astype", "transpose", "swapaxes",
                     "dynamic_slice", "dynamic_slice_in_dim",
                     "slice_in_dim", "squeeze", "expand_dims",
                     "concatenate", "stop_gradient", "tuple", "dict",
                     "list"} | set(_KINDS) | {"axis_size", "axis_index"}


def _callee_name(node):
    """Bare name of a Call's callee (Name or Attribute), else None."""
    f = node.func if isinstance(node, ast.Call) else node
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _is_numeric_const(node):
    return isinstance(node, ast.Constant) and isinstance(
        node.value, (int, float)) and not isinstance(node.value, bool)


class _Site(object):
    """One collective call site."""
    __slots__ = ("fn", "node", "line", "kind", "axis", "verb", "reason",
                 "feeds_compute")

    def __init__(self, fn, node, kind, axis):
        self.fn = fn
        self.node = node
        self.line = node.lineno
        self.kind = kind
        self.axis = axis            # resolved axis string, or None
        self.verb = None            # sanction tag verb on the site line
        self.reason = None
        self.feeds_compute = False

    @property
    def path(self):
        return self.fn.path


class _Region(object):
    """One shard_map region: the traced block budgets count against."""
    __slots__ = ("owner", "body", "line", "call", "in_specs", "closure")

    def __init__(self, owner, body, line, call, in_specs):
        self.owner = owner          # _Func containing the construction
        self.body = body            # _Func traced as the body (may be None)
        self.line = line
        self.call = call            # the shard_map Call / partial Call
        self.in_specs = in_specs    # ast expr or None
        self.closure = ()           # _Func keys in the traced closure

    @property
    def qual(self):
        return (self.body.qual if self.body is not None
                else "%s@%d" % (self.owner.qual, self.line))


class _Analysis(object):
    def __init__(self, graph, repo_mode=True):
        self.graph = graph
        self.repo_mode = repo_mode
        self.modules = [
            m for m in graph.modules.values()
            if not repo_mode or m.path.startswith(SCAN_PREFIXES)]
        self.by_qual = {}           # (module path, qual) -> _Func
        for mod in self.modules:
            for fn in mod.func_order:
                self.by_qual[(mod.path, fn.qual)] = fn
        self.declared = []          # [(mod, line, scope, axes tuple)]
        self.universe = set()
        self.usage = set()          # axis names referenced anywhere
        self.pspec_axes = []        # [(mod, line, scope, axis)]
        self.sites = []             # [_Site] (wrapper module exempt)
        self.regions = []           # [_Region]
        self.budgets = {}           # fn key -> (line, {kind: int})
        self.bitwise_fns = set()    # fn keys marked "# mxshard: bitwise"
        self.loop_bodies = set()    # fn keys passed to scan/fori/while
        self.extra_edges = {}       # fn key -> [callee keys] (nested sibs)
        self._collect()

    # -- collection -----------------------------------------------------
    def _scope_of(self, mod, node):
        best = "<module>"
        for fn in mod.func_order:
            n = fn.node
            if (n.lineno <= node.lineno
                    and node.lineno <= (getattr(n, "end_lineno", n.lineno)
                                        or n.lineno)):
                best = fn.qual
        return best

    def _collect(self):
        for mod in self.modules:
            if mod.tree is None:
                continue
            self._collect_meshes_and_specs(mod)
            for fn in mod.func_order:
                self._collect_fn(mod, fn)
        self._resolve_regions()
        self._mark_loop_bodies()
        self._collect_usage()
        for site in self.sites:
            if site.kind == "all_gather":
                site.feeds_compute = _feeds_compute(site)

    def _collect_meshes_and_specs(self, mod):
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ClassDef) and node.name == "MeshConfig":
                for stmt in node.body:
                    if isinstance(stmt, ast.AnnAssign) and isinstance(
                            stmt.target, ast.Name):
                        self.universe.add(stmt.target.id)
            if not isinstance(node, ast.Call):
                continue
            name = _callee_name(node)
            if name == "Mesh" and len(node.args) >= 2:
                axes_node = node.args[1]
                if isinstance(axes_node, (ast.Tuple, ast.List)):
                    axes = tuple(
                        e.value for e in axes_node.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, str))
                    if axes and len(axes) == len(axes_node.elts):
                        self.universe.update(axes)
                        self.declared.append(
                            (mod, node.lineno, self._scope_of(mod, node),
                             axes))
            elif name in ("P", "PartitionSpec"):
                for arg in node.args:
                    if isinstance(arg, ast.Constant) and isinstance(
                            arg.value, str):
                        self.pspec_axes.append(
                            (mod, arg.lineno, self._scope_of(mod, node),
                             arg.value))

    def _collect_fn(self, mod, fn):
        key = fn.key
        # budget / bitwise annotations on the def line or the line above
        first = fn.node.lineno
        for dec in fn.node.decorator_list:
            first = min(first, dec.lineno)
        for ln in (fn.node.lineno, first, first - 1):
            comment = mod.comments.get(ln, "")
            m = _BUDGET_RE.search(comment)
            if m and key not in self.budgets:
                budget = _parse_budget(m.group(1))
                if budget is not None:
                    self.budgets[key] = (ln, budget)
            if _BITWISE_RE.search(comment):
                self.bitwise_fns.add(key)

        exempt = self.repo_mode and mod.path == _WRAPPER_MODULE
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _callee_name(node)
            if name == "shard_map":
                self.regions.append(self._region_from_call(fn, node))
                continue
            kind = _KINDS.get(name)
            if kind is None or exempt:
                continue
            if name == "axis_size":
                continue
            if (kind == "psum" and node.args
                    and _is_numeric_const(node.args[0])):
                continue  # psum(1, ax): static axis size, not a collective
            site = _Site(fn, node, kind, _axis_of(node, self, fn))
            for ln in range(node.lineno,
                            (getattr(node, "end_lineno", None)
                             or node.lineno) + 1):
                tag = _TAG_RE.search(mod.comments.get(ln, ""))
                if tag:
                    site.verb = tag.group(1)
                    site.reason = tag.group(2).strip()
                    break
            self.sites.append(site)
        # decorator form: @functools.partial(shard_map, mesh=..., ...)
        for dec in fn.node.decorator_list:
            if (isinstance(dec, ast.Call)
                    and _callee_name(dec) == "partial" and dec.args
                    and _callee_name(dec.args[0]) == "shard_map"):
                in_specs = _kwarg(dec, "in_specs")
                self.regions.append(
                    _Region(fn, fn, fn.node.lineno, dec, in_specs))

    def _region_from_call(self, fn, call):
        body_expr = call.args[0] if call.args else None
        if (isinstance(body_expr, ast.Call)
                and _callee_name(body_expr) == "partial"
                and body_expr.args):
            body_expr = body_expr.args[0]
        body = None
        if isinstance(body_expr, ast.Name):
            body = self._resolve_func_name(fn, body_expr.id)
        in_specs = _kwarg(call, "in_specs")
        if in_specs is None and len(call.args) >= 3:
            in_specs = call.args[2]
        return _Region(fn, body, call.lineno, call, in_specs)

    def _resolve_func_name(self, fn, name):
        """Resolve ``name`` from ``fn``'s scope to a _Func: nested defs of
        ``fn`` or any lexical ancestor first (the call graph cannot see
        sibling nested defs), then module-level resolution."""
        mod = fn.module
        for anc_qual in [fn.qual] + _qual_prefixes(fn.qual):
            got = self.by_qual.get((mod.path, "%s.%s" % (anc_qual, name)))
            if got is not None:
                return got
        got = self.by_qual.get((mod.path, name))
        if got is not None:
            return got
        resolved = self.graph.resolve_symbol(mod, name)
        if resolved and resolved[0] == "func":
            return self.graph.funcs.get(resolved[1])
        return None

    def _resolve_regions(self):
        # supplementary edges: calls to sibling/ancestor-nested defs
        for mod in self.modules:
            for fn in mod.func_order:
                extra = []
                known = {k for k, _ in fn.calls}
                for node in _own_nodes(fn):
                    if isinstance(node, ast.Call) and isinstance(
                            node.func, ast.Name):
                        got = self._resolve_func_name(fn, node.func.id)
                        if (got is not None and got.key != fn.key
                                and got.key not in known):
                            extra.append(got.key)
                self.extra_edges[fn.key] = extra
        for region in self.regions:
            region.closure = self._closure(region.body)

    def _closure(self, body):
        if body is None:
            return ()
        seen = {body.key}
        queue = [body]
        while queue:
            fn = queue.pop()
            callees = [k for k, _ in fn.calls]
            callees += self.extra_edges.get(fn.key, [])
            for key in callees:
                callee = self.graph.funcs.get(key)
                if (callee is None or callee.key in seen
                        or (self.repo_mode
                            and not callee.path.startswith(SCAN_PREFIXES))):
                    continue
                seen.add(callee.key)
                queue.append(callee)
        return tuple(seen)

    def _mark_loop_bodies(self):
        for mod in self.modules:
            for fn in mod.func_order:
                nested = {f.name: f for f in mod.func_order
                          if f.qual.startswith(fn.qual + ".")
                          and "." not in f.qual[len(fn.qual) + 1:]}
                if not nested:
                    continue
                for node in _own_nodes(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    if _callee_name(node) not in _LOOP_NAMES:
                        continue
                    for arg in node.args:
                        if (isinstance(arg, ast.Name)
                                and arg.id in nested):
                            self.loop_bodies.add(nested[arg.id].key)

    def _collect_usage(self):
        for site in self.sites:
            if site.axis:
                self.usage.add(site.axis)
        for _mod, _line, _scope, axis in self.pspec_axes:
            self.usage.add(axis)
        for mod in self.modules:
            if mod.tree is None:
                continue
            for node in ast.walk(mod.tree):
                if isinstance(node, ast.Call):
                    for kw in node.keywords:
                        if (kw.arg == "axis_name"
                                and isinstance(kw.value, ast.Constant)
                                and isinstance(kw.value.value, str)):
                            self.usage.add(kw.value.value)
                    # axis_size/axis_index reference the axis without
                    # performing a collective — still a use
                    if _callee_name(node) in ("axis_size", "axis_index"):
                        for arg in node.args:
                            if (isinstance(arg, ast.Constant)
                                    and isinstance(arg.value, str)):
                                self.usage.add(arg.value)
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    for p, d in _param_defaults(node):
                        if (p == "axis_name"
                                and isinstance(d, ast.Constant)
                                and isinstance(d.value, str)):
                            self.usage.add(d.value)

    # -- helpers --------------------------------------------------------
    def lexical_ancestors(self, fn):
        """fn plus every enclosing _Func (by qual prefix)."""
        out = [fn]
        for pq in _qual_prefixes(fn.qual):
            got = self.by_qual.get((fn.module.path, pq))
            if got is not None:
                out.append(got)
        return out

    def in_loop_body(self, fn):
        if fn.key in self.loop_bodies:
            return True
        for pq in _qual_prefixes(fn.qual):
            got = self.by_qual.get((fn.module.path, pq))
            if got is not None and got.key in self.loop_bodies:
                return True
        return False

    def on_bitwise_path(self, site):
        if self.repo_mode and site.path.startswith(_BITWISE_PREFIX):
            return True
        return any(f.key in self.bitwise_fns
                   for f in self.lexical_ancestors(site.fn))

    def budget_cover(self):
        """-> (covered site ids, breach findings).  A region's declared
        budget covers the first N sites (by file/line order) of each
        budgeted kind in its closure; the excess breaches."""
        covered = set()
        findings = []
        sites_by_fn = {}
        for s in self.sites:
            sites_by_fn.setdefault(s.fn.key, []).append(s)
        for region in self.regions:
            if region.body is None:
                continue
            got = self.budgets.get(region.body.key)
            if got is None:
                continue
            _ln, budget = got
            by_kind = {}
            for key in region.closure:
                for s in sites_by_fn.get(key, ()):
                    by_kind.setdefault(s.kind, []).append(s)
            for kind, allowed in budget.items():
                sites = sorted(by_kind.get(kind, ()),
                               key=lambda s: (s.path, s.line))
                for s in sites[:allowed]:
                    covered.add(id(s))
                for s in sites[allowed:]:
                    findings.append(Finding(
                        "SPD002", s.path, s.line, s.fn.qual,
                        "collective budget breach: %d %s site(s) in region "
                        "`%s` exceed its declared budget(%s=%d)"
                        % (len(sites), kind, region.qual, kind, allowed),
                        detail="budget:%s@%s" % (kind, region.qual)))
        return covered, findings


def _qual_prefixes(qual):
    """Enclosing quals, innermost first: "A.b.c" -> ["A.b", "A"]."""
    out = []
    while "." in qual:
        qual = qual.rsplit(".", 1)[0]
        out.append(qual)
    return out


def _kwarg(call, name):
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _param_defaults(node):
    """[(param name, default node)] for a function def."""
    args = node.args
    out = []
    pos = args.posonlyargs + args.args
    for p, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        out.append((p.arg, d))
    for p, d in zip(args.kwonlyargs, args.kw_defaults):
        if d is not None:
            out.append((p.arg, d))
    return out


def _parse_budget(text):
    """"psum=1, all_gather=3" -> {kind: int}; None if malformed."""
    budget = {}
    for part in text.split(","):
        if not part.strip():
            return None
        m = _BUDGET_ITEM_RE.match(part)
        if m is None or m.group(1) not in _KIND_NAMES:
            return None
        budget[m.group(1)] = int(m.group(2))
    return budget or None


def _axis_of(call, analysis, fn):
    """Best-effort collective axis: 2nd positional / axis_name kwarg,
    resolved through parameter defaults and single constant assignments
    in the lexical scope chain."""
    expr = call.args[1] if len(call.args) >= 2 else _kwarg(call, "axis_name")
    if expr is None:
        name = _callee_name(call)
        if name in ("allreduce", "allgather", "reduce_scatter", "pmean"):
            return "dp"  # the wrappers' default axis
        return None
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    if isinstance(expr, ast.Name):
        for scope in analysis.lexical_ancestors(fn):
            for p, d in _param_defaults(scope.node):
                if (p == expr.id and isinstance(d, ast.Constant)
                        and isinstance(d.value, str)):
                    return d.value
            for node in _own_nodes(scope):
                if (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and node.targets[0].id == expr.id
                        and isinstance(node.value, ast.Constant)
                        and isinstance(node.value.value, str)):
                    return node.value.value
    return None


def _feeds_compute(site):
    """True when the gather's result provably flows into a contraction or
    an opaque kernel call within the same function (the gather tax)."""
    fn = site.fn
    tainted = set()
    # names assigned (directly or transitively, two rounds) from the site
    for _round in (0, 1):
        for node in _own_nodes(fn):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)):
                src_names = {n.id for n in ast.walk(node.value)
                             if isinstance(n, ast.Name)}
                holds_site = any(sub is site.node
                                 for sub in ast.walk(node.value))
                if holds_site or (tainted & src_names):
                    tainted.add(node.targets[0].id)

    def is_tainted(expr):
        for sub in ast.walk(expr):
            if sub is site.node:
                return True
            if isinstance(sub, ast.Name) and sub.id in tainted:
                return True
        return False

    for node in _own_nodes(fn):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.MatMult):
            if is_tainted(node.left) or is_tainted(node.right):
                return True
        if isinstance(node, ast.Call):
            name = _callee_name(node)
            if name in _COMPUTE_CALLS:
                if any(is_tainted(a) for a in node.args):
                    return True
            elif (name is not None and name not in _SHAPE_ONLY_CALLS
                  and node is not site.node):
                # opaque call (e.g. the wrapped inner kernel): the gathered
                # operand becomes that callee's replicated compute input
                if any(is_tainted(a) for a in node.args
                       if not isinstance(a, ast.Starred)):
                    return True
    return False


# ---------------------------------------------------------------------------
# guard detection (SPD004)
# ---------------------------------------------------------------------------

def _has_guard(analysis, fn):
    """An eager divisibility guard in ``fn``, a lexical ancestor, or any
    method of its class: a ``check_*`` call, or an if/raise whose test
    looks at extents (``%`` / ``.shape`` / ``len``)."""
    scopes = list(analysis.lexical_ancestors(fn))
    if fn.cls is not None:
        scopes.extend(fn.cls.methods.values())
    seen = set()
    for scope in scopes:
        if scope.key in seen:
            continue
        seen.add(scope.key)
        for node in _own_nodes(scope):
            if (isinstance(node, ast.Call)
                    and (_callee_name(node) or "").startswith("check_")):
                return True
            if isinstance(node, ast.If) and _test_reads_extents(node.test):
                if any(isinstance(s, ast.Raise) for s in ast.walk(node)):
                    return True
    return False


def _test_reads_extents(test):
    for sub in ast.walk(test):
        if isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.Mod):
            return True
        if isinstance(sub, ast.Attribute) and sub.attr == "shape":
            return True
        if isinstance(sub, ast.Call) and _callee_name(sub) == "len":
            return True
    return False


def _demands_divisibility(analysis, region):
    """True when the region's in_specs shard a named axis (operand extents
    must divide the axis), resolving one level of local-name/function
    indirection."""
    expr = region.in_specs
    if expr is None:
        return False
    exprs = [expr]
    names = {n.id for n in ast.walk(expr) if isinstance(n, ast.Name)}
    for scope in analysis.lexical_ancestors(region.owner):
        for node in _own_nodes(scope):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id in names):
                exprs.append(node.value)
    for name in names:
        got = analysis._resolve_func_name(region.owner, name)
        if got is not None:
            exprs.append(got.node)
    for e in exprs:
        for sub in ast.walk(e):
            if (isinstance(sub, ast.Call)
                    and _callee_name(sub) in ("P", "PartitionSpec")):
                for arg in sub.args:
                    if isinstance(arg, ast.Constant):
                        if isinstance(arg.value, str):
                            return True
                    elif not (isinstance(arg, ast.Constant)
                              and arg.value is None):
                        return True  # variable axis entry
    return False


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def _analyze_graph(graph, repo_mode=True):
    analysis = _Analysis(graph, repo_mode=repo_mode)
    findings = []
    reported = set()   # site ids that already carry a specific finding

    # SPD003: axis-name errors ------------------------------------------
    for mod, line, scope, axis in analysis.pspec_axes:
        if axis not in analysis.universe:
            findings.append(Finding(
                "SPD003", mod.path, line, scope,
                "partition spec names axis %r, which no mesh construction "
                "declares (universe: %s)"
                % (axis, ", ".join(sorted(analysis.universe)) or "none"),
                detail="unknown-axis:%s" % axis))
    for site in analysis.sites:
        if site.axis is not None and site.axis not in analysis.universe:
            reported.add(id(site))
            findings.append(Finding(
                "SPD003", site.path, site.line, site.fn.qual,
                "collective %s over axis %r, which no mesh construction "
                "declares (universe: %s)"
                % (site.kind, site.axis,
                   ", ".join(sorted(analysis.universe)) or "none"),
                detail="unknown-axis:%s@%s" % (site.kind, site.axis)))
    for mod, line, scope, axes in analysis.declared:
        for axis in axes:
            if axis not in analysis.usage:
                findings.append(Finding(
                    "SPD003", mod.path, line, scope,
                    "mesh declares axis %r but no collective, partition "
                    "spec, or axis_name ever uses it" % axis,
                    detail="unused-axis:%s" % axis))

    # SPD007: tag hygiene -----------------------------------------------
    budget_lines = {(analysis.graph.funcs[key].module.path, ln)
                    for key, (ln, _b) in analysis.budgets.items()}
    region_body_keys = {r.body.key for r in analysis.regions
                        if r.body is not None}
    sites_by_line = {}
    for s in analysis.sites:
        for ln in range(s.line, (getattr(s.node, "end_lineno", None)
                                 or s.line) + 1):
            sites_by_line.setdefault((s.path, ln), []).append(s)
    for mod in analysis.modules:
        for line, comment in sorted(mod.comments.items()):
            if not _ANY_MXSHARD_RE.search(comment):
                continue
            if _BITWISE_RE.search(comment):
                continue
            tag = _TAG_RE.search(comment)
            budget = _BUDGET_RE.search(comment)
            scope = analysis._scope_of(
                mod, ast.parse("0").body[0]) if False else None
            if tag:
                verb, reason = tag.group(1), tag.group(2).strip()
                here = sites_by_line.get((mod.path, line), ())
                scope = here[0].fn.qual if here else "<module>"
                if verb not in _VERB_KINDS:
                    findings.append(Finding(
                        "SPD007", mod.path, line, scope,
                        "unknown mxshard sanction verb %r (known: %s)"
                        % (verb, ", ".join(sorted(_VERB_KINDS))),
                        detail="bad-verb:%s" % verb))
                elif not reason:
                    findings.append(Finding(
                        "SPD007", mod.path, line, scope,
                        "mxshard %s tag has an empty reason — the "
                        "justification is the point of the tag" % verb,
                        detail="empty-reason:%s" % verb))
                elif not here:
                    findings.append(Finding(
                        "SPD007", mod.path, line, scope,
                        "stale mxshard %s tag: no collective site on this "
                        "line" % verb, detail="stale-tag:%s" % verb))
                elif all(s.kind not in _VERB_KINDS[verb] for s in here):
                    findings.append(Finding(
                        "SPD007", mod.path, line, scope,
                        "mxshard %s tag cannot sanction a %s site (it "
                        "covers: %s)"
                        % (verb, here[0].kind,
                           ", ".join(sorted(_VERB_KINDS[verb]))),
                        detail="verb-mismatch:%s@%s" % (verb,
                                                        here[0].kind)))
            elif budget:
                parsed = _parse_budget(budget.group(1))
                if parsed is None:
                    findings.append(Finding(
                        "SPD007", mod.path, line, "<module>",
                        "malformed mxshard budget %r (want "
                        "\"kind=N, ...\" with kinds from: %s)"
                        % (budget.group(1).strip(),
                           ", ".join(_KIND_NAMES)),
                        detail="bad-budget"))
                elif (mod.path, line) in budget_lines:
                    key = next(k for k, (ln, _b) in analysis.budgets.items()
                               if (analysis.graph.funcs[k].module.path,
                                   ln) == (mod.path, line))
                    if key not in region_body_keys:
                        findings.append(Finding(
                            "SPD007", mod.path, line,
                            analysis.graph.funcs[key].qual,
                            "mxshard budget attached to `%s`, which is not "
                            "a shard_map region body"
                            % analysis.graph.funcs[key].qual,
                            detail="budget-off-region"))
                else:
                    findings.append(Finding(
                        "SPD007", mod.path, line, "<module>",
                        "mxshard budget comment is not attached to a "
                        "function def (put it on the line above the def)",
                        detail="budget-unattached"))
            else:
                findings.append(Finding(
                    "SPD007", mod.path, line, "<module>",
                    "unrecognized mxshard annotation %r (vocabulary: "
                    "gather-ok/reduce-ok/reshard-ok/allclose-ok(reason), "
                    "budget(kind=N), bitwise)" % comment.strip(),
                    detail="bad-annotation"))

    # SPD004: missing eager divisibility validation ---------------------
    for region in analysis.regions:
        if not _demands_divisibility(analysis, region):
            continue
        if not _has_guard(analysis, region.owner):
            findings.append(Finding(
                "SPD004", region.owner.path, region.line,
                region.owner.qual,
                "shard_map region `%s` shards a named axis in its in_specs "
                "but neither `%s` nor its enclosing scope validates "
                "divisibility eagerly (add a ctor-time ValueError naming "
                "both extents)" % (region.qual, region.owner.qual),
                detail="no-guard:%s" % region.qual))
    for site in analysis.sites:
        if site.kind != "all_to_all":
            continue
        tiled = _kwarg(site.node, "tiled")
        if (isinstance(tiled, ast.Constant) and tiled.value is True
                and not _has_guard(analysis, site.fn)):
            findings.append(Finding(
                "SPD004", site.path, site.line, site.fn.qual,
                "tiled all_to_all requires the split extent to divide the "
                "axis, but `%s` has no eager divisibility guard (add a "
                "trace-time ValueError naming both extents)"
                % site.fn.qual,
                detail="no-guard:all_to_all@%s" % site.fn.qual))

    # budgets: coverage + breaches (SPD002) -----------------------------
    covered, breach_findings = analysis.budget_cover()
    for f in breach_findings:
        findings.append(f)
    breached_lines = {(f.path, f.line) for f in breach_findings}

    # per-site rules ----------------------------------------------------
    for site in analysis.sites:
        if id(site) in reported:            # axis error: root cause
            continue
        valid_tag = (site.verb in _VERB_KINDS
                     and site.kind in _VERB_KINDS[site.verb]
                     and (site.reason or "").strip())
        if analysis.in_loop_body(site.fn) and not (
                valid_tag and site.verb == "reshard-ok"):
            findings.append(Finding(
                "SPD006", site.path, site.line, site.fn.qual,
                "%s inside a scan/fori_loop body — a hidden collective "
                "per step; sanction with `# mxshard: reshard-ok(<reason>)` "
                "or hoist it out of the carry" % site.kind,
                detail="loop-carry:%s@%s" % (site.kind, site.axis or "?")))
            continue
        if (site.kind in _REDUCE_KINDS
                and analysis.on_bitwise_path(site)
                and not (valid_tag and site.verb == "allclose-ok")):
            findings.append(Finding(
                "SPD005", site.path, site.line, site.fn.qual,
                "%s on a bitwise-gated path: reduction order is not "
                "deterministic across shardings; document the allclose "
                "contract with `# mxshard: allclose-ok(<reason>)` or move "
                "the reduction off the bitwise path" % site.kind,
                detail="bitwise-reduce:%s@%s" % (site.kind,
                                                 site.axis or "?")))
            continue
        if valid_tag or id(site) in covered:
            continue
        if (site.path, site.line) in breached_lines:
            continue                        # already a breach finding
        if site.kind == "all_gather":
            why = ("feeds a contraction/kernel on replicated operands — "
                   "the gather tax the decode step no longer pays; a "
                   "sharded contraction + psum would serve"
                   if site.feeds_compute else
                   "moves a full operand copy to every shard")
            findings.append(Finding(
                "SPD001", site.path, site.line, site.fn.qual,
                "un-sanctioned all_gather over %r %s; sanction with "
                "`# mxshard: gather-ok(<reason>)` or budget the region"
                % (site.axis or "?", why),
                detail="gather:%s%s" % (site.axis or "?",
                                        ":compute" if site.feeds_compute
                                        else "")))
        else:
            findings.append(Finding(
                "SPD002", site.path, site.line, site.fn.qual,
                "un-sanctioned %s over %r: tag it (%s) or declare a "
                "region `# mxshard: budget(%s=N)`"
                % (site.kind, site.axis or "?",
                   "/".join(v for v, kinds in sorted(_VERB_KINDS.items())
                            if site.kind in kinds),
                   site.kind),
                detail="unsanctioned:%s@%s" % (site.kind,
                                               site.axis or "?")))
    return findings


def run(root, package_dir=None):
    """The spd pass entry point registered in PASS_REGISTRY."""
    graph = dataflow.build_graph(root, package_dir)
    return dataflow._postprocess(graph, _analyze_graph(graph,
                                                       repo_mode=True))


def analyze_source(source, path="<fixture>"):
    """Lint one python source string (fixture/unit-test entry point)."""
    graph = dataflow.build_graph_from_source(source, path)
    return dataflow._postprocess(graph, _analyze_graph(graph,
                                                       repo_mode=False))


# ---------------------------------------------------------------------------
# site inventory / COLLECTIVE_MAP / the decode-step cost model
# ---------------------------------------------------------------------------

def _site_entries(analysis):
    covered, _breaches = analysis.budget_cover()
    region_of = {}
    for region in analysis.regions:
        for key in region.closure:
            region_of.setdefault(key, region.qual)
    entries = []
    for site in analysis.sites:
        valid_tag = (site.verb in _VERB_KINDS
                     and site.kind in _VERB_KINDS[site.verb]
                     and (site.reason or "").strip())
        if valid_tag:
            sanction, reason = site.verb, site.reason
        elif id(site) in covered:
            sanction, reason = "budget", "covered by the region budget"
        else:
            sanction, reason = "UNSANCTIONED", ""
        entries.append({
            "path": site.path, "line": site.line, "scope": site.fn.qual,
            "kind": site.kind, "axis": site.axis or "?",
            "sanction": sanction, "reason": reason,
            "region": region_of.get(site.fn.key),
        })
    entries.sort(key=lambda e: (e["path"], e["line"]))
    return entries


def _budget_entries(analysis):
    sites_by_fn = {}
    for s in analysis.sites:
        sites_by_fn.setdefault(s.fn.key, []).append(s)
    out = []
    for region in analysis.regions:
        if region.body is None:
            continue
        got = analysis.budgets.get(region.body.key)
        if got is None:
            continue
        line, budget = got
        counts = {}
        for key in region.closure:
            for s in sites_by_fn.get(key, ()):
                counts[s.kind] = counts.get(s.kind, 0) + 1
        out.append({"path": region.body.path, "line": line,
                    "region": region.qual, "budget": budget,
                    "counts": counts})
    out.sort(key=lambda e: (e["path"], e["line"]))
    return out


def collective_sites(root, package_dir=None):
    """Every collective site in the scanned dirs, with its sanction."""
    graph = dataflow.build_graph(root, package_dir)
    return _site_entries(_Analysis(graph, repo_mode=True))


def source_collective_sites(source, path="<fixture>"):
    graph = dataflow.build_graph_from_source(source, path)
    return _site_entries(_Analysis(graph, repo_mode=False))


def site_counts(entries):
    """Aggregate site entries to {kind: site count} (the static half of
    the static/runtime cross-check)."""
    out = {}
    for e in entries:
        out[e["kind"]] = out.get(e["kind"], 0) + 1
    return out


def region_collective_counts(root, package_dir=None):
    """{region qual: {kind: static site count in the traced closure}}."""
    graph = dataflow.build_graph(root, package_dir)
    analysis = _Analysis(graph, repo_mode=True)
    sites_by_fn = {}
    for s in analysis.sites:
        sites_by_fn.setdefault(s.fn.key, []).append(s)
    out = {}
    for region in analysis.regions:
        counts = {}
        for key in region.closure:
            for s in sites_by_fn.get(key, ()):
                counts[s.kind] = counts.get(s.kind, 0) + 1
        out[region.qual] = counts
    return out


def collective_map_entries(root, package_dir=None):
    """(site entries, budget entries) for docs/COLLECTIVE_MAP.md."""
    graph = dataflow.build_graph(root, package_dir)
    analysis = _Analysis(graph, repo_mode=True)
    return _site_entries(analysis), _budget_entries(analysis)


def render_collective_map(entries):
    sites, budgets = entries
    lines = [
        "# COLLECTIVE_MAP — sanctioned cross-device collectives",
        "",
        "Machine-generated by `python tools/mxlint.py --collective-map`;",
        "do not edit by hand (tests/test_mxshard.py compares this file",
        "against a fresh render).  Every entry is a collective site the",
        "spd pass (docs/LINT.md) would flag, sanctioned by an inline",
        "justification tag or a region budget.  The decode-step region",
        "holds the Megatron compute-parallel contract: ZERO gather-ok",
        "sites (the PR 15 gather-at-use tax is deleted) and a",
        "budget(psum=4) covering its four allclose-sanctioned psum sites",
        "— embedding assembly (order-free, exact), the per-block",
        "row-parallel reduction, its opt-in 2-bit quantized wire, and",
        "the tied-unembed reduction (docs/PERF.md has the resulting",
        "2L+2-psum/zero-gather bill; tests/test_mxshard.py counts it).",
        "",
    ]
    cur = None
    for e in sites:
        if e["path"] != cur:
            if cur is not None:
                lines.append("")
            cur = e["path"]
            lines.append("## %s" % cur)
            lines.append("")
        region = (" — region `%s`" % e["region"]) if e["region"] else ""
        lines.append("- L%d `%s` — `%s` over `%s`%s — **%s** — %s"
                     % (e["line"], e["scope"], e["kind"], e["axis"],
                        region, e["sanction"], e["reason"] or "(none)"))
    if budgets:
        lines.append("")
        lines.append("## region budgets")
        lines.append("")
        for b in budgets:
            declared = ", ".join("%s=%d" % (k, v)
                                 for k, v in sorted(b["budget"].items()))
            used = (", ".join("%s=%d" % (k, v)
                              for k, v in sorted(b["counts"].items()))
                    or "none")
            lines.append("- %s:L%d region `%s` — budget(%s) — traced "
                         "closure uses: %s"
                         % (b["path"], b["line"], b["region"], declared,
                            used))
    lines.append("")
    lines.append("%d sanctioned collective site(s), %d region budget(s)."
                 % (len(sites), len(budgets)))
    lines.append("")
    return "\n".join(lines)


def predict_decode_step_collectives(model, slots=2, itemsize=4):
    """Per-step collective cost of a ShardedDecodeModel decode region,
    derived from the compute-parallel kernel structure, NOT from tracing:
    one exact scatter-assembly psum for the column-sharded embedding
    (``[slots, hidden]`` fp32), two Megatron block psums per layer
    (row-parallel attention-out and MLP-out, ``[slots, hidden]`` — int8
    code bytes under ``wire="2bit"``), and one weight-tied unembedding
    psum (``[slots, vocab]``, always exact fp32).  Zero all_gathers: the
    K/V pools never leave their head shard and weights contract locally
    (the ``budget(psum=4)`` region — 4 static sites, ``2L + 2`` runtime
    calls).

    This is the static half of the acceptance cross-check: the runtime
    counter delta over ONE un-jitted ``decode_fn`` call with ``slots``
    decode slots (the shard_map body re-traces per call) must match
    exactly — call counts and bytes (the counters record psum INPUT
    operand bytes, and a psum input is full-width on every member).
    """
    L = int(model.num_layers)
    S = int(slots)
    hidden = int(model.num_heads) * int(model.head_dim)
    vocab = int(model.vocab_size)
    wire_itemsize = 1 if getattr(model, "wire", None) == "2bit" \
        else itemsize
    nbytes = (S * hidden * itemsize          # embedding assembly, exact
              + 2 * L * S * hidden * wire_itemsize   # Megatron blocks
              + S * vocab * itemsize)        # tied unembed, exact
    return {
        "all_gather": {"calls": 0, "bytes": 0},
        "psum": {"calls": 2 * L + 2, "bytes": nbytes},
    }
