"""mxmem — static device-memory liveness, donation, and footprint lint.

The mem pass (``tools/mxlint.py --passes mem``) gives device memory the
treatment PR 16 gave collectives: the original MXNet design ran graph-level
memory planning as a first-class pass (arxiv 1512.01274 §5), and every
capacity claim the runtime now rests on — ZeRO's 1/N optimizer-state bytes,
the 1/K head-sharded K/V pools, ``donate='auto'`` on the compiled step,
worst-case KV reservation at admission — deserves a static model, not
scattered runtime spot-checks.  The pass walks the mxflow call graph over
``mxnet_tpu/parallel/``, ``mxnet_tpu/module/``, and
``mxnet_tpu/serving/decode/``, builds a symbolic per-buffer size model, and
enforces the MEM rule family.  Its runtime twin is the per-region byte
accountant in :mod:`mxnet_tpu.memory_accounting` — the static site counts
and byte predictions are pinned to one runtime ground truth in
tests/test_mxmem.py.

Abstract-memory model
---------------------
* **Sizes** — an allocation's size is a product of factors read from the
  shape expression (literal ints, parameter defaults, single local constant
  assignments, walking lexical ancestors) times a dtype itemsize (literal
  dtype string/attribute; float32 when unstated).  Unresolvable dimensions
  stay *symbolic*: they never contribute to a budget subtotal (the subtotal
  is a sound lower bound) but are counted and cataloged.
* **Sites** — three site kinds anchor the rules: *compile* sites
  (``jax.jit`` / ``CachedOp`` constructions, each with a donation state
  resolved to static / none / runtime), *gather* sites (``allgather`` /
  ``all_gather`` / ``broadcast`` — a full-shape output temp), and *alloc*
  sites (``zeros`` / ``ones`` / ``empty`` / ``full`` / ``*_like`` /
  ``zeros_pool`` plus the pool-growth methods ``grow`` /
  ``ensure_capacity`` / ``init_pools``).  The wrapper definitions in
  ``parallel/collectives.py`` are the instrumentation layer and are exempt.
* **Regions** — a ``shard_map`` construction opens a sharded region (the
  traced closure MEM005 polices); a ``# mxmem: budget(hbm=...)`` on any def
  opens a *budget region* whose closure (callees, sibling nested defs, and
  the bodies of shard_map regions it constructs) is charged for every alloc
  and gather site inside.
* **Liveness** — the model is conservatively reuse-free: everything a
  region allocates is live until the region ends, so a region's peak is the
  sum of its sites.  That is exactly the runtime accountant's
  ``track_region`` model, which is what makes the two sides comparable with
  ``==`` (``predict_decode_step_peak_bytes`` vs the metered peak, in
  tests/test_mxmem.py).

Rules (empty baseline; fix or tag, never suppress)
--------------------------------------------------
MEM001  state carried in and out of a jit/CachedOp region without donation
        (double-buffer hazard: input and output buffers coexist); a
        runtime-resolved donation flag counts as undonated until
        documented.  Sanction: ``# mxmem: nodonate(<reason>)``.
MEM002  use-after-donate: a handle passed at a donated argument position is
        read again on a path after the call that consumed it.
MEM003  per-region peak-HBM budget breach: the *concrete* byte subtotal of
        a budget region's closure exceeds its declared
        ``# mxmem: budget(hbm=...)`` cap (symbolic sites are cataloged but
        never breach — the subtotal is a sound lower bound).
MEM004  device allocation reachable from a hot region (``# mxflow: hot``)
        not covered by a worst-case ``reserve()`` — the no-mid-stream-OOM
        contract made mechanical.  Covered when the function, a lexical
        ancestor, or a method of its class calls ``reserve``, when its
        class IS the reserving allocator (defines ``reserve``), or by
        ``# mxmem: reserve-ok(<reason>)``.
MEM005  full-shape materialization inside a sharded region: an
        allgather/broadcast temp whose symbolic size carries no mesh-axis
        divisor.  Covered by membership in an hbm-budgeted closure (the
        budget IS the declared worst case) or
        ``# mxmem: fullshape-ok(<reason>)``.
MEM006  tag hygiene: malformed/empty-reason/kind-mismatched ``mxmem:``
        annotations, stale tags on lines without a matching site, budgets
        not attached to a def.

Every sanctioned site and budget is cataloged in docs/MEM_MAP.md
(``tools/mxlint.py --mem-map``; freshness-gated in tier-1).
"""
from __future__ import annotations

import ast
import re

from .common import Finding
from . import dataflow
from .dataflow import _own_nodes, _unparse

__all__ = ["run", "analyze_source", "memory_sites", "source_memory_sites",
           "site_counts", "mem_map_entries", "render_mem_map",
           "predict_decode_step_peak_bytes", "SCAN_PREFIXES"]

#: repo-relative path prefixes the pass scans (and --since triggers on)
SCAN_PREFIXES = ("mxnet_tpu/parallel/", "mxnet_tpu/module/",
                 "mxnet_tpu/serving/decode/", "mxnet_tpu/serving/deploy.py")
#: the wrapper/instrumentation module — definitions, not uses
_WRAPPER_MODULE = "mxnet_tpu/parallel/collectives.py"

# allocator callee names: first argument is (or names) the shape
_ALLOC_NAMES = {"zeros", "ones", "empty", "full", "zeros_like", "ones_like",
                "empty_like", "full_like", "zeros_pool"}
# pool-growth methods: device blocks/pools appear without a shape literal
_GROW_NAMES = {"grow", "ensure_capacity", "init_pools"}
# gather-materialization callee names: the output is a full-shape temp
_GATHER_NAMES = {"allgather": "all_gather", "all_gather": "all_gather",
                 "broadcast": "broadcast"}

_DTYPE_BYTES = {
    "float64": 8, "int64": 8, "uint64": 8, "complex64": 8,
    "float32": 4, "int32": 4, "uint32": 4,
    "float16": 2, "bfloat16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "bool": 1, "bool_": 1,
}

# sanction verb -> site kinds it may sanction (MEM006 vocabulary)
_VERB_SITES = {
    "nodonate": {"compile"},
    "fullshape-ok": {"gather"},
    "reserve-ok": {"alloc"},
}

_TAG_RE = re.compile(r"mxmem:\s*([a-z][a-z-]*)\s*\(([^()]*)\)")
_BUDGET_RE = re.compile(r"mxmem:\s*budget\s*\(([^()]*)\)")
_ANY_MXMEM_RE = re.compile(r"mxmem:")
_BUDGET_ITEM_RE = re.compile(
    r"^\s*hbm\s*=\s*(\d+)\s*(B|KB|MB|GB)?\s*$")
_UNIT_BYTES = {None: 1, "B": 1, "KB": 1024, "MB": 1024 ** 2,
               "GB": 1024 ** 3}


def _callee_name(node):
    """Bare name of a Call's callee (Name or Attribute), else None."""
    f = node.func if isinstance(node, ast.Call) else node
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _parse_budget(text):
    """"hbm=256MB" -> byte count; None if malformed."""
    m = _BUDGET_ITEM_RE.match(text)
    if m is None:
        return None
    return int(m.group(1)) * _UNIT_BYTES[m.group(2)]


def _format_bytes(n):
    for unit, div in (("GB", 1024 ** 3), ("MB", 1024 ** 2), ("KB", 1024)):
        if n >= div and n % div == 0:
            return "%d%s" % (n // div, unit)
    return "%dB" % n


class _Size(object):
    """A symbolic buffer size: concrete factors x symbolic factors x
    itemsize.  ``nbytes`` is an int only when fully concrete."""
    __slots__ = ("factors", "symbols", "itemsize", "dtype")

    def __init__(self, factors, symbols, itemsize, dtype):
        self.factors = tuple(factors)
        self.symbols = tuple(symbols)
        self.itemsize = itemsize
        self.dtype = dtype

    @property
    def concrete(self):
        return not self.symbols

    @property
    def nbytes(self):
        if self.symbols:
            return None
        total = self.itemsize
        for f in self.factors:
            total *= f
        return total

    def describe(self):
        dims = [str(f) for f in self.factors]
        dims += ["(%s)" % s for s in self.symbols]
        shape = "x".join(dims) if dims else "scalar"
        if self.concrete:
            return "%s %s = %dB" % (shape, self.dtype, self.nbytes)
        return "%s %s (symbolic)" % (shape, self.dtype)


class _Site(object):
    """One memory-relevant site: compile / gather / alloc."""
    __slots__ = ("fn", "node", "line", "kind", "verb", "reason", "size",
                 "donation", "carry", "flavor", "axis")

    def __init__(self, fn, node, kind):
        self.fn = fn
        self.node = node
        self.line = node.lineno
        self.kind = kind            # "compile" | "gather" | "alloc"
        self.verb = None            # sanction tag verb on the site line
        self.reason = None
        self.size = None            # _Size for alloc sites
        self.donation = None        # compile: "static" | "none" | "runtime"
        self.carry = False          # compile: state visibly threaded back
        self.flavor = None          # compile: "jit" | "CachedOp"; alloc:
                                    # the callee name; gather: the kind
        self.axis = None            # gather: best-effort mesh axis

    @property
    def path(self):
        return self.fn.path

    def span(self):
        return range(self.line, (getattr(self.node, "end_lineno", None)
                                 or self.line) + 1)


class _Region(object):
    """One shard_map region (the sharded block MEM005 polices)."""
    __slots__ = ("owner", "body", "line", "call", "closure")

    def __init__(self, owner, body, line, call):
        self.owner = owner
        self.body = body
        self.line = line
        self.call = call
        self.closure = ()

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
        self.sites = []             # [_Site] (wrapper module exempt)
        self.regions = []           # [_Region]
        self.budgets = {}           # fn key -> (line, cap bytes)
        self.extra_edges = {}       # fn key -> [callee keys] (nested sibs)
        self.hot_of = {}            # fn key -> hot-root qual (reachability)
        self._budget_closures = None
        self._collect()

    # -- collection -----------------------------------------------------
    def _scope_of(self, mod, line):
        best = "<module>"
        for fn in mod.func_order:
            n = fn.node
            if (n.lineno <= line
                    <= (getattr(n, "end_lineno", n.lineno) or n.lineno)):
                best = fn.qual
        return best

    def _collect(self):
        for mod in self.modules:
            if mod.tree is None:
                continue
            for fn in mod.func_order:
                self._collect_fn(mod, fn)
        self._resolve_edges()
        for region in self.regions:
            region.closure = self._closure(region.body)
        self._mark_hot_closure()

    def _collect_fn(self, mod, fn):
        key = fn.key
        # budget annotation: the def line, the decorator line, or any line
        # in the run of consecutive comment lines directly above (budgets
        # stack with mxshard budgets and prose in the same comment block)
        first = fn.node.lineno
        for dec in fn.node.decorator_list:
            first = min(first, dec.lineno)
        lines = [fn.node.lineno, first]
        ln = first - 1
        while ln in mod.comments:
            lines.append(ln)
            ln -= 1
        for ln in lines:
            m = _BUDGET_RE.search(mod.comments.get(ln, ""))
            if m and key not in self.budgets:
                cap = _parse_budget(m.group(1))
                if cap is not None:
                    self.budgets[key] = (ln, cap)

        exempt = self.repo_mode and mod.path == _WRAPPER_MODULE
        for node in _own_nodes(fn):
            if not isinstance(node, ast.Call):
                continue
            name = _callee_name(node)
            if name == "shard_map":
                self.regions.append(self._region_from_call(fn, node))
                continue
            if exempt:
                continue
            site = None
            if name == "jit":
                site = _Site(fn, node, "compile")
                site.flavor = "jit"
                site.donation, argnums = _jit_donation(node, self, fn)
                site.carry = _jit_carry(fn, node)
            elif name == "CachedOp":
                site = _Site(fn, node, "compile")
                site.flavor = "CachedOp"
                site.donation, _ = _cachedop_donation(node, self, fn)
                site.carry = True   # params/aux are threaded in and out
            elif name in _GATHER_NAMES:
                site = _Site(fn, node, "gather")
                site.flavor = _GATHER_NAMES[name]
                site.axis = _axis_of(node, self, fn)
            elif name in _ALLOC_NAMES and (node.args or node.keywords):
                site = _Site(fn, node, "alloc")
                site.flavor = name
                site.size = _alloc_size(node, self, fn)
            elif name in _GROW_NAMES and isinstance(node.func,
                                                    ast.Attribute):
                site = _Site(fn, node, "alloc")
                site.flavor = name
                site.size = _Size((), ("pool:%s" % name,), 1, "?")
            if site is None:
                continue
            for ln in site.span():
                tag = _TAG_RE.search(mod.comments.get(ln, ""))
                if tag and tag.group(1) != "budget":
                    site.verb = tag.group(1)
                    site.reason = tag.group(2).strip()
                    break
            self.sites.append(site)
        # decorator compile sites: @jax.jit / @functools.partial(jax.jit,..)
        for dec in fn.node.decorator_list:
            call = None
            if _callee_name(dec) == "jit" and not isinstance(dec, ast.Call):
                site = _Site(fn, dec, "compile")
                site.flavor = "jit"
                site.donation = "none"
                self.sites.append(site)
                continue
            if isinstance(dec, ast.Call):
                if _callee_name(dec) == "jit":
                    call = dec
                elif (_callee_name(dec) == "partial" and dec.args
                      and _callee_name(dec.args[0]) == "jit"):
                    call = dec
            if call is not None:
                site = _Site(fn, call, "compile")
                site.flavor = "jit"
                site.donation, _ = _jit_donation(call, self, fn)
                for ln in site.span():
                    tag = _TAG_RE.search(mod.comments.get(ln, ""))
                    if tag and tag.group(1) != "budget":
                        site.verb = tag.group(1)
                        site.reason = tag.group(2).strip()
                        break
                self.sites.append(site)
            # decorator form: @functools.partial(shard_map, ...)
            if (isinstance(dec, ast.Call)
                    and _callee_name(dec) == "partial" and dec.args
                    and _callee_name(dec.args[0]) == "shard_map"):
                self.regions.append(_Region(fn, fn, fn.node.lineno, dec))

    def _region_from_call(self, fn, call):
        body_expr = call.args[0] if call.args else None
        if (isinstance(body_expr, ast.Call)
                and _callee_name(body_expr) == "partial"
                and body_expr.args):
            body_expr = body_expr.args[0]
        body = None
        if isinstance(body_expr, ast.Name):
            body = self._resolve_func_name(fn, body_expr.id)
        return _Region(fn, body, call.lineno, call)

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

    def _resolve_edges(self):
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

    def _callees(self, fn, bridge_regions):
        callees = [k for k, _ in fn.calls]
        callees += self.extra_edges.get(fn.key, [])
        if bridge_regions:
            # a shard_map constructed here traces its body: the budget
            # closure must charge the region's allocations too
            callees += [r.body.key for r in self.regions
                        if r.owner.key == fn.key and r.body is not None]
        return callees

    def _closure(self, body, bridge_regions=False):
        if body is None:
            return ()
        seen = {body.key}
        queue = [body]
        while queue:
            fn = queue.pop()
            for key in self._callees(fn, bridge_regions):
                callee = self.graph.funcs.get(key)
                if (callee is None or callee.key in seen
                        or (self.repo_mode
                            and not callee.path.startswith(SCAN_PREFIXES))):
                    continue
                seen.add(callee.key)
                queue.append(callee)
        return tuple(seen)

    def _mark_hot_closure(self):
        """hot_of: fn key -> the hot root it is reachable from.  Roots are
        ``# mxflow: hot`` functions (the dataflow builder sets fn.hot);
        traversal crosses module boundaries — a hot loop in serving/ can
        reach allocators in the scanned dirs — but sites are only
        collected (and so only flagged) inside SCAN_PREFIXES."""
        roots = [f for f in self.graph.funcs.values()
                 if f.hot and not f.cold]
        for root in roots:
            seen = {root.key}
            queue = [root]
            self.hot_of.setdefault(root.key, root.qual)
            while queue:
                fn = queue.pop()
                for key in self._callees(fn, bridge_regions=True):
                    callee = self.graph.funcs.get(key)
                    if callee is None or callee.key in seen:
                        continue
                    seen.add(callee.key)
                    self.hot_of.setdefault(callee.key, root.qual)
                    queue.append(callee)

    # -- helpers --------------------------------------------------------
    def lexical_ancestors(self, fn):
        """fn plus every enclosing _Func (by qual prefix)."""
        out = [fn]
        for pq in _qual_prefixes(fn.qual):
            got = self.by_qual.get((fn.module.path, pq))
            if got is not None:
                out.append(got)
        return out

    def budget_closures(self):
        """{budgeted fn key: set of closure fn keys} (region-bridged)."""
        if self._budget_closures is None:
            self._budget_closures = {
                key: set(self._closure(self.graph.funcs[key],
                                       bridge_regions=True))
                for key in self.budgets}
        return self._budget_closures

    def budget_of_site(self, site):
        """The budgeted fn key whose closure covers ``site``, or None."""
        for key, closure in sorted(self.budget_closures().items()):
            if site.fn.key in closure:
                return key
        return None

    def reserve_covered(self, fn):
        """MEM004 coverage: the function, a lexical ancestor, or a method
        of its class calls reserve(); or the class IS the reserving
        allocator (defines reserve — the pool implements admission)."""
        scopes = list(self.lexical_ancestors(fn))
        if fn.cls is not None:
            if "reserve" in fn.cls.methods:
                return True
            scopes.extend(fn.cls.methods.values())
        seen = set()
        for scope in scopes:
            if scope.key in seen:
                continue
            seen.add(scope.key)
            for node in _own_nodes(scope):
                if (isinstance(node, ast.Call)
                        and _callee_name(node) == "reserve"):
                    return True
        return False


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


def _local_assignment(name, analysis, fn):
    """The value of a single-target ``name = <expr>`` assignment in fn or a
    lexical ancestor, or None."""
    for scope in analysis.lexical_ancestors(fn):
        for node in _own_nodes(scope):
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and node.targets[0].id == name):
                return node.value
    return None


def _const_of(name, analysis, fn, types):
    """A constant of ``types`` bound to ``name`` via a parameter default or
    a single local assignment in the lexical scope chain, else None."""
    for scope in analysis.lexical_ancestors(fn):
        for p, d in _param_defaults(scope.node):
            if (p == name and isinstance(d, ast.Constant)
                    and isinstance(d.value, types)):
                return d.value
    expr = _local_assignment(name, analysis, fn)
    if (isinstance(expr, ast.Constant)
            and isinstance(expr.value, types)):
        return expr.value
    return None


def _axis_of(call, analysis, fn):
    """Best-effort gather axis: 2nd positional / axis_name kwarg, resolved
    through parameter defaults and single constant assignments."""
    expr = (call.args[1] if len(call.args) >= 2
            else _kwarg(call, "axis_name"))
    if expr is None:
        name = _callee_name(call)
        if name in ("allgather", "all_gather"):
            return "dp"  # the wrappers' default axis
        return None
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        return expr.value
    if isinstance(expr, ast.Name):
        got = _const_of(expr.id, analysis, fn, str)
        if got is not None:
            return got
    return None


# ---------------------------------------------------------------------------
# the symbolic size model
# ---------------------------------------------------------------------------

def _dim_factor(expr, analysis, fn):
    """-> (int factor, None) or (None, symbol string)."""
    if (isinstance(expr, ast.Constant) and isinstance(expr.value, int)
            and not isinstance(expr.value, bool)):
        return expr.value, None
    if isinstance(expr, ast.Name):
        got = _const_of(expr.id, analysis, fn, int)
        if got is not None and not isinstance(got, bool):
            return got, None
    return None, _unparse(expr)[:48]


def _dtype_itemsize(expr, analysis, fn):
    """-> (itemsize, dtype label); float32/4 when unresolvable."""
    if expr is None:
        return 4, "f32"
    name = None
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str):
        name = expr.value
    elif isinstance(expr, ast.Attribute):
        name = expr.attr
    elif isinstance(expr, ast.Name):
        got = _const_of(expr.id, analysis, fn, str)
        name = got if got is not None else expr.id
    if name in _DTYPE_BYTES:
        return _DTYPE_BYTES[name], name
    return 4, "f32"


def _alloc_size(call, analysis, fn):
    """The symbolic _Size of an allocator call."""
    name = _callee_name(call)
    if name.endswith("_like"):
        src = _unparse(call.args[0])[:48] if call.args else "?"
        return _Size((), ("like:%s" % src,), 1, "?")
    if name == "zeros_pool":
        src = _unparse(call.args[0])[:48] if call.args else "pool"
        return _Size((), ("pool:%s" % src,), 1, "?")
    shape = call.args[0] if call.args else _kwarg(call, "shape")
    dtype_expr = _kwarg(call, "dtype")
    if (dtype_expr is None and name in ("zeros", "ones", "empty")
            and len(call.args) >= 2):
        dtype_expr = call.args[1]
    itemsize, dtype = _dtype_itemsize(dtype_expr, analysis, fn)
    factors, symbols = [], []
    if isinstance(shape, ast.Name):
        resolved = _local_assignment(shape.id, analysis, fn)
        if isinstance(resolved, (ast.Tuple, ast.List)):
            shape = resolved
    if isinstance(shape, (ast.Tuple, ast.List)):
        for e in shape.elts:
            f, s = _dim_factor(e, analysis, fn)
            if f is not None:
                factors.append(f)
            else:
                symbols.append(s)
    elif shape is None:
        symbols.append("?")
    else:
        f, s = _dim_factor(shape, analysis, fn)
        if f is not None:
            factors.append(f)
        else:
            symbols.append(s)
    return _Size(factors, symbols, itemsize, dtype)


# ---------------------------------------------------------------------------
# donation resolution (MEM001/MEM002)
# ---------------------------------------------------------------------------

def _jit_literal(expr):
    """("static", positions) / ("none", ()) for a literal donate_argnums,
    else None."""
    if isinstance(expr, (ast.Tuple, ast.List)):
        positions = []
        for e in expr.elts:
            if (isinstance(e, ast.Constant) and isinstance(e.value, int)
                    and not isinstance(e.value, bool)):
                positions.append(e.value)
            else:
                return None
        return (("static", tuple(positions)) if positions
                else ("none", ()))
    if (isinstance(expr, ast.Constant) and isinstance(expr.value, int)
            and not isinstance(expr.value, bool)):
        return ("static", (expr.value,))
    return None


def _flags_literal(expr):
    """CachedOp flags: ("static", ()) for a literal donate_params=True
    dict, ("none", ()) for any other literal dict / None, else None."""
    if isinstance(expr, ast.Dict):
        for k, v in zip(expr.keys, expr.values):
            if (isinstance(k, ast.Constant) and k.value == "donate_params"
                    and isinstance(v, ast.Constant) and v.value is True):
                return ("static", ())
        return ("none", ())
    if isinstance(expr, ast.Constant) and expr.value is None:
        return ("none", ())
    return None


def _resolve_donation(expr, analysis, fn, literal):
    """Donation state of a donate_argnums / flags expression:
    "static" (provably donated), "none" (provably not), or "runtime"
    (resolved at dispatch — undonated until documented)."""
    if expr is None:
        return ("none", ())
    got = literal(expr)
    if got is not None:
        return got
    if isinstance(expr, ast.IfExp):
        cond = None
        if isinstance(expr.test, ast.Constant) and isinstance(
                expr.test.value, bool):
            cond = expr.test.value
        elif isinstance(expr.test, ast.Name):
            cond = _const_of(expr.test.id, analysis, fn, bool)
        if cond is None:
            return ("runtime", ())
        branch = expr.body if cond else expr.orelse
        got = literal(branch)
        return got if got is not None else ("runtime", ())
    return ("runtime", ())


def _jit_donation(call, analysis, fn):
    return _resolve_donation(_kwarg(call, "donate_argnums"), analysis, fn,
                             _jit_literal)


def _cachedop_donation(call, analysis, fn):
    expr = _kwarg(call, "flags")
    if isinstance(expr, ast.Name):
        resolved = _local_assignment(expr.id, analysis, fn)
        if resolved is not None:
            expr = resolved
    return _resolve_donation(expr, analysis, fn, _flags_literal)


def _jit_carry(fn, call):
    """True when the jitted callable is bound to a local name and some
    call of that name visibly threads state back into itself
    (``state = step(state)``) — the double-buffer carry MEM001 polices."""
    bound = None
    for node in _own_nodes(fn):
        if (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and any(sub is call for sub in ast.walk(node.value))):
            bound = node.targets[0].id
    if bound is None:
        return False
    for node in _own_nodes(fn):
        if not (isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call)):
            continue
        callee = node.value.func
        if not (isinstance(callee, ast.Name) and callee.id == bound):
            continue
        targets = set()
        for t in node.targets:
            for sub in ast.walk(t):
                if isinstance(sub, ast.Name):
                    targets.add(sub.id)
        arg_names = {sub.id for a in node.value.args
                     for sub in ast.walk(a) if isinstance(sub, ast.Name)}
        if targets & arg_names:
            return True
    return False


def _donated_consumptions(analysis, fn):
    """[(consumed name, consuming-call end line)] for calls through
    locally-bound, provably-donating jit/CachedOp handles."""
    donating = {}   # local name -> donated positions tuple, or None (all)
    for node in _own_nodes(fn):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)):
            continue
        callee = _callee_name(node.value)
        if callee == "jit":
            state, positions = _jit_donation(node.value, analysis, fn)
            if state == "static":
                donating[node.targets[0].id] = positions
        elif callee == "CachedOp":
            state, _ = _cachedop_donation(node.value, analysis, fn)
            if state == "static":
                donating[node.targets[0].id] = None
    out = []
    for node in _own_nodes(fn):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in donating):
            continue
        positions = donating[node.func.id]
        end = getattr(node, "end_lineno", None) or node.lineno
        if positions is None:
            picked = list(enumerate(node.args))
        else:
            picked = [(i, node.args[i]) for i in positions
                      if i < len(node.args)]
        for _i, arg in picked:
            if isinstance(arg, ast.Name):
                out.append((arg.id, end))
    return out


def _use_after_donate(analysis, fn):
    """MEM002 read sites: [(name, read line)] — a donated handle read
    after the consuming call with no intervening rebind."""
    consumptions = _donated_consumptions(analysis, fn)
    if not consumptions:
        return []
    rebinds = {}    # name -> sorted rebind lines
    reads = {}      # name -> sorted read lines
    for node in _own_nodes(fn):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target])
            for t in targets:
                for sub in ast.walk(t):
                    if isinstance(sub, ast.Name):
                        rebinds.setdefault(sub.id, []).append(sub.lineno)
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            reads.setdefault(node.id, []).append(node.lineno)
    out = []
    for name, consumed_at in consumptions:
        rebind = min((ln for ln in rebinds.get(name, ())
                      if ln > consumed_at), default=None)
        for ln in sorted(set(reads.get(name, ()))):
            if ln <= consumed_at:
                continue
            if rebind is not None and ln >= rebind:
                break
            out.append((name, ln))
    return sorted(set(out))


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def _valid_tag(site):
    return (site.verb in _VERB_SITES
            and site.kind in _VERB_SITES[site.verb]
            and (site.reason or "").strip())


def _analyze_graph(graph, repo_mode=True):
    analysis = _Analysis(graph, repo_mode=repo_mode)
    findings = []

    region_member = set()
    for region in analysis.regions:
        region_member.update(region.closure)
    region_of = {}
    for region in analysis.regions:
        for key in region.closure:
            region_of.setdefault(key, region.qual)
    budget_closures = analysis.budget_closures()

    # MEM001: undonated / runtime-donated carries ------------------------
    for site in analysis.sites:
        if site.kind != "compile":
            continue
        if _valid_tag(site) and site.verb == "nodonate":
            continue
        if site.donation == "runtime":
            findings.append(Finding(
                "MEM001", site.path, site.line, site.fn.qual,
                "%s region's donation is resolved at runtime (%s) — the "
                "carried state double-buffers whenever the branch lands "
                "on no-donate; document the backend contract with "
                "`# mxmem: nodonate(<reason>)` or make the donation "
                "static" % (site.flavor,
                            _unparse(site.node)[:60]),
                detail="runtime-donation:%s@%s" % (site.flavor,
                                                   site.fn.qual)))
        elif site.donation == "none" and site.carry:
            findings.append(Finding(
                "MEM001", site.path, site.line, site.fn.qual,
                "%s region threads state in and out without donation: "
                "input and output buffers coexist every step (double "
                "the state bytes); donate the carry "
                "(donate_argnums/donate_params) or sanction with "
                "`# mxmem: nodonate(<reason>)`" % site.flavor,
                detail="undonated-carry:%s@%s" % (site.flavor,
                                                  site.fn.qual)))

    # MEM002: use-after-donate ------------------------------------------
    seen_fns = set()
    for site in analysis.sites:
        fn = site.fn
        if site.kind != "compile" or fn.key in seen_fns:
            continue
        seen_fns.add(fn.key)
        for name, line in _use_after_donate(analysis, fn):
            findings.append(Finding(
                "MEM002", fn.path, line, fn.qual,
                "`%s` is read after the call that donated it — the "
                "buffer was surrendered to XLA and may already be "
                "aliased by the output; re-bind the result or drop the "
                "read" % name,
                detail="use-after-donate:%s@%s" % (name, fn.qual)))

    # MEM003: budget breaches -------------------------------------------
    sites_by_fn = {}
    for s in analysis.sites:
        sites_by_fn.setdefault(s.fn.key, []).append(s)
    for key, (line, cap) in sorted(analysis.budgets.items()):
        owner = analysis.graph.funcs[key]
        concrete = 0
        symbolic = 0
        for fkey in budget_closures[key]:
            for s in sites_by_fn.get(fkey, ()):
                if s.kind == "alloc":
                    if s.size is not None and s.size.concrete:
                        concrete += s.size.nbytes
                    else:
                        symbolic += 1
                elif s.kind == "gather":
                    symbolic += 1
        if concrete > cap:
            findings.append(Finding(
                "MEM003", owner.path, line, owner.qual,
                "budget region `%s` allocates %d concrete byte(s) "
                "(+%d symbolic site(s)), over its declared "
                "budget(hbm=%s) — shrink the region or raise the "
                "declared worst case" % (owner.qual, concrete, symbolic,
                                         _format_bytes(cap)),
                detail="budget-breach:%s" % owner.qual))

    # MEM004: hot allocation without a worst-case reserve ---------------
    for site in analysis.sites:
        if site.kind != "alloc":
            continue
        root = analysis.hot_of.get(site.fn.key)
        if root is None:
            continue
        if _valid_tag(site) and site.verb == "reserve-ok":
            continue
        if analysis.reserve_covered(site.fn):
            continue
        findings.append(Finding(
            "MEM004", site.path, site.line, site.fn.qual,
            "device allocation (%s: %s) reachable from hot region "
            "`%s` with no worst-case reserve() on the admission path — "
            "a mid-stream OOM candidate; reserve up front or sanction "
            "with `# mxmem: reserve-ok(<reason>)`"
            % (site.flavor, site.size.describe() if site.size else "?",
               root),
            detail="hot-alloc:%s@%s" % (site.flavor, site.fn.qual)))

    # MEM005: full-shape materialization in a sharded region ------------
    for site in analysis.sites:
        if site.kind != "gather" or site.fn.key not in region_member:
            continue
        if _valid_tag(site) and site.verb == "fullshape-ok":
            continue
        if analysis.budget_of_site(site) is not None:
            continue
        findings.append(Finding(
            "MEM005", site.path, site.line, site.fn.qual,
            "%s over %r inside sharded region `%s` materializes the "
            "full shape on every shard — a temp with no mesh-axis "
            "divisor; declare the region's worst case with "
            "`# mxmem: budget(hbm=...)` or sanction with "
            "`# mxmem: fullshape-ok(<reason>)`"
            % (site.flavor, site.axis or "?",
               region_of.get(site.fn.key, "?")),
            detail="fullshape:%s@%s" % (site.flavor, site.fn.qual)))

    # MEM006: tag hygiene -----------------------------------------------
    budget_lines = {(analysis.graph.funcs[key].module.path, ln)
                    for key, (ln, _cap) in analysis.budgets.items()}
    sites_by_line = {}
    for s in analysis.sites:
        for ln in s.span():
            sites_by_line.setdefault((s.path, ln), []).append(s)
    for mod in analysis.modules:
        for line, comment in sorted(mod.comments.items()):
            if not _ANY_MXMEM_RE.search(comment):
                continue
            budget = _BUDGET_RE.search(comment)
            tag = _TAG_RE.search(comment)
            if budget is not None:
                if _parse_budget(budget.group(1)) is None:
                    findings.append(Finding(
                        "MEM006", mod.path, line, "<module>",
                        "malformed mxmem budget %r (want "
                        "\"hbm=N[B|KB|MB|GB]\")" % budget.group(1).strip(),
                        detail="bad-budget"))
                elif (mod.path, line) not in budget_lines:
                    findings.append(Finding(
                        "MEM006", mod.path, line, "<module>",
                        "mxmem budget comment is not attached to a "
                        "function def (put it in the comment block "
                        "directly above the def)",
                        detail="budget-unattached"))
            elif tag is not None:
                verb, reason = tag.group(1), tag.group(2).strip()
                here = sites_by_line.get((mod.path, line), ())
                scope = (here[0].fn.qual if here
                         else analysis._scope_of(mod, line))
                if verb not in _VERB_SITES:
                    findings.append(Finding(
                        "MEM006", mod.path, line, scope,
                        "unknown mxmem sanction verb %r (known: %s)"
                        % (verb, ", ".join(sorted(_VERB_SITES))),
                        detail="bad-verb:%s" % verb))
                elif not reason:
                    findings.append(Finding(
                        "MEM006", mod.path, line, scope,
                        "mxmem %s tag has an empty reason — the "
                        "justification is the point of the tag" % verb,
                        detail="empty-reason:%s" % verb))
                elif not any(s.kind in _VERB_SITES[verb] for s in here):
                    findings.append(Finding(
                        "MEM006", mod.path, line, scope,
                        "stale mxmem %s tag: no %s site on this line"
                        % (verb, "/".join(sorted(_VERB_SITES[verb]))),
                        detail="stale-tag:%s" % verb))
            else:
                findings.append(Finding(
                    "MEM006", mod.path, line, "<module>",
                    "unrecognized mxmem annotation %r (vocabulary: "
                    "nodonate/fullshape-ok/reserve-ok(reason), "
                    "budget(hbm=N))" % comment.strip(),
                    detail="bad-annotation"))
    return findings


def run(root, package_dir=None):
    """The mem pass entry point registered in PASS_REGISTRY."""
    graph = dataflow.build_graph(root, package_dir)
    return dataflow._postprocess(graph, _analyze_graph(graph,
                                                       repo_mode=True))


def analyze_source(source, path="<fixture>"):
    """Lint one python source string (fixture/unit-test entry point)."""
    graph = dataflow.build_graph_from_source(source, path)
    return dataflow._postprocess(graph, _analyze_graph(graph,
                                                       repo_mode=False))


# ---------------------------------------------------------------------------
# site inventory / MEM_MAP / the decode-step footprint model
# ---------------------------------------------------------------------------

def _site_entries(analysis):
    region_of = {}
    for region in analysis.regions:
        for key in region.closure:
            region_of.setdefault(key, region.qual)
    entries = []
    for site in analysis.sites:
        tagged = _valid_tag(site)
        if site.kind == "compile":
            detail = "%s donation=%s%s" % (site.flavor, site.donation,
                                           " carry" if site.carry else "")
            if site.donation == "static":
                sanction, reason = "donated", "statically donated carry"
            elif tagged and site.verb == "nodonate":
                sanction, reason = site.verb, site.reason
            elif site.donation == "none" and not site.carry:
                sanction, reason = "clean", "no visible carry"
            else:
                sanction, reason = "UNSANCTIONED", ""
        elif site.kind == "gather":
            detail = "%s over %s" % (site.flavor, site.axis or "?")
            budget_key = analysis.budget_of_site(site)
            if tagged and site.verb == "fullshape-ok":
                sanction, reason = site.verb, site.reason
            elif site.fn.key not in region_of:
                sanction, reason = "clean", "outside any sharded region"
            elif budget_key is not None:
                sanction = "budget"
                reason = ("covered by budget region `%s`"
                          % analysis.graph.funcs[budget_key].qual)
            else:
                sanction, reason = "UNSANCTIONED", ""
        else:
            detail = "%s: %s" % (site.flavor,
                                 site.size.describe() if site.size
                                 else "?")
            hot_root = analysis.hot_of.get(site.fn.key)
            if tagged and site.verb == "reserve-ok":
                sanction, reason = site.verb, site.reason
            elif hot_root is None:
                sanction, reason = "cold", "not reachable from a hot region"
            elif analysis.reserve_covered(site.fn):
                sanction = "reserve"
                reason = ("worst-case reserve() on the admission path "
                          "(hot via `%s`)" % hot_root)
            else:
                sanction, reason = "UNSANCTIONED", ""
        entries.append({
            "path": site.path, "line": site.line, "scope": site.fn.qual,
            "kind": site.kind, "detail": detail,
            "bytes": site.size.nbytes if site.size is not None else None,
            "hot": site.fn.key in analysis.hot_of,
            "region": region_of.get(site.fn.key),
            "sanction": sanction, "reason": reason,
        })
    entries.sort(key=lambda e: (e["path"], e["line"]))
    return entries


def _budget_entries(analysis):
    sites_by_fn = {}
    for s in analysis.sites:
        sites_by_fn.setdefault(s.fn.key, []).append(s)
    closures = analysis.budget_closures()
    out = []
    for key, (line, cap) in analysis.budgets.items():
        owner = analysis.graph.funcs[key]
        concrete = symbolic = gathers = 0
        for fkey in closures[key]:
            for s in sites_by_fn.get(fkey, ()):
                if s.kind == "alloc":
                    if s.size is not None and s.size.concrete:
                        concrete += s.size.nbytes
                    else:
                        symbolic += 1
                elif s.kind == "gather":
                    gathers += 1
        out.append({"path": owner.path, "line": line, "region": owner.qual,
                    "cap_bytes": cap, "concrete_bytes": concrete,
                    "symbolic_sites": symbolic, "gather_sites": gathers})
    out.sort(key=lambda e: (e["path"], e["line"]))
    return out


def memory_sites(root, package_dir=None):
    """Every memory site in the scanned dirs, with its sanction."""
    graph = dataflow.build_graph(root, package_dir)
    return _site_entries(_Analysis(graph, repo_mode=True))


def source_memory_sites(source, path="<fixture>"):
    graph = dataflow.build_graph_from_source(source, path)
    return _site_entries(_Analysis(graph, repo_mode=False))


def site_counts(entries):
    """Aggregate site entries to {kind: site count} (the static half of
    the static/runtime cross-check)."""
    out = {}
    for e in entries:
        out[e["kind"]] = out.get(e["kind"], 0) + 1
    return out


def mem_map_entries(root, package_dir=None):
    """(site entries, budget entries) for docs/MEM_MAP.md."""
    graph = dataflow.build_graph(root, package_dir)
    analysis = _Analysis(graph, repo_mode=True)
    return _site_entries(analysis), _budget_entries(analysis)


def render_mem_map(entries):
    sites, budgets = entries
    lines = [
        "# MEM_MAP — the lint-enforced device-memory footprint catalog",
        "",
        "Machine-generated by `python tools/mxlint.py --mem-map`; do not",
        "edit by hand (tests/test_mxmem.py compares this file against a",
        "fresh render).  Every entry is a memory site the mem pass",
        "(docs/LINT.md) tracks: compile sites with their donation state,",
        "gather sites with their full-shape temps, allocation sites with",
        "their symbolic sizes.  `nodonate` entries are documented",
        "double-buffer carries; `budget` regions declare the worst-case",
        "peak their closure is held to; `reserve` allocations ride the",
        "admission-time worst-case reservation (the no-mid-stream-OOM",
        "contract).  The runtime twin is mxnet_tpu/memory_accounting.py",
        "(tests/test_mxmem.py pins static == runtime peak bytes).",
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
        flags = []
        if e["hot"]:
            flags.append("hot")
        if e["region"]:
            flags.append("region `%s`" % e["region"])
        suffix = (" — %s" % ", ".join(flags)) if flags else ""
        lines.append("- L%d `%s` — %s%s — **%s** — %s"
                     % (e["line"], e["scope"], e["detail"], suffix,
                        e["sanction"], e["reason"] or "(none)"))
    if budgets:
        lines.append("")
        lines.append("## hbm budgets")
        lines.append("")
        for b in budgets:
            lines.append("- %s:L%d region `%s` — budget(hbm=%s) — closure "
                         "holds %d concrete byte(s), %d symbolic alloc "
                         "site(s), %d gather site(s)"
                         % (b["path"], b["line"], b["region"],
                            _format_bytes(b["cap_bytes"]),
                            b["concrete_bytes"], b["symbolic_sites"],
                            b["gather_sites"]))
    lines.append("")
    lines.append("%d memory site(s), %d hbm budget(s)."
                 % (len(sites), len(budgets)))
    lines.append("")
    return "\n".join(lines)


def predict_decode_step_peak_bytes(model, slots=2, itemsize=4):
    """Worst-case per-step HBM temp peak of the sharded decode region,
    derived from the compute-parallel kernel structure alone — no
    tracing: the only collective temps a decode step materializes are its
    psum OUTPUTS (a psum output is shaped like its input), one per
    runtime psum call — the ``[slots, hidden]`` embedding assembly, two
    ``[slots, hidden]`` Megatron block reductions per layer (int8 code
    bytes under ``wire="2bit"``), and the ``[slots, vocab]`` tied-unembed
    logits.  Under the accountant's reuse-free region model every temp is
    live until the region ends, so the peak is their sum.  The PR 15
    gather-at-use wrapper peaked at the FULL gathered weights + both full
    K/V pools; the compute-parallel kernels delete those temps entirely.

    This is the static half of the acceptance cross-check: the runtime
    ``track_region`` peak over ONE un-jitted ``decode_fn`` call with
    ``slots`` decode slots (the shard_map body re-traces per call, and
    every collective wrapper records its output temp) must equal it
    EXACTLY."""
    L = int(model.num_layers)
    S = int(slots)
    hidden = int(model.num_heads) * int(model.head_dim)
    vocab = int(model.vocab_size)
    wire_itemsize = 1 if getattr(model, "wire", None) == "2bit" \
        else itemsize
    return (S * hidden * itemsize
            + 2 * L * S * hidden * wire_itemsize
            + S * vocab * itemsize)
