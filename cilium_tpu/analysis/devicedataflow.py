"""device-dataflow (ctlint v4): host↔device hazards on the serving
hot path.

Every engine/ring/multichip number so far comes from CPU hosts, where
a host↔device round-trip is free — so the hazards that will wreck
real-v5e latency are invisible to every tier-1 test: an implicit host
sync (``float()`` of a device scalar, ``np.asarray`` per loop
iteration, a Python branch on a device value) serializes the async
dispatch pipeline; a per-iteration ``device_put`` puts a PCIe/ICI
transfer on the critical path; an undonated in-place buffer update
doubles HBM traffic. This family proves the hot path free of them the
same way v3 proved it free of data races.

Mechanically it extends the v2 dataflow core (``dataflow.AbsVal``)
with a **device-residency dimension**: values produced by jitted
dispatches, ``jax.device_put``, ``jnp.*``/``lax.*`` constructors, and
the known device tables (memo table, session row table, ServedPack
lanes) carry ``device=True`` plus a ``dev_chain`` def-site provenance
chain, propagated through ops, subscripts, calls, and containers. Hot
roots are discovered over the callgraph — any in-scope function that
issues a device dispatch (a jitted entry call, a ``self._step``-style
memoized step, a ``_gather_step()(…)`` factory step, or a serve-plane
method like ``serve_ids``/``verdict_chunk``) — plus the named serving
spine (ring pack, session serve, capture chunk, serve-loop cycle,
dnsproxy batch, megakernel step). Four rules consume the resulting
event stream; findings carry the residency chain in schema-v4
CTLINT.json.

False-negative classes are deliberate (miss, don't invent) — see
docs/ANALYSIS.md §v4 for the catalog: residency is lost at
unresolvable method boundaries (``self.ring.pack(...)``), through
dict containers, and through first-class callables (the phase probes'
``_timed(fn)`` indirection); a single terminal batched readback at
the API edge is the *contract*, not a hazard, and is exempt by
construction.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Set, Tuple

from cilium_tpu.analysis import dataflow
from cilium_tpu.analysis.callgraph import (ModuleInfo, Project, dotted,
                                           project_for)
from cilium_tpu.analysis.core import Finding, ProjectIndex, checker
from cilium_tpu.analysis.dataflow import AbsVal, EventSink, Interp
from cilium_tpu.analysis.purity import find_entries

RULE_SYNC = "implicit-sync"
RULE_H2D = "hot-loop-h2d"
RULE_DONATE = "missing-donation"
RULE_ORDER = "readback-ordering"

#: the serving hot path lives here; everything else is staging/CLI
#: surface where a sync is fine. dst.py is the simulation harness
#: (its reference lane reads back eagerly BY DESIGN, to compare), and
#: parallel/ holds the mesh lanes — both out of scope.
_SCOPE_PREFIXES = ("cilium_tpu/engine/", "cilium_tpu/runtime/")
_SCOPE_FILES = ("cilium_tpu/fqdn/dnsproxy.py",)
_SCOPE_EXCLUDE = ("cilium_tpu/runtime/dst.py",)

#: attribute-call names that ARE a device dispatch in this codebase:
#: the serve-plane methods and the ``self._step`` jit-memo idiom. A
#: dispatch is a residency boundary — the walk does not enter it (the
#: callee is analyzed as its own root); its result is device.
DISPATCH_ATTRS = frozenset({
    "serve_ids", "verdict_chunk", "verdict_idx", "verdict_rows",
    "verdict_batch_arrays", "gather", "_step", "_full",
})

#: self-attributes that are device-resident tables, scoped by file
#: suffix so a generic name ("table") marks only the module whose
#: table actually lives on device
DEVICE_ATTRS: Dict[str, Tuple[str, ...]] = {
    "rows_dev": ("engine/session.py",),
    "table": ("engine/memo.py",),
    "verdict": ("engine/attribution.py",),
    "l7_match": ("engine/attribution.py",),
    "match_spec": ("engine/attribution.py",),
}

#: the named serving spine — always roots, even if a refactor hides
#: their dispatch behind an unresolvable boundary
NAMED_ROOTS: Tuple[Tuple[str, Optional[str], str], ...] = (
    ("cilium_tpu/engine/ring.py", "VerdictRing", "pack"),
    ("cilium_tpu/engine/session.py", "IncrementalSession", "serve_ids"),
    ("cilium_tpu/engine/verdict.py", "CaptureReplay", "verdict_chunk"),
    ("cilium_tpu/runtime/serveloop.py", "ServeLoop", "step"),
    ("cilium_tpu/fqdn/dnsproxy.py", "DNSProxy", "check_batch"),
    ("cilium_tpu/engine/megakernel.py", None, "fused_verdict_step"),
    ("cilium_tpu/engine/attribution.py", "ServedPack", "host"),
)

#: sync vocabulary (kept in parity with purity._HOST_SYNC): scalar
#: coercions block the host wherever they appear; bulk readbacks are
#: the legitimate API-edge pattern and only flag inside a loop (or
#: when fragmented — several straight-line readbacks that should be
#: one batched device_get)
_SCALAR_SYNCS = frozenset({"int()", "float()", "bool()", ".item()",
                           ".tolist()", "truthiness"})
_BULK_SYNCS = frozenset({"np.asarray", "np.array", "device_get",
                         "block_until_ready"})


def _in_scope(path: str) -> bool:
    if path in _SCOPE_EXCLUDE:
        return False
    return path.startswith(_SCOPE_PREFIXES) or path in _SCOPE_FILES


# -- dispatch recognition ---------------------------------------------------


def _dispatch_label(node: ast.Call) -> Optional[str]:
    """Syntactic device-dispatch forms: ``obj.serve_ids(…)`` /
    ``self._step(…)`` attribute calls, and the jit-factory idiom
    ``_gather_step()(table, idx)`` / ``self._blob_step(layout)(…)``."""
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr in DISPATCH_ATTRS:
        return f.attr
    if isinstance(f, ast.Call):
        inner = f.func
        name = inner.attr if isinstance(inner, ast.Attribute) else (
            inner.id if isinstance(inner, ast.Name) else None)
        if name is not None and name.endswith("_step"):
            return f"{name}()"
    return None


def _resolve_call(project: Project, mi: ModuleInfo,
                  node: ast.Call) -> Optional[Tuple[ModuleInfo, ast.AST]]:
    """The project-resolution the dataflow core uses for plain calls:
    bare names through all_functions/imports, ``mod.fn`` through an
    imported project module."""
    d = dotted(node.func)
    if d is None:
        return None
    if "." not in d:
        fns = mi.all_functions.get(d)
        if fns:
            return mi, fns[0]
        return project.resolve_function(mi, d)
    root, _, attr = d.rpartition(".")
    target = project.modules.get(mi.imports.get(root, ""))
    if target is not None and "." not in attr \
            and attr in target.functions:
        return target, target.functions[attr]
    return None


def _is_jit_dispatch(project: Project, mi: ModuleInfo, node: ast.Call,
                     jit_ids: Set[int]) -> Optional[str]:
    resolved = _resolve_call(project, mi, node)
    if resolved is not None and id(resolved[1]) in jit_ids:
        return getattr(resolved[1], "name", "<jit>")
    return None


# -- hot-root discovery -----------------------------------------------------


def _module_units(mi: ModuleInfo):
    """(class name or None, ClassDef or None, fn) for every top-level
    function and class-body method. Nested defs are reached
    interprocedurally from their parent, not walked as roots."""
    for node in mi.sf.tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield None, None, node
        elif isinstance(node, ast.ClassDef):
            for stmt in node.body:
                if isinstance(stmt, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    yield node.name, node, stmt


def _is_named_root(path: str, cls: Optional[str], fn_name: str) -> bool:
    for p, c, n in NAMED_ROOTS:
        if path.endswith(p) and fn_name == n \
                and (c is None or c == cls):
            return True
    return False


def _has_dispatch(project: Project, mi: ModuleInfo, fn: ast.AST,
                  jit_ids: Set[int]) -> bool:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        if _dispatch_label(node) is not None:
            return True
        if _is_jit_dispatch(project, mi, node, jit_ids) is not None:
            return True
    return False


def find_hot_roots(project: Project, jit_ids: Optional[Set[int]] = None
                   ) -> List[Tuple[ModuleInfo, ast.AST,
                                   Optional[ast.ClassDef], str]]:
    """Every in-scope function/method that issues a device dispatch,
    plus the named serving spine. Sorted by label so shared-site
    finding attribution is deterministic."""
    if jit_ids is None:
        jit_ids = {id(fn) for _, fn in find_entries(project)}
    roots = []
    seen: Set[int] = set()
    for modname in sorted(project.modules):
        mi = project.modules[modname]
        path = mi.sf.path
        if not _in_scope(path):
            continue
        for cls_name, cls_node, fn in _module_units(mi):
            if id(fn) in seen:
                continue
            if _is_named_root(path, cls_name, fn.name) \
                    or _has_dispatch(project, mi, fn, jit_ids):
                seen.add(id(fn))
                owner = f"{cls_name}." if cls_name else ""
                roots.append((mi, fn, cls_node,
                              f"{path}::{owner}{fn.name}"))
    roots.sort(key=lambda r: r[3])
    return roots


# -- the residency-aware interpreter state ----------------------------------


class _DevSink(EventSink):
    """Ordered, deduplicated residency event stream for one root.

    The core's loop bodies run twice (widening) and exclusive branch
    arms run serialized, so raw emission both duplicates and
    scrambles; dedup on (kind, site, how) keeps the first occurrence,
    and the ordering rule additionally gates on straight-line events
    (branch_depth 0, not in a loop) where emission order IS program
    order."""

    def __init__(self) -> None:
        self.events: List[tuple] = []
        self._seen: Set[tuple] = set()

    def _emit(self, key: tuple, ev: tuple) -> None:
        if key in self._seen:
            return
        self._seen.add(key)
        self.events.append(ev)

    def host_sync(self, path, line, how, val, in_loop,
                  branch_depth=0):
        self._emit(("sync", path, line, how),
                   ("sync", path, line, how, val, in_loop,
                    branch_depth))

    def h2d(self, path, line, how, val, in_loop, staged,
            branch_depth=0):
        self._emit(("h2d", path, line, how),
                   ("h2d", path, line, how, val, in_loop, staged,
                    branch_depth))

    def device_dispatch(self, path, line, label, arg_chains, out_chain,
                        in_loop, branch_depth=0):
        self._emit(("dispatch", path, line, label),
                   ("dispatch", path, line, label, arg_chains,
                    out_chain, in_loop, branch_depth))


class _DevState(dataflow._State):
    """The core's state plus the codebase's device boundaries: known
    device tables on ``self``, dispatch-attr calls as residency
    sources (not walked — the callee is its own root), jitted-entry
    calls likewise, and ``self.method(…)`` resolution through the
    root's class so residency survives the helper-method hop."""

    def _attribute(self, node: ast.Attribute) -> AbsVal:
        if isinstance(node.value, ast.Name) and node.value.id == "self":
            suffixes = DEVICE_ATTRS.get(node.attr)
            if suffixes and self.mi.sf.path.endswith(suffixes):
                site = (f"{self.mi.sf.path}:{node.lineno} "
                        f"self.{node.attr} (device table)")
                return AbsVal.array(None, None,
                                    origin=f"self.{node.attr}",
                                    device=True, dev_chain=(site,))
        return super()._attribute(node)

    def _call(self, node: ast.Call) -> AbsVal:
        label = _dispatch_label(node)
        if label is not None:
            return self._dispatch(node, label)
        fn = node.func
        if isinstance(fn, ast.Attribute) \
                and isinstance(fn.value, ast.Name) \
                and fn.value.id == "self":
            meth = self.interp.self_methods.get(fn.attr)
            if meth is not None:
                return self._self_call(node, meth)
        return super()._call(node)

    def _project_call(self, node: ast.Call, q: str,
                      argvals: List[AbsVal]) -> AbsVal:
        name = _is_jit_dispatch(self.interp.project, self.mi, node,
                                self.interp.jit_ids)
        if name is not None:
            return self._emit_dispatch(node, f"jit `{name}`", argvals)
        return super()._project_call(node, q, argvals)

    def _dispatch(self, node: ast.Call, label: str) -> AbsVal:
        f = node.func
        if isinstance(f, ast.Attribute):
            self.eval(f.value)
        elif isinstance(f, ast.Call):
            for a in f.args:
                self.eval(a)
        argvals = [self.eval(a) for a in node.args]
        for kw in node.keywords:
            if kw.value is not None:
                argvals.append(self.eval(kw.value))
        return self._emit_dispatch(node, label, argvals)

    def _emit_dispatch(self, node: ast.Call, label: str,
                       argvals: Sequence[AbsVal]) -> AbsVal:
        path, line = self.mi.sf.path, node.lineno
        chains: List[Tuple[str, ...]] = []
        for v in argvals:
            if v.device:
                chains.append(v.dev_chain)
            elif not (v.kind == "const" or v.from_shape):
                # a host value the dispatch consumes — it MAY depend
                # on an earlier readback, so the ordering rule must
                # not call this dispatch independent
                chains.append(("<host>",))
        out_chain = (f"{path}:{line} {label} dispatch",)
        self.sink.device_dispatch(path, line, label, tuple(chains),
                                  out_chain,
                                  self.interp.loop_depth > 0,
                                  self.interp.branch_depth)
        return AbsVal.array(None, None, origin=f"{label} result",
                            device=True, dev_chain=out_chain)

    def _self_call(self, node: ast.Call, meth: ast.AST) -> AbsVal:
        params = [a.arg for a in meth.args.args]
        env: Dict[str, AbsVal] = {}
        if params and params[0] == "self":
            env["self"] = AbsVal.host(origin="self")
            params = params[1:]
        argvals = [self.eval(a) for a in node.args]
        for p, v in zip(params, argvals):
            env[p] = v if v.origin \
                else dataflow._with_origin(v, f"param `{p}`")
        for kw in node.keywords:
            if kw.value is None:
                continue
            v = self.eval(kw.value)
            if kw.arg is not None and kw.arg in params:
                env[kw.arg] = v
        self._default_params(meth, env)
        return self.interp.run_function(self.mi, meth, env,
                                        self.depth + 1)


class _DevInterp(Interp):
    state_cls = _DevState

    def __init__(self, project: Project, sink: EventSink,
                 jit_ids: Set[int],
                 self_methods: Dict[str, ast.AST]):
        super().__init__(project, sink)
        self.jit_ids = jit_ids
        #: the root's class methods, for `self.helper(…)` resolution
        self.self_methods = self_methods


def _walk_root(project: Project, jit_ids: Set[int], mi: ModuleInfo,
               fn: ast.AST,
               cls_node: Optional[ast.ClassDef]) -> _DevSink:
    sink = _DevSink()
    methods: Dict[str, ast.AST] = {}
    if cls_node is not None:
        for stmt in cls_node.body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                methods[stmt.name] = stmt
    interp = _DevInterp(project, sink, jit_ids, methods)
    env = dataflow.param_shapes(mi, fn)
    # `self` is an object, never an array — param_shapes' array seed
    # would swallow every attribute/method access on it
    env["self"] = AbsVal.host(origin="self")
    interp.run_function(mi, fn, env)
    return sink


# -- rules over the event stream --------------------------------------------


def _residency(val: AbsVal) -> Tuple[str, ...]:
    return tuple(val.dev_chain)


def _findings_for_root(sink: _DevSink, label: str) -> List[Finding]:
    out: List[Finding] = []
    #: straight-line bulk readbacks, for the fragmented face
    frag: List[tuple] = []
    for ev in sink.events:
        if ev[0] == "sync":
            _, path, line, how, val, in_loop, bd = ev
            what = val.origin or "a device value"
            if how in _SCALAR_SYNCS:
                out.append(Finding(
                    path, line, RULE_SYNC,
                    f"implicit host sync: `{how}` coerces "
                    f"device-resident {what} on hot path `{label}` — "
                    f"the host blocks mid-dispatch; read back once, "
                    f"in bulk, at the path's edge",
                    residency=_residency(val)))
            elif how in _BULK_SYNCS and in_loop:
                out.append(Finding(
                    path, line, RULE_SYNC,
                    f"per-iteration host readback `{how}` of "
                    f"device-resident {what} inside a loop on hot "
                    f"path `{label}` — batch one readback outside "
                    f"the loop",
                    residency=_residency(val)))
            elif how in _BULK_SYNCS and bd == 0 and val.dev_chain:
                frag.append((path, line, how, val))
        elif ev[0] == "h2d":
            _, path, line, how, val, in_loop, staged, bd = ev
            if in_loop and not staged and not val.device:
                out.append(Finding(
                    path, line, RULE_H2D,
                    f"per-iteration host→device transfer `{how}` "
                    f"inside a loop on hot path `{label}` — hoist it "
                    f"out of the loop, or stage it ahead into "
                    f"instance state (the capture-prefetch "
                    f"double-buffer idiom)",
                    residency=(f"{path}:{line} {how}",)))
    # fragmented readback: several straight-line bulk readbacks on one
    # hot path — each is a separate blocking transfer where a single
    # batched jax.device_get would do
    if len(frag) >= 2:
        path, line, how, val = frag[0]
        others = ", ".join(f"{p.rsplit('/', 1)[-1]}:{ln}"
                           for p, ln, _h, _v in frag[1:])
        out.append(Finding(
            path, line, RULE_SYNC,
            f"fragmented readback: {len(frag)} separate host "
            f"readbacks on hot path `{label}` (also {others}) — "
            f"batch them into a single jax.device_get",
            residency=_residency(val)))
    out.extend(_ordering_findings(sink, label))
    return out


def _ordering_findings(sink: _DevSink, label: str) -> List[Finding]:
    """A straight-line bulk readback of one dispatch's result issued
    BEFORE a later, provably independent dispatch: the readback
    blocks the host, so the second dispatch misses its pipeline slot.
    Independence is conservative — every dispatch argument must be
    device-resident (chains disjoint from the readback's) or a known
    static; any plain host argument may depend on the readback and
    vetoes the pairing."""
    out: List[Finding] = []
    events = sink.events
    for i, ev in enumerate(events):
        if ev[0] != "sync":
            continue
        _, path, line, how, val, in_loop, bd = ev
        if how not in _BULK_SYNCS or in_loop or bd != 0 \
                or not val.dev_chain:
            continue
        chain = set(val.dev_chain)
        for later in events[i + 1:]:
            if later[0] != "dispatch":
                continue
            (_, dpath, dline, dlabel, arg_chains, _out_chain,
             d_in_loop, d_bd) = later
            if d_in_loop or d_bd != 0:
                continue
            if any(c == ("<host>",) for c in arg_chains):
                continue
            if any(chain & set(c) for c in arg_chains):
                continue
            out.append(Finding(
                path, line, RULE_ORDER,
                f"host readback `{how}` of "
                f"{val.origin or 'a device value'} blocks before the "
                f"independent device dispatch `{dlabel}` at "
                f"{dpath}:{dline} on hot path `{label}` — issue the "
                f"dispatch first (or batch readbacks after all "
                f"dispatches) to keep the device pipeline full",
                residency=_residency(val)))
            break
    return out


# -- missing-donation (syntactic, over the jitted entries) ------------------


def _int_elems(node: ast.expr) -> List[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return [node.value]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int):
                out.append(e.value)
        return out
    return []


def _decorated_donations(mi: ModuleInfo, fn: ast.AST) -> Set[int]:
    donated: Set[int] = set()
    for dec in getattr(fn, "decorator_list", []):
        if not isinstance(dec, ast.Call):
            continue
        q = mi.qualify(dec.func)
        keywords = ()
        if q in ("functools.partial", "partial") and dec.args \
                and mi.qualify(dec.args[0]) in ("jax.jit", "jit",
                                                "jax.pmap"):
            keywords = dec.keywords
        elif q in ("jax.jit", "jit", "jax.pmap"):
            keywords = dec.keywords
        for kw in keywords:
            if kw.arg in ("donate_argnums", "donate_argnames"):
                donated.update(_int_elems(kw.value))
                donated.add(-1)  # marker: donation was declared
    return donated


def _wrap_site_donations(project: Project) -> Dict[int, Set[int]]:
    """``jax.jit(fn, donate_argnums=…)`` wrap-call sites, mapped onto
    the resolved function."""
    out: Dict[int, Set[int]] = {}
    for mi in project.modules.values():
        # wrap sites for in-scope entries live in-scope too (the wrap
        # IS the dispatch the hot path calls) — skip the rest of the
        # tree rather than re-walking it
        if not _in_scope(mi.sf.path):
            continue
        for node in ast.walk(mi.sf.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            if mi.qualify(node.func) not in ("jax.jit", "jit",
                                             "jax.pmap"):
                continue
            donated: Set[int] = set()
            for kw in node.keywords:
                if kw.arg in ("donate_argnums", "donate_argnames"):
                    donated.update(_int_elems(kw.value))
                    donated.add(-1)
            if not donated:
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Name):
                resolved = project.resolve_function(mi, arg.id)
                if resolved is not None:
                    out.setdefault(id(resolved[1]),
                                   set()).update(donated)
    return out


def _updated_params(fn: ast.AST) -> List[Tuple[int, str, int]]:
    """(param index, param name, line) for every in-place functional
    update of a direct parameter: ``param.at[…].set(…)`` or
    ``lax.dynamic_update_slice(param, …)``."""
    params = [a.arg for a in getattr(fn, "args", ast.arguments(
        args=[], posonlyargs=[], kwonlyargs=[], kw_defaults=[],
        defaults=[])).args]
    index = {p: i for i, p in enumerate(params)}
    out: List[Tuple[int, str, int]] = []
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        # param.at[...].set(...)
        if isinstance(f, ast.Attribute) and f.attr == "set" \
                and isinstance(f.value, ast.Subscript) \
                and isinstance(f.value.value, ast.Attribute) \
                and f.value.value.attr == "at" \
                and isinstance(f.value.value.value, ast.Name):
            name = f.value.value.value.id
            if name in index:
                out.append((index[name], name, node.lineno))
        # dynamic_update_slice(param, ...)
        d = dotted(f) or ""
        if d.rsplit(".", 1)[-1] == "dynamic_update_slice" \
                and node.args and isinstance(node.args[0], ast.Name):
            name = node.args[0].id
            if name in index:
                out.append((index[name], name, node.lineno))
    return out


def check_donation(index: ProjectIndex,
                   project: Optional[Project] = None) -> List[Finding]:
    project = project or project_for(index)
    wrap_donations = _wrap_site_donations(project)
    findings: List[Finding] = []
    for mi, fn in find_entries(project):
        if not _in_scope(mi.sf.path):
            continue
        donated = _decorated_donations(mi, fn)
        donated |= wrap_donations.get(id(fn), set())
        name = getattr(fn, "name", "<lambda>")
        seen: Set[Tuple[int, int]] = set()
        for idx, pname, line in _updated_params(fn):
            if idx in donated or (idx, line) in seen:
                continue
            seen.add((idx, line))
            findings.append(Finding(
                mi.sf.path, line, RULE_DONATE,
                f"jitted entry `{name}` overwrites its parameter "
                f"`{pname}` in place without donating it — XLA "
                f"allocates a fresh output buffer every call; add "
                f"donate_argnums=({idx},) to the jit wrap",
                residency=(f"{mi.sf.path}:{getattr(fn, 'lineno', line)}"
                           f" jit `{name}` param `{pname}`",)))
    return findings


# -- the checker ------------------------------------------------------------


@checker
def check(index: ProjectIndex) -> List[Finding]:
    project = project_for(index)
    findings = check_donation(index, project)
    jit_ids = {id(fn) for _, fn in find_entries(project)}
    picked: Dict[Tuple[str, int, str], Finding] = {}
    for mi, fn, cls_node, label in find_hot_roots(project, jit_ids):
        sink = _walk_root(project, jit_ids, mi, fn, cls_node)
        for f in _findings_for_root(sink, label):
            # the first (label-sorted) root to reach a shared helper
            # site owns the attribution
            picked.setdefault((f.path, f.line, f.rule), f)
    findings.extend(picked.values())
    return sorted(set(findings))
check.emits = (RULE_SYNC, RULE_H2D, RULE_DONATE, RULE_ORDER)
