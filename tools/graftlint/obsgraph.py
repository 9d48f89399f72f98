"""GL10xx — the observability/config contract graph (ObsModel).

Six string-keyed telemetry/config planes are produced in one module and
consumed by literal name in another: the metrics registry, labeled
families, timeline series, flight-recorder kinds, the /debug route
registry, and the INI/param surface.  GL601-609 prove every such name is
a *literal*; nothing proved that the literal on the consuming side
matches one on the producing side — the two worst recent bugs were
exactly this class (the dead `iter_cost1` gflops attribution; the SLO
engine reading ``aggregator.requests.rate`` where the producer publishes
``aggregator.request.rate``).

This pass builds a project-wide **ObsModel** (cached in
``project.cache`` alongside the ContractModel) with every producer and
consumer site, then cross-checks the dataflow:

* producers — ``metrics.counter/gauge/histogram`` (+ the ``inc`` /
  ``set_gauge`` / ``observe`` conveniences and ``trace.span/record``,
  which feed the same registry), ``metrics.Family`` constructions with
  their label-key sets (including bounded-loop expansions such as
  ``Family("flight." + key) for key in _FLIGHT_KEYS``),
  ``timeline.record`` series, ``flightrec.record/span`` kinds,
  ``ctlaudit.record`` rules, the metrics_http ``_routes`` registry,
  ``core/params`` specs + the ``LIVE_ACTUATIONS`` registry, and the
  qualmon triage-verdict classifier returns;
* consumers — ``timeline.latest/window_values/window_stats/points``
  reads (the SLO engine's ``_Objective`` series lists are expanded
  through a bounded string evaluator that understands concatenation
  and refined ``base == "server"`` conditionals),
  ``metrics.counter_value/gauge_value/histogram_or_none`` reads,
  hostprof's ``EXPECTED_ROUTES``
  (tests/test_hostprof.py), docs/PARAMETERS.md rows, and
  ``[Service]``/``[Aggregator]``/``[Index]``/``[QueryConfig]`` INI key
  parsing.

Series derivation is modeled, not guessed: a registry counter ``X``
exists on the timeline as ``X.rate``; a histogram as ``X.p50_ms`` /
``X.p99_ms`` / ``X.rate``; a gauge as ``X``; a family sample with
labels as ``X{k="v"}`` and without as bare ``X`` (utils/timeline.py
``sample_now``).

Rules:

* GL1001 — a consumed name is never published by any producer (the
  PR 15 ``aggregator.requests.rate`` bug class; error tier).  Includes
  kind mismatches (``counter_value`` of a gauge) and triage verdicts
  returned by the classifier but missing from ``TRIAGE_VERDICTS``.
* GL1002 — a published name is never consumed by a structured reader
  AND never mentioned in docs/tests/tools (warn tier; a justified
  baseline entry is the sanctioned waiver).  Also flags a
  ``TRIAGE_VERDICTS`` registry entry no classifier can return.
* GL1003 — producer/consumer label-set mismatch on a family: two
  producer sites publish the same family with different label-key
  sets, or a consumer reads the BARE series name of a family that only
  ever publishes labeled samples (the bare timeline key would never
  receive a point).
* GL1004 — config-surface/doc drift: a core/params spec or live
  actuation without a PARAMETERS.md mention, a PARAMETERS.md table row
  naming no spec/actuation, or a parsed serve-tier INI key
  (``[Service]``/``[Aggregator]``/``[QueryConfig]``) PARAMETERS.md
  never documents.
* GL1005 — a literal param name at a ``set_parameter`` / ``get_param``
  / actuation call site with no backing spec or registry entry, or an
  index-scoped ``LIVE_ACTUATIONS`` entry whose name matches no
  ParamSpec (the actuation would raise at apply time).
* GL1006 — a /debug route registered in metrics_http's ``_routes``
  but absent from ``EXPECTED_ROUTES`` (or vice versa): the
  route-contract tests would silently skip the new endpoint.

Cross-tree surfaces (docs/PARAMETERS.md, tests/test_hostprof.py)
are consulted only for disk-backed
projects (``project.source_root``); in-memory fixture projects may
plant them via ``extra_sources`` (a ``docs/PARAMETERS.md`` key) or
in-project assignments (``EXPECTED_ROUTES = [...]``).  The runtime
complement lives in tools/graftlint/schemadump.py (`--schema-dump`).
"""

from __future__ import annotations

import ast
import dataclasses
import os
import re
from typing import Dict, FrozenSet, List, Optional, Sequence, Set, Tuple

from tools.graftlint.core import Finding, ModuleInfo, Project, _dotted

RULES = {
    "GL1001": "consumed observability/config name is never published by "
              "any producer (stale or typo'd consumer literal)",
    "GL1002": "published name is never consumed and never documented "
              "(warn tier; justify in the baseline or delete it)",
    "GL1003": "producer/consumer label-set mismatch on a metric family",
    "GL1004": "param/config surface and docs/PARAMETERS.md disagree "
              "(spec without a doc row, or doc row without a spec)",
    "GL1005": "param name used or actuation registered with no backing "
              "spec/registry entry",
    "GL1006": "/debug route registry and EXPECTED_ROUTES disagree",
}

CACHE_KEY = "obsgraph.model"

_METRICS_MODULE = "sptag_tpu.utils.metrics"
_TRACE_MODULE = "sptag_tpu.utils.trace"
_TIMELINE_MODULE = "sptag_tpu.utils.timeline"
_FLIGHT_MODULE = "sptag_tpu.utils.flightrec"
_QUALMON_MODULE = "sptag_tpu.utils.qualmon"
_CTLAUDIT_MODULE = "sptag_tpu.serve.ctlaudit"
_PARAMS_MODULE = "sptag_tpu.core.params"

#: expansion caps for the bounded string evaluator — anything bigger is
#: treated as unbounded (the GL60x literal rules already bound the raw
#: call-site surface; the evaluator only needs small closed sets)
_MAX_SET = 64

_IDENTISH = re.compile(r"^[A-Za-z_][A-Za-z0-9_.\[\]]*$")
_BACKTICK = re.compile(r"`([^`]+)`")


@dataclasses.dataclass(frozen=True, order=True)
class Site:
    path: str
    line: int
    symbol: str = ""


# ---------------------------------------------------------------------------
# bounded string evaluation
# ---------------------------------------------------------------------------

class _Env:
    """Best-effort, bounded string-set bindings for one function scope:
    module-level str constants, simple local assignments, and for-loop/
    comprehension targets iterating literal tuples of constants.  A
    lookup answers "which strings can this name hold" or None for
    unbounded."""

    def __init__(self, mod: ModuleInfo, fn_node: Optional[ast.AST]):
        self.mod = mod
        self.assigns: Dict[str, List[ast.AST]] = {}
        self.loops: Dict[str, Optional[Set[str]]] = {}
        self.tuples: Dict[str, ast.AST] = {}
        self._module_bindings()
        if fn_node is not None:
            self._scope_bindings(fn_node)

    def _module_bindings(self) -> None:
        for node in self.mod.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                name = node.targets[0].id
                if isinstance(node.value, ast.Constant) and \
                        isinstance(node.value.value, str):
                    self.assigns.setdefault(name, []).append(node.value)
                elif isinstance(node.value, (ast.Tuple, ast.List)):
                    self.tuples[name] = node.value

    def _scope_bindings(self, fn_node: ast.AST) -> None:
        for node in ast.walk(fn_node):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if isinstance(tgt, ast.Name):
                    if isinstance(node.value, (ast.Tuple, ast.List)):
                        self.tuples[tgt.id] = node.value
                    else:
                        self.assigns.setdefault(tgt.id, []) \
                            .append(node.value)
            elif isinstance(node, ast.For):
                self._bind_loop(node.target, node.iter)
            elif isinstance(node, (ast.ListComp, ast.SetComp,
                                   ast.GeneratorExp, ast.DictComp)):
                for gen in node.generators:
                    self._bind_loop(gen.target, gen.iter)

    def _rows(self, iter_expr: ast.AST) -> Optional[List[ast.AST]]:
        if isinstance(iter_expr, (ast.Tuple, ast.List)):
            return list(iter_expr.elts)
        if isinstance(iter_expr, ast.Name):
            lit = self.tuples.get(iter_expr.id)
            if lit is not None:
                return list(lit.elts)
        return None

    def _bind_loop(self, target: ast.AST, iter_expr: ast.AST) -> None:
        rows = self._rows(iter_expr)
        targets: List[ast.AST] = (
            list(target.elts) if isinstance(target, ast.Tuple)
            else [target])
        for i, tgt in enumerate(targets):
            if not isinstance(tgt, ast.Name):
                continue
            if rows is None:
                self.loops.setdefault(tgt.id, None)
                continue
            vals: Optional[Set[str]] = set()
            for row in rows:
                elt = row
                if isinstance(target, ast.Tuple):
                    if isinstance(row, (ast.Tuple, ast.List)) and \
                            i < len(row.elts):
                        elt = row.elts[i]
                    else:
                        vals = None
                        break
                if isinstance(elt, ast.Constant) and \
                        isinstance(elt.value, str):
                    vals.add(elt.value)
                elif isinstance(elt, ast.Constant):
                    continue          # non-str constant: not a name source
                else:
                    vals = None
                    break
            self.loops[tgt.id] = vals

    def lookup(self, name: str, overlay: Dict[str, Optional[Set[str]]],
               seen: FrozenSet[str]) -> Optional[Set[str]]:
        if name in overlay:
            return overlay[name]
        if name in seen:
            return None
        if name in self.loops:
            return self.loops[name]
        if name in self.assigns:
            out: Set[str] = set()
            for expr in self.assigns[name]:
                vals = eval_str_set(expr, self, overlay,
                                    seen | frozenset([name]))
                if vals is None:
                    return None
                out |= vals
            return out if out and len(out) <= _MAX_SET else None
        return None


def eval_str_set(expr: ast.AST, env: _Env,
                 overlay: Optional[Dict[str, Optional[Set[str]]]] = None,
                 seen: FrozenSet[str] = frozenset()
                 ) -> Optional[Set[str]]:
    """The bounded set of strings `expr` can evaluate to, or None."""
    overlay = overlay or {}
    if isinstance(expr, ast.Constant):
        return {expr.value} if isinstance(expr.value, str) else None
    if isinstance(expr, ast.Name):
        return env.lookup(expr.id, overlay, seen)
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        left = eval_str_set(expr.left, env, overlay, seen)
        right = eval_str_set(expr.right, env, overlay, seen)
        if left is None or right is None:
            return None
        out = {a + b for a in left for b in right}
        return out if len(out) <= _MAX_SET else None
    if isinstance(expr, ast.IfExp):
        # refined-branch evaluation: `X + ".a" if base == "server" else
        # Y` must not leak the "aggregator" binding into the body arm
        body_overlay, orelse_overlay = dict(overlay), dict(overlay)
        test = expr.test
        if isinstance(test, ast.Compare) and len(test.ops) == 1 and \
                isinstance(test.ops[0], ast.Eq) and \
                len(test.comparators) == 1:
            lhs, rhs = test.left, test.comparators[0]
            if isinstance(rhs, ast.Name) and isinstance(lhs, ast.Constant):
                lhs, rhs = rhs, lhs
            if isinstance(lhs, ast.Name) and \
                    isinstance(rhs, ast.Constant) and \
                    isinstance(rhs.value, str):
                cur = env.lookup(lhs.id, overlay, seen)
                body_overlay[lhs.id] = {rhs.value}
                if cur is not None:
                    orelse_overlay[lhs.id] = cur - {rhs.value}
        body = eval_str_set(expr.body, env, body_overlay, seen)
        orelse = eval_str_set(expr.orelse, env, orelse_overlay, seen)
        if body is None or orelse is None:
            return None
        out = body | orelse
        return out if len(out) <= _MAX_SET else None
    if isinstance(expr, ast.JoinedStr):
        parts: List[Set[str]] = []
        for value in expr.values:
            if isinstance(value, ast.Constant):
                parts.append({str(value.value)})
                continue
            if isinstance(value, ast.FormattedValue):
                sub = eval_str_set(value.value, env, overlay, seen)
                if sub is None:
                    return None
                parts.append(sub)
                continue
            return None
        out = {""}
        for part in parts:
            out = {a + b for a in out for b in part}
            if len(out) > _MAX_SET:
                return None
        return out
    return None


def eval_str_prefixes(expr: ast.AST, env: _Env) -> Set[str]:
    """When full evaluation fails, the bounded literal PREFIXES of
    `expr` (e.g. ``"quality." + name`` -> {"quality."}) — recorded as
    wildcard producers so dynamic-name surfaces stay modeled."""
    full = eval_str_set(expr, env)
    if full is not None:
        return set()
    if isinstance(expr, ast.BinOp) and isinstance(expr.op, ast.Add):
        left = eval_str_set(expr.left, env)
        if left is not None:
            return set(left)
        return eval_str_prefixes(expr.left, env)
    if isinstance(expr, ast.JoinedStr) and expr.values:
        head = expr.values[0]
        if isinstance(head, ast.Constant):
            return {str(head.value)}
        if isinstance(head, ast.FormattedValue):
            sub = eval_str_set(head.value, env)
            if sub is not None:
                return set(sub)
    return set()


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FamilyProd:
    sites: List[Site] = dataclasses.field(default_factory=list)
    #: distinct non-empty label-key sets observed across producer sites
    label_sets: Set[FrozenSet[str]] = dataclasses.field(default_factory=set)
    unlabeled: bool = False           # an unlabeled aggregate add exists
    unknown_labels: bool = False      # an unresolvable add: assume both


@dataclasses.dataclass
class SeriesProd:
    sites: List[Site] = dataclasses.field(default_factory=list)
    bare: bool = False                # recorded without a label
    labeled: bool = False             # recorded with a label


@dataclasses.dataclass
class ObsModel:
    """Every producer and consumer of a string-keyed telemetry/config
    name, project-wide.  Built once per lint invocation and shared via
    ``project.cache[CACHE_KEY]`` (schemadump reuses it)."""

    # producers
    metrics: Dict[str, Dict[str, List[Site]]] = \
        dataclasses.field(default_factory=dict)   # name -> kind -> sites
    metric_prefixes: Set[str] = dataclasses.field(default_factory=set)
    families: Dict[str, FamilyProd] = dataclasses.field(default_factory=dict)
    family_prefixes: Set[str] = dataclasses.field(default_factory=set)
    timeline: Dict[str, SeriesProd] = dataclasses.field(default_factory=dict)
    flight_kinds: Dict[str, List[Site]] = \
        dataclasses.field(default_factory=dict)
    ctl_rules: Dict[str, List[Site]] = dataclasses.field(default_factory=dict)
    routes: Dict[str, Site] = dataclasses.field(default_factory=dict)
    param_specs: Dict[str, Site] = dataclasses.field(default_factory=dict)
    actuations: Dict[str, Tuple[str, Site]] = \
        dataclasses.field(default_factory=dict)   # name -> (scope, site)
    verdicts_returned: Dict[str, Site] = \
        dataclasses.field(default_factory=dict)
    verdict_registry: Dict[str, Site] = \
        dataclasses.field(default_factory=dict)

    # consumers
    timeline_reads: List[Tuple[str, Site]] = \
        dataclasses.field(default_factory=list)
    metric_reads: List[Tuple[str, str, Site]] = \
        dataclasses.field(default_factory=list)   # (name, kind, site)
    expected_routes: Dict[str, Site] = dataclasses.field(default_factory=dict)
    param_uses: List[Tuple[str, Site]] = \
        dataclasses.field(default_factory=list)
    ini_reads: List[Tuple[str, str, Site]] = \
        dataclasses.field(default_factory=list)   # (section, key, site)
    doc_rows: Dict[str, int] = dataclasses.field(default_factory=dict)
    doc_mentions: Set[str] = dataclasses.field(default_factory=set)
    has_doc: bool = False
    #: docs/tests/tools text for the GL1002 "documented anywhere" check
    corpus: str = ""
    has_corpus: bool = False

    # ------------------------------------------------------------ queries

    def add_metric(self, name: str, kind: str, site: Site) -> None:
        self.metrics.setdefault(name, {}).setdefault(kind, []).append(site)

    def metric_kinds(self, name: str) -> Set[str]:
        return set(self.metrics.get(name, ()))

    def bare_series(self) -> Set[str]:
        """Every timeline key a consumer may read WITHOUT a label part:
        direct bare records, counter/histogram derivations, gauges, and
        families carrying an unlabeled aggregate sample."""
        out: Set[str] = set()
        for name, prod in self.timeline.items():
            if prod.bare:
                out.add(name)
        for name, kinds in self.metrics.items():
            if "counter" in kinds:
                out.add(name + ".rate")
            if "gauge" in kinds:
                out.add(name)
            if "histogram" in kinds:
                out.update((name + ".p50_ms", name + ".p99_ms",
                            name + ".rate"))
        for name, fam in self.families.items():
            if fam.unlabeled or fam.unknown_labels:
                out.add(name)
        return out

    def labeled_only_series(self) -> Set[str]:
        """Names published ONLY under a label — a bare read of one of
        these can never see a point (the GL1003 consumer direction)."""
        out: Set[str] = set()
        for name, fam in self.families.items():
            if fam.label_sets and not fam.unlabeled \
                    and not fam.unknown_labels:
                out.add(name)
        for name, prod in self.timeline.items():
            if prod.labeled and not prod.bare:
                out.add(name)
        return out - self.bare_series()

    def matches_prefix(self, name: str) -> bool:
        return any(name.startswith(p)
                   for p in (self.metric_prefixes | self.family_prefixes)
                   if p)

    def all_published(self) -> Dict[str, List[Site]]:
        """Producer name -> sites across every plane (GL1002 surface)."""
        out: Dict[str, List[Site]] = {}
        for name, kinds in self.metrics.items():
            for sites in kinds.values():
                out.setdefault(name, []).extend(sites)
        for name, fam in self.families.items():
            out.setdefault(name, []).extend(fam.sites)
        for name, prod in self.timeline.items():
            out.setdefault(name, []).extend(prod.sites)
        for name, sites in self.flight_kinds.items():
            out.setdefault(name, []).extend(sites)
        for name, sites in self.ctl_rules.items():
            out.setdefault(name, []).extend(sites)
        return out


# ---------------------------------------------------------------------------
# harvest
# ---------------------------------------------------------------------------

def _resolve_call(call: ast.Call, mod: ModuleInfo
                  ) -> Tuple[Optional[str], str]:
    """-> (full module path, function name) for `module.fn(...)` calls
    resolved through import aliases, or (None, bare-name) otherwise."""
    func = call.func
    if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
        return mod.resolve_head(func.value.id), func.attr
    if isinstance(func, ast.Name):
        target = mod.from_imports.get(func.id, "")
        if target:
            modpath, _, sym = target.rpartition(".")
            return modpath, sym
        return None, func.id
    return None, ""


def _arg(call: ast.Call, pos: int, kwname: str) -> Optional[ast.AST]:
    if len(call.args) > pos and not any(
            isinstance(a, ast.Starred) for a in call.args[:pos + 1]):
        return call.args[pos]
    for kw in call.keywords:
        if kw.arg == kwname:
            return kw.value
    return None


def _enclosing(mod: ModuleInfo, lineno: int) -> Tuple[str, Optional[ast.AST]]:
    best, best_line, node = "", -1, None
    for fn in mod.functions:
        end = getattr(fn.node, "end_lineno", fn.node.lineno)
        if fn.node.lineno <= lineno <= end and fn.node.lineno > best_line:
            best, best_line, node = fn.qualname, fn.node.lineno, fn.node
    return best, node


class _ModuleHarvest:
    """One pass over a module collecting every producer/consumer site."""

    _METRIC_PRODUCERS = {"counter": "counter", "inc": "counter",
                         "gauge": "gauge", "set_gauge": "gauge",
                         "histogram": "histogram", "observe": "histogram"}
    _METRIC_READS = {"counter_value": "counter", "gauge_value": "gauge",
                     "histogram_or_none": "histogram"}
    _TIMELINE_READS = {"latest", "window_values", "window_stats", "points"}
    _PARAM_USES = {"set_parameter", "get_param"}
    _ACTUATION_USES = {"clamp_actuation": 0, "actuation_spec": 0,
                       "actuate_index": 1, "bind_tier_knob": 0}

    def __init__(self, mod: ModuleInfo, model: ObsModel):
        self.mod = mod
        self.model = model
        self._envs: Dict[int, _Env] = {}
        #: Family-construct names per local variable, per function id —
        #: fam.add(value, {...}) label harvesting
        self._fam_vars: Dict[Tuple[int, str], Set[str]] = {}

    # ------------------------------------------------------------- helpers

    def _env_at(self, lineno: int) -> Tuple[str, _Env]:
        symbol, fn_node = _enclosing(self.mod, lineno)
        key = id(fn_node)
        if key not in self._envs:
            self._envs[key] = _Env(self.mod, fn_node)
        return symbol, self._envs[key]

    def _site(self, node: ast.AST, symbol: str) -> Site:
        return Site(self.mod.relpath, node.lineno, symbol)

    def _names_or_prefixes(self, expr: ast.AST, env: _Env
                           ) -> Tuple[Set[str], Set[str]]:
        vals = eval_str_set(expr, env)
        if vals is not None:
            return vals, set()
        return set(), eval_str_prefixes(expr, env)

    # ------------------------------------------------------------- harvest

    def run(self) -> None:
        self._harvest_routes_and_expected()
        for node in ast.walk(self.mod.tree):
            if isinstance(node, ast.Call):
                self._harvest_call(node)
            elif isinstance(node, ast.Return):
                self._harvest_verdict_return(node)

    def _harvest_call(self, call: ast.Call) -> None:
        modpath, fn = _resolve_call(call, self.mod)
        symbol, env = self._env_at(call.lineno)
        site = self._site(call, symbol)

        if modpath == _METRICS_MODULE or (
                modpath is None and fn == "Family"):
            self._harvest_metrics_call(call, fn, env, site)
        if modpath == _TRACE_MODULE and fn in ("span", "record"):
            self._harvest_named(call, _arg(call, 0, "name"), env,
                                "histogram", site)
        if modpath == _TIMELINE_MODULE:
            if fn == "record":
                self._harvest_timeline_record(call, env, site)
            elif fn in self._TIMELINE_READS:
                arg = _arg(call, 0, "name")
                if arg is not None:
                    vals = eval_str_set(arg, env)
                    for v in sorted(vals or ()):
                        self.model.timeline_reads.append((v, site))
        if modpath == _FLIGHT_MODULE and fn in ("record", "span"):
            arg = _arg(call, 1, "kind")
            if arg is not None:
                vals = eval_str_set(arg, env)
                for v in sorted(vals or ()):
                    self.model.flight_kinds.setdefault(v, []).append(site)
        if modpath == _CTLAUDIT_MODULE and fn == "record":
            arg = _arg(call, 0, "rule")
            if arg is not None:
                vals = eval_str_set(arg, env)
                for v in sorted(vals or ()):
                    self.model.ctl_rules.setdefault(v, []).append(site)
        if modpath == _QUALMON_MODULE and fn in ("gauge", "inc"):
            arg = _arg(call, 0, "name")
            if arg is not None:
                vals, prefixes = self._names_or_prefixes(arg, env)
                for v in sorted(vals):
                    fam = self.model.families.setdefault(
                        "quality." + v, FamilyProd())
                    fam.sites.append(site)
                    fam.unknown_labels = True
                for p in prefixes:
                    self.model.family_prefixes.add("quality." + p)
        if fn == "_spec" or fn == "ParamSpec":
            arg = _arg(call, 3, "name")
            if arg is not None:
                for v in sorted(eval_str_set(arg, env) or ()):
                    self.model.param_specs.setdefault(v, site)
        if fn == "ActuationSpec":
            arg = _arg(call, 0, "name")
            scope_arg = _arg(call, 4, "scope")
            scope = "index"
            if isinstance(scope_arg, ast.Constant) and \
                    isinstance(scope_arg.value, str):
                scope = scope_arg.value
            if arg is not None:
                for v in sorted(eval_str_set(arg, env) or ()):
                    self.model.actuations.setdefault(v, (scope, site))
        if fn == "_Objective":
            series_arg = _arg(call, 1, "series")
            if isinstance(series_arg, (ast.List, ast.Tuple)):
                for elt in series_arg.elts:
                    for v in sorted(eval_str_set(elt, env) or ()):
                        self.model.timeline_reads.append((v, site))
        if fn in self._PARAM_USES and isinstance(call.func, ast.Attribute):
            arg = _arg(call, 0, "name")
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                self.model.param_uses.append((arg.value, site))
        if fn in self._ACTUATION_USES and (
                modpath == _PARAMS_MODULE
                or isinstance(call.func, ast.Attribute)):
            pos = self._ACTUATION_USES[fn]
            arg = _arg(call, pos, "name" if pos == 0 else "knob")
            if fn == "bind_tier_knob":
                arg = _arg(call, 0, "knob")
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                self.model.param_uses.append((arg.value, site))
        if fn == "get_parameter" and isinstance(call.func, ast.Attribute) \
                and len(call.args) >= 2:
            sec, key = call.args[0], call.args[1]
            if isinstance(sec, ast.Constant) and isinstance(sec.value, str) \
                    and isinstance(key, ast.Constant) \
                    and isinstance(key.value, str):
                self.model.ini_reads.append((sec.value, key.value, site))
        if modpath == _QUALMON_MODULE and fn == "record_sample":
            arg = _arg(call, 5, "verdict")
            if arg is not None:
                for v in sorted(eval_str_set(arg, env) or ()):
                    if v:
                        self.model.verdicts_returned.setdefault(v, site)

    def _harvest_metrics_call(self, call: ast.Call, fn: str, env: _Env,
                              site: Site) -> None:
        if fn in self._METRIC_PRODUCERS:
            self._harvest_named(call, _arg(call, 0, "name"), env,
                                self._METRIC_PRODUCERS[fn], site)
        elif fn in self._METRIC_READS:
            arg = _arg(call, 0, "name")
            if arg is not None:
                vals = eval_str_set(arg, env)
                for v in sorted(vals or ()):
                    self.model.metric_reads.append(
                        (v, self._METRIC_READS[fn], site))
        elif fn == "Family":
            self._harvest_family(call, env, site)

    def _harvest_named(self, call: ast.Call, arg: Optional[ast.AST],
                       env: _Env, kind: str, site: Site) -> None:
        if arg is None:
            return
        vals, prefixes = self._names_or_prefixes(arg, env)
        for v in sorted(vals):
            self.model.add_metric(v, kind, site)
        self.model.metric_prefixes.update(prefixes)

    def _harvest_timeline_record(self, call: ast.Call, env: _Env,
                                 site: Site) -> None:
        arg = _arg(call, 0, "name")
        if arg is None:
            return
        label = _arg(call, 2, "label")
        labeled = label is not None and not (
            isinstance(label, ast.Constant) and label.value in ("", None))
        vals, prefixes = self._names_or_prefixes(arg, env)
        for v in sorted(vals):
            prod = self.model.timeline.setdefault(v, SeriesProd())
            prod.sites.append(site)
            if labeled:
                prod.labeled = True
            else:
                prod.bare = True
        self.model.metric_prefixes.update(prefixes)

    # -- families ----------------------------------------------------------

    def _harvest_family(self, call: ast.Call, env: _Env,
                        site: Site) -> None:
        arg = _arg(call, 0, "name")
        if arg is None:
            return
        names, prefixes = self._names_or_prefixes(arg, env)
        self.model.family_prefixes.update(prefixes)
        if not names:
            return
        unlabeled, label_sets, unknown = self._family_adds(call, env)
        for name in sorted(names):
            fam = self.model.families.setdefault(name, FamilyProd())
            fam.sites.append(site)
            fam.unlabeled |= unlabeled
            fam.unknown_labels |= unknown
            fam.label_sets |= label_sets

    def _family_adds(self, fam_call: ast.Call, env: _Env
                     ) -> Tuple[bool, Set[FrozenSet[str]], bool]:
        """Inspect every ``.add(value, labels)`` reaching this Family
        construction: chained directly, or through the local variable
        it is assigned to within the enclosing function."""
        _symbol, fn_node = _enclosing(self.mod, fam_call.lineno)
        scope: ast.AST = fn_node if fn_node is not None else self.mod.tree
        var_names: Set[str] = set()
        add_calls: List[ast.Call] = []
        for node in ast.walk(scope):
            if isinstance(node, ast.Assign) and node.value is fam_call:
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        var_names.add(tgt.id)
            if isinstance(node, ast.Call) and \
                    isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "add":
                recv = node.func.value
                if recv is fam_call:
                    add_calls.append(node)
                elif isinstance(recv, ast.Call) and recv is fam_call:
                    add_calls.append(node)
        # second walk: adds through the assigned variable(s)
        if var_names:
            for node in ast.walk(scope):
                if isinstance(node, ast.Call) and \
                        isinstance(node.func, ast.Attribute) and \
                        node.func.attr == "add" and \
                        isinstance(node.func.value, ast.Name) and \
                        node.func.value.id in var_names:
                    add_calls.append(node)
        unlabeled, unknown = False, False
        label_sets: Set[FrozenSet[str]] = set()
        for add in add_calls:
            labels = _arg(add, 1, "labels")
            got = self._label_keys(labels, env)
            if got == "unlabeled":
                unlabeled = True
            elif got == "unknown":
                unknown = True
            elif got == "both":
                unlabeled = unknown = True
            else:
                label_sets.add(got)
        if not add_calls:
            unknown = True            # samples= kwarg or external fill
        return unlabeled, label_sets, unknown

    def _label_keys(self, labels: Optional[ast.AST], env: _Env):
        """-> frozenset of label keys, "unlabeled", "both" (conditional
        labels like ``{...} if mode else None``), or "unknown"."""
        if labels is None or (isinstance(labels, ast.Constant)
                              and labels.value is None):
            return "unlabeled"
        if isinstance(labels, ast.IfExp):
            arms = [self._label_keys(labels.body, env),
                    self._label_keys(labels.orelse, env)]
            if "unknown" in arms:
                return "unknown"
            if "unlabeled" in arms or "both" in arms:
                return "both"
            return arms[0]            # two labeled arms: report the first
        if isinstance(labels, ast.Name):
            exprs = env.assigns.get(labels.id, ())
            dicts = [e for e in exprs if isinstance(e, ast.Dict)]
            if len(dicts) == 1:
                return self._label_keys(dicts[0], env)
            return "unknown"
        if isinstance(labels, ast.Dict):
            keys: Set[str] = set()
            for k in labels.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    keys.add(k.value)
                else:
                    return "unknown"
            return frozenset(keys) if keys else "unlabeled"
        return "unknown"

    # -- routes / EXPECTED_ROUTES -----------------------------------------

    def _harvest_routes_and_expected(self) -> None:
        for node in ast.walk(self.mod.tree):
            # `self._routes: Dict[str, _Route] = {...}` is an AnnAssign
            if isinstance(node, ast.AnnAssign) and node.value is not None:
                tgt, value = node.target, node.value
            elif isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt, value = node.targets[0], node.value
            else:
                continue
            tgt_name = tgt.id if isinstance(tgt, ast.Name) else (
                tgt.attr if isinstance(tgt, ast.Attribute) else "")
            symbol, _env = self._env_at(node.lineno)
            if tgt_name == "_routes" and isinstance(value, ast.Dict):
                for k in value.keys:
                    if isinstance(k, ast.Constant) and \
                            isinstance(k.value, str):
                        self.model.routes.setdefault(
                            k.value, self._site(k, symbol))
            if tgt_name == "EXPECTED_ROUTES" and \
                    isinstance(value, (ast.List, ast.Tuple)):
                for elt in value.elts:
                    if isinstance(elt, ast.Constant) and \
                            isinstance(elt.value, str):
                        self.model.expected_routes.setdefault(
                            elt.value, self._site(elt, symbol))
            if tgt_name == "TRIAGE_VERDICTS" and \
                    isinstance(value, (ast.List, ast.Tuple)):
                for elt in value.elts:
                    if isinstance(elt, ast.Constant) and \
                            isinstance(elt.value, str):
                        self.model.verdict_registry.setdefault(
                            elt.value, self._site(elt, symbol))

    # -- verdict classifier returns ---------------------------------------

    def _harvest_verdict_return(self, node: ast.Return) -> None:
        if _QUALMON_MODULE.split(".")[-1] not in self.mod.relpath and \
                not self.mod.relpath.endswith("qualmon.py"):
            return
        symbol, _fn = _enclosing(self.mod, node.lineno)
        if not symbol.startswith("classify_"):
            return
        val = node.value
        if isinstance(val, ast.Tuple) and val.elts:
            head = val.elts[0]
            if isinstance(head, ast.Constant) and \
                    isinstance(head.value, str):
                self.model.verdicts_returned.setdefault(
                    head.value, self._site(head, symbol))


# ---------------------------------------------------------------------------
# cross-tree surfaces
# ---------------------------------------------------------------------------

def _read_surface(project: Project, relpath: str) -> Optional[str]:
    """A cross-tree file's text: a planted in-memory extra source first,
    else the real file under the project's disk root."""
    if relpath in project.extra_sources:
        return project.extra_sources[relpath]
    if project.source_root:
        full = os.path.join(project.source_root, relpath)
        if os.path.isfile(full):
            with open(full, encoding="utf-8") as f:
                return f.read()
    return None


def _harvest_external_module(project: Project, model: ObsModel,
                             relpath: str) -> None:
    text = _read_surface(project, relpath)
    if text is None:
        return
    try:
        mod = ModuleInfo(relpath, text)
    except SyntaxError:
        return
    _ModuleHarvest(mod, model).run()


def _harvest_doc(project: Project, model: ObsModel) -> None:
    text = _read_surface(project, "docs/PARAMETERS.md")
    if text is None:
        return
    model.has_doc = True
    for lineno, line in enumerate(text.splitlines(), 1):
        stripped = line.strip()
        names = _BACKTICK.findall(stripped)
        for name in names:
            if _IDENTISH.match(name):
                model.doc_mentions.add(name)
        if stripped.startswith("|"):
            cells = [c.strip() for c in stripped.strip("|").split("|")]
            if cells and cells[0].startswith("`"):
                for name in _BACKTICK.findall(cells[0]):
                    if _IDENTISH.match(name) and \
                            name not in model.doc_rows:
                        model.doc_rows[name] = lineno


def _harvest_corpus(project: Project, model: ObsModel) -> None:
    """docs/tests/tools text, for the GL1002 "mentioned anywhere"
    check.  The producing package itself is deliberately excluded —
    a name trivially appears at its own call site."""
    chunks: List[str] = [text for path, text in
                         sorted(project.extra_sources.items())]
    root = project.source_root
    if root:
        for sub in ("docs", "tests", "tools"):
            base = os.path.join(root, sub)
            if not os.path.isdir(base):
                continue
            for dirpath, dirnames, filenames in os.walk(base):
                dirnames[:] = [d for d in dirnames if d != "__pycache__"]
                for fname in sorted(filenames):
                    if fname.endswith((".py", ".md", ".sh", ".toml")):
                        try:
                            with open(os.path.join(dirpath, fname),
                                      encoding="utf-8") as f:
                                chunks.append(f.read())
                        except OSError:
                            continue
        for fname in ("README.md", "ROADMAP.md", "CHANGES.md"):
            full = os.path.join(root, fname)
            if os.path.isfile(full):
                with open(full, encoding="utf-8") as f:
                    chunks.append(f.read())
        model.has_corpus = True
    elif project.extra_sources:
        model.has_corpus = True
    model.corpus = "\n".join(chunks)


# ---------------------------------------------------------------------------
# build + checks
# ---------------------------------------------------------------------------

def build_model(project: Project) -> ObsModel:
    cached = project.cache.get(CACHE_KEY)
    if isinstance(cached, ObsModel):
        return cached
    model = ObsModel()
    for mod in project.modules.values():
        _ModuleHarvest(mod, model).run()
    # cross-tree consumer surfaces (disk-backed projects only, unless a
    # fixture plants them): the route-contract test's EXPECTED_ROUTES,
    # the docs, and the GL1002 corpus
    if not any(p.endswith("tests/test_hostprof.py")
               for p in project.modules):
        _harvest_external_module(project, model, "tests/test_hostprof.py")
    _harvest_doc(project, model)
    _harvest_corpus(project, model)
    project.cache[CACHE_KEY] = model
    return model


def _consumed_names(model: ObsModel) -> Set[str]:
    """Every producer name a structured consumer resolves to, with
    timeline derivations folded back onto their base metric."""
    out: Set[str] = set()
    for name, _site in model.timeline_reads:
        out.add(name)
        for suffix in (".rate", ".p50_ms", ".p99_ms"):
            if name.endswith(suffix):
                out.add(name[: -len(suffix)])
    for name, _kind, _site in model.metric_reads:
        out.add(name)
    return out


def _check_series_reads(model: ObsModel) -> List[Finding]:
    out: List[Finding] = []
    bare = model.bare_series()
    labeled_only = model.labeled_only_series()
    seen: Set[Tuple[str, str, int]] = set()
    for name, site in model.timeline_reads:
        key = (name, site.path, site.line)
        if key in seen:
            continue
        seen.add(key)
        if name in bare or model.matches_prefix(name):
            continue
        if name in labeled_only:
            out.append(Finding(
                "GL1003", site.path, site.line,
                f"timeline read of bare series `{name}` but every "
                "producer publishes it labeled — the unlabeled key "
                "never receives a point (publish an unlabeled "
                "aggregate sample or read the labeled key)",
                site.symbol))
            continue
        out.append(Finding(
            "GL1001", site.path, site.line,
            f"timeline series `{name}` is consumed here but no "
            "producer publishes it (no matching timeline.record, "
            "counter/gauge/histogram derivation, or family sample)",
            site.symbol))
    for name, kind, site in model.metric_reads:
        kinds = model.metric_kinds(name)
        if kind in kinds or model.matches_prefix(name):
            continue
        if kinds:
            out.append(Finding(
                "GL1001", site.path, site.line,
                f"metric `{name}` is read as a {kind} but only "
                f"published as {'/'.join(sorted(kinds))} — the read "
                "resolves to a different instrument", site.symbol))
        else:
            out.append(Finding(
                "GL1001", site.path, site.line,
                f"metric `{name}` is read here but never published "
                "by any registry producer", site.symbol))
    return out


def _check_family_labels(model: ObsModel) -> List[Finding]:
    out: List[Finding] = []
    for name, fam in sorted(model.families.items()):
        if len(fam.label_sets) > 1:
            sets = " vs ".join(
                "{%s}" % ",".join(sorted(s))
                for s in sorted(fam.label_sets, key=sorted))
            site = fam.sites[0]
            out.append(Finding(
                "GL1003", site.path, site.line,
                f"family `{name}` is published with conflicting "
                f"label-key sets ({sets}) — consumers keying on one "
                "set silently miss samples from the other",
                site.symbol))
    return out


def _mentioned(name: str, corpus: str) -> bool:
    """Does the corpus mention `name` — either verbatim or in its
    Prometheus-rendered form (tests scrape /metrics, where `x.y` is
    exposed as `sptag_tpu_x_y`; see utils/metrics._metric_name)?"""
    if name in corpus:
        return True
    prom = "sptag_tpu_" + re.sub(r"[^0-9A-Za-z_]", "_", name)
    return prom in corpus


def _check_unconsumed(model: ObsModel) -> List[Finding]:
    if not model.has_corpus:
        corpus = ""
    else:
        corpus = model.corpus
    consumed = _consumed_names(model)
    out: List[Finding] = []
    for name, sites in sorted(model.all_published().items()):
        if name in consumed:
            continue
        if corpus and _mentioned(name, corpus):
            continue
        site = sites[0]
        out.append(Finding(
            "GL1002", site.path, site.line,
            f"`{name}` is published but never consumed by a "
            "structured reader and never mentioned in docs/tests/"
            "tools — document it, consume it, or justify it in the "
            "baseline", site.symbol))
    for name, site in sorted(model.verdict_registry.items()):
        if name not in model.verdicts_returned:
            out.append(Finding(
                "GL1002", site.path, site.line,
                f"triage verdict `{name}` is declared in "
                "TRIAGE_VERDICTS but no classifier returns it",
                site.symbol))
    return out


def _check_verdicts(model: ObsModel) -> List[Finding]:
    if not model.verdict_registry:
        return []
    out: List[Finding] = []
    for name, site in sorted(model.verdicts_returned.items()):
        if name not in model.verdict_registry:
            out.append(Finding(
                "GL1001", site.path, site.line,
                f"triage verdict `{name}` is produced here but absent "
                "from qualmon.TRIAGE_VERDICTS — dashboards and tests "
                "keying on the registry never see it", site.symbol))
    return out


def _check_docs(model: ObsModel) -> List[Finding]:
    if not model.has_doc:
        return []
    out: List[Finding] = []
    for name, site in sorted(model.param_specs.items()):
        if name not in model.doc_mentions:
            out.append(Finding(
                "GL1004", site.path, site.line,
                f"param spec `{name}` has no docs/PARAMETERS.md row",
                site.symbol))
    for name, (scope, site) in sorted(model.actuations.items()):
        if name not in model.doc_mentions:
            out.append(Finding(
                "GL1004", site.path, site.line,
                f"live actuation `{name}` ({scope}-scoped) has no "
                "docs/PARAMETERS.md row", site.symbol))
    known = set(model.param_specs) | set(model.actuations)
    ini_keys = {key for _sec, key, _site in model.ini_reads}
    for name, lineno in sorted(model.doc_rows.items()):
        if name not in known and name not in ini_keys:
            out.append(Finding(
                "GL1004", "docs/PARAMETERS.md", lineno,
                f"documented row `{name}` names no param spec, live "
                "actuation, or parsed INI key — stale doc row"))
    doc_sections = {"Service", "Aggregator", "QueryConfig"}
    seen: Set[str] = set()
    for sec, key, site in sorted(model.ini_reads):
        if sec not in doc_sections or key in seen:
            continue
        seen.add(key)
        if key not in model.doc_mentions:
            out.append(Finding(
                "GL1004", site.path, site.line,
                f"INI key [{sec}] {key} is parsed here but "
                "docs/PARAMETERS.md never documents it", site.symbol))
    return out


def _check_param_uses(model: ObsModel) -> List[Finding]:
    if not model.param_specs and not model.actuations:
        return []
    known = {n.lower() for n in model.param_specs}
    known |= {n.lower() for n in model.actuations}
    out: List[Finding] = []
    seen: Set[Tuple[str, str, int]] = set()
    for name, site in model.param_uses:
        key = (name, site.path, site.line)
        if key in seen:
            continue
        seen.add(key)
        if name.lower() not in known:
            out.append(Finding(
                "GL1005", site.path, site.line,
                f"param name `{name}` has no backing ParamSpec or "
                "live-actuation entry — set_parameter would reject it "
                "(or silently no-op)", site.symbol))
    for name, (scope, site) in sorted(model.actuations.items()):
        if scope == "index" and \
                name.lower() not in {n.lower()
                                     for n in model.param_specs}:
            out.append(Finding(
                "GL1005", site.path, site.line,
                f"index-scoped live actuation `{name}` matches no "
                "ParamSpec — actuate_index would raise at apply time",
                site.symbol))
    return out


def _check_routes(model: ObsModel) -> List[Finding]:
    if not model.routes or not model.expected_routes:
        return []
    out: List[Finding] = []
    for path, site in sorted(model.routes.items()):
        if path not in model.expected_routes:
            out.append(Finding(
                "GL1006", site.path, site.line,
                f"route `{path}` is registered but absent from "
                "EXPECTED_ROUTES — the route-contract tests skip it",
                site.symbol))
    for path, site in sorted(model.expected_routes.items()):
        if path not in model.routes:
            out.append(Finding(
                "GL1006", site.path, site.line,
                f"EXPECTED_ROUTES lists `{path}` but no handler "
                "registers it", site.symbol))
    return out


def _covers_package(project: Project) -> bool:
    """The contract graph is a WHOLE-package analysis: producers and
    consumers live in different subpackages (slo.py reads series that
    qualmon publishes; docs rows name specs from core/params.py), so a
    subpackage-scoped lint of a disk tree would report phantom
    GL1001/1002/1004s for every cross-subpackage edge.  Disk-backed
    projects run the pass only when the anchor modules of both halves
    were parsed; in-memory fixture projects are self-contained and
    always run."""
    if project.source_root is None:
        return True
    has_metrics = any(p.endswith("utils/metrics.py")
                      for p in project.modules)
    has_params = any(p.endswith("core/params.py")
                     for p in project.modules)
    return has_metrics and has_params


def check(project: Project) -> List[Finding]:
    if not _covers_package(project):
        return []
    model = build_model(project)
    out: List[Finding] = []
    out.extend(_check_series_reads(model))
    out.extend(_check_family_labels(model))
    out.extend(_check_verdicts(model))
    out.extend(_check_unconsumed(model))
    out.extend(_check_docs(model))
    out.extend(_check_param_uses(model))
    out.extend(_check_routes(model))
    return out
