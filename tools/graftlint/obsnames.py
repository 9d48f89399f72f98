"""GL6xx — observability-name lint (metric-cardinality bound).

The telemetry registry (utils/metrics.py) keys series directly off their
names and never expires one: a span/counter/histogram name interpolated
from runtime values (an f-string, concatenation, %-format, .format, a
per-call variable) mints a fresh series per distinct value — unbounded
registry growth in a long-lived server, and every Prometheus scrape
re-serializes all of it.  Names must therefore be STRING LITERALS at the
call site; module-level `NAME = "..."` constants are accepted too (their
value set is bounded by definition).

Rules:

* GL601 — the name argument of `trace.span(...)` / `trace.record(...)` /
  `trace.record_sum(...)` is not a string literal or module-level string constant.
* GL602 — the name argument of a metrics-registry call
  (`metrics.counter/gauge/histogram/inc/set_gauge/observe/
  counter_value/histogram_or_none`) is not a string literal or
  module-level string constant.
* GL603 — the `kind` argument of a flight-recorder call
  (`flightrec.record(tier, kind, ...)` / `flightrec.span(tier, kind,
  ...)`) is not a string literal or module-level string constant: the
  Chrome-trace export keys tracks off the kind and the ring never
  expires a name, so kinds are a bounded taxonomy by the same
  cardinality argument as GL601/602.
* GL606 — the name argument of a quality-monitor series call
  (`qualmon.gauge(name, ...)` / `qualmon.inc(name, ...)`) is not a
  string literal or module-level string constant: the labeled quality
  exposition keys series off the name and the windows never expire
  one.  The `mode`/`shard` LABELS are out of scope — they are bounded
  by deployment (search modes are an enum, shards come from the
  service config), exactly like flightrec's tier argument.
* GL607 — the stage argument of a host-profiler pin
  (`hostprof.set_stage(stage, ...)` / `hostprof.stage(stage, ...)`) is
  not a string literal or module-level string constant: the folded-
  stack aggregate injects a synthetic ``stage:<name>`` frame per
  sample and the per-stage counters never expire a name, so stages
  are a bounded taxonomy (decode/queue/execute/encode/merge) by the
  same cardinality argument.  The `rid` argument is out of scope —
  rid attribution is a bounded LRU by design.
* GL608 — the name argument of a timeline series record
  (`timeline.record(name, value, ...)`) is not a string literal or
  module-level string constant: the time-series store keys fixed-size
  rings off the name and never expires one, so the series taxonomy
  (timeline/SLO/canary series alike — the SLO engine and canary
  prober both publish through this call) must be bounded.  The
  `label` argument is out of scope — labels are deployment-bounded
  (index names, objective names), the qualmon shard-label rationale.
* GL609 — the rule argument of a controller decision-audit record
  (`ctlaudit.record(rule, ...)`) is not a string literal or
  module-level string constant: the audit ring is the control plane's
  accountability surface — dashboards and the acceptance drill key off
  rule names, the ring counts decisions per rule, and a dynamic rule
  name would make the decision taxonomy (burn_step_down /
  revert_on_worse / canary_floor_veto / ...) unsearchable.  The `knob`
  argument is out of scope — knob names come from the core/params
  live-actuation registry, bounded by deployment like flightrec's
  tier.

Calls are resolved through import aliases (`from sptag_tpu.utils import
trace` / `import sptag_tpu.utils.metrics as metrics` / from-imports of the
functions themselves), so the modules' own internal plumbing that passes a
`name` PARAMETER through is out of scope by construction — the lint
surface is the call sites that choose the name.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from tools.graftlint.core import Finding, ModuleInfo, Project, _dotted

RULES = {
    "GL601": "trace span/record name is not a string literal — dynamic "
             "names make metric cardinality unbounded",
    "GL602": "metrics registry name is not a string literal — dynamic "
             "names make metric cardinality unbounded",
    "GL603": "flight-recorder event kind is not a string literal — "
             "dynamic kinds make the event taxonomy unbounded",
    "GL606": "quality-monitor series name is not a string literal — "
             "dynamic names make the quality exposition unbounded",
    "GL607": "host-profiler stage name is not a string literal — "
             "dynamic stages make the folded-stack taxonomy unbounded",
    "GL608": "timeline series name is not a string literal — dynamic "
             "names make the time-series store unbounded",
    "GL609": "controller audit rule name is not a string literal — "
             "dynamic rule names make the decision taxonomy unbounded",
}

_TRACE_MODULE = "sptag_tpu.utils.trace"
_METRICS_MODULE = "sptag_tpu.utils.metrics"
_FLIGHT_MODULE = "sptag_tpu.utils.flightrec"
_QUALMON_MODULE = "sptag_tpu.utils.qualmon"
_HOSTPROF_MODULE = "sptag_tpu.utils.hostprof"
_TIMELINE_MODULE = "sptag_tpu.utils.timeline"
_CTLAUDIT_MODULE = "sptag_tpu.serve.ctlaudit"

_TRACE_FNS = {"span", "record", "record_sum"}
_METRICS_FNS = {"counter", "gauge", "histogram", "inc", "set_gauge",
                "observe", "counter_value", "histogram_or_none"}
_FLIGHT_FNS = {"record", "span"}
_QUALMON_FNS = {"gauge", "inc"}
_HOSTPROF_FNS = {"set_stage", "stage"}
_TIMELINE_FNS = {"record"}
_CTLAUDIT_FNS = {"record"}

#: per-rule (positional index, keyword name) of the argument that must
#: be a bounded string — GL60x's lint surface
_NAME_ARG = {"GL601": (0, "name"), "GL602": (0, "name"),
             "GL603": (1, "kind"), "GL606": (0, "name"),
             "GL607": (0, "stage"), "GL608": (0, "name"),
             "GL609": (0, "rule")}


def _module_str_constants(mod: ModuleInfo) -> Set[str]:
    """Names bound at module level to a string constant (e.g.
    `TRACE_SPAN = "xla.backend_compile"`) — bounded by definition."""
    out: Set[str] = set()
    for node in mod.tree.body:
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Constant) and \
                isinstance(node.value.value, str):
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    out.add(tgt.id)
    return out


def _rule_for_call(call: ast.Call, mod: ModuleInfo) -> Optional[str]:
    """GL601/GL602 when this call targets the trace/metrics registries
    (resolved through the module's import aliases), else None."""
    func = call.func
    if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
        full = mod.resolve_head(func.value.id)
        if full == _TRACE_MODULE and func.attr in _TRACE_FNS:
            return "GL601"
        if full == _METRICS_MODULE and func.attr in _METRICS_FNS:
            return "GL602"
        if full == _FLIGHT_MODULE and func.attr in _FLIGHT_FNS:
            return "GL603"
        if full == _QUALMON_MODULE and func.attr in _QUALMON_FNS:
            return "GL606"
        if full == _HOSTPROF_MODULE and func.attr in _HOSTPROF_FNS:
            return "GL607"
        if full == _TIMELINE_MODULE and func.attr in _TIMELINE_FNS:
            return "GL608"
        if full == _CTLAUDIT_MODULE and func.attr in _CTLAUDIT_FNS:
            return "GL609"
        return None
    if isinstance(func, ast.Name):
        target = mod.from_imports.get(func.id, "")
        modpath, _, sym = target.rpartition(".")
        if modpath == _TRACE_MODULE and sym in _TRACE_FNS:
            return "GL601"
        if modpath == _METRICS_MODULE and sym in _METRICS_FNS:
            return "GL602"
        if modpath == _FLIGHT_MODULE and sym in _FLIGHT_FNS:
            return "GL603"
        if modpath == _QUALMON_MODULE and sym in _QUALMON_FNS:
            return "GL606"
        if modpath == _HOSTPROF_MODULE and sym in _HOSTPROF_FNS:
            return "GL607"
        if modpath == _TIMELINE_MODULE and sym in _TIMELINE_FNS:
            return "GL608"
        if modpath == _CTLAUDIT_MODULE and sym in _CTLAUDIT_FNS:
            return "GL609"
    return None


def _name_arg(call: ast.Call, rule: str) -> Optional[ast.AST]:
    pos, kwname = _NAME_ARG[rule]
    if len(call.args) > pos:
        return call.args[pos]
    for kw in call.keywords:
        if kw.arg == kwname:
            return kw.value
    return None


def _is_bounded(arg: ast.AST, constants: Set[str]) -> bool:
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return True
    return isinstance(arg, ast.Name) and arg.id in constants


def _describe(arg: ast.AST) -> str:
    if isinstance(arg, ast.JoinedStr):
        return "an f-string"
    if isinstance(arg, ast.BinOp):
        return "a concatenation/format expression"
    if isinstance(arg, ast.Call):
        return "a call result"
    if isinstance(arg, ast.Name):
        return f"the variable `{arg.id}`"
    return "a dynamic expression"


def _check_module(mod: ModuleInfo) -> List[Finding]:
    out: List[Finding] = []
    constants = _module_str_constants(mod)

    def enclosing(lineno: int) -> str:
        best, best_line = "", -1
        for fn in mod.functions:
            end = getattr(fn.node, "end_lineno", fn.node.lineno)
            if fn.node.lineno <= lineno <= end and \
                    fn.node.lineno > best_line:
                best, best_line = fn.qualname, fn.node.lineno
        return best

    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        rule = _rule_for_call(node, mod)
        if rule is None:
            continue
        arg = _name_arg(node, rule)
        if arg is None or _is_bounded(arg, constants):
            continue
        fn_name = _dotted(node.func) or "<call>"
        what = ("kind" if rule == "GL603"
                else "stage" if rule == "GL607"
                else "rule" if rule == "GL609" else "name")
        out.append(Finding(
            rule, mod.relpath, node.lineno,
            f"`{fn_name}` {what} is {_describe(arg)} — use a string "
            "literal (or a module-level str constant) so metric "
            "cardinality stays bounded", enclosing(node.lineno)))
    return out


def check(project: Project) -> List[Finding]:
    out: List[Finding] = []
    for mod in project.modules.values():
        out.extend(_check_module(mod))
    return out
