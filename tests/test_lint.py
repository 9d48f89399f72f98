"""graftlint tier-1 gate + per-rule unit tests.

Two jobs:

1. every lint rule has a positive-detection test (a snippet that MUST be
   flagged) and a clean-pass test (idiomatic code that must NOT be);
2. the repo itself stays lint-clean: `lint_project(sptag_tpu/)` under the
   shipped baseline yields ZERO unsuppressed findings, every baseline
   entry is justified (the loader enforces it), and no baseline entry is
   stale.  A new finding fails tier-1 here, not rounds later as a bench
   regression.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.graftlint.baseline import (BaselineError, apply_baseline,  # noqa: E402
                                      parse_baseline)
from tools.graftlint.runner import (ALL_RULES, DEFAULT_BASELINE,  # noqa: E402
                                    lint_project, lint_sources)


def rules_of(findings):
    return sorted({f.rule for f in findings})


def lint_one(src, path="sptag_tpu/algo/snippet.py", select=None):
    return lint_sources({path: src}, select=select)


# ---------------------------------------------------------------------------
# GL1xx host-sync
# ---------------------------------------------------------------------------

def test_gl101_item_in_jitted_function_flagged():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.sum().item()\n"
    )
    found = lint_one(src, select=["GL101"])
    assert rules_of(found) == ["GL101"]
    assert found[0].symbol == "f"


def test_gl101_item_outside_jit_clean():
    src = (
        "import numpy as np\n"
        "def host_summary(x):\n"
        "    return x.sum().item()\n"
    )
    assert lint_one(src, select=["GL101"]) == []


def test_gl101_reaches_through_the_call_graph():
    """A helper called FROM a jitted kernel is on the hot path too."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def helper(x):\n"
        "    return x.max().item()\n"
        "@jax.jit\n"
        "def kernel(x):\n"
        "    return helper(x)\n"
    )
    found = lint_one(src, select=["GL101"])
    assert [f.symbol for f in found] == ["helper"]


def test_gl102_float_on_traced_value_flagged():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    s = jnp.sum(x)\n"
        "    return float(s)\n"
    )
    assert rules_of(lint_one(src, select=["GL102"])) == ["GL102"]


def test_gl102_static_arg_and_shape_casts_clean():
    src = (
        "import functools\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@functools.partial(jax.jit, static_argnames=('k',))\n"
        "def f(x, k: int):\n"
        "    n = float(x.shape[0])\n"
        "    return jnp.sum(x) * n * int(k)\n"
    )
    assert lint_one(src, select=["GL102"]) == []


def test_gl103_np_asarray_in_jitted_function_flagged():
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return np.asarray(x).sum()\n"
    )
    assert rules_of(lint_one(src, select=["GL103"])) == ["GL103"]


def test_gl103_np_outside_jit_clean():
    src = (
        "import numpy as np\n"
        "def prepare(x):\n"
        "    return np.asarray(x, dtype=np.float32)\n"
    )
    assert lint_one(src, select=["GL103"]) == []


def test_gl104_branch_on_traced_value_flagged():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    s = jnp.sum(x)\n"
        "    if s > 0:\n"
        "        return s\n"
        "    return -s\n"
    )
    assert rules_of(lint_one(src, select=["GL104"])) == ["GL104"]


def test_gl104_static_branches_clean():
    """`is None` checks, `.shape`/`.dtype` comparisons and jnp metadata
    queries (issubdtype) are host-decidable — no finding."""
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x, sq=None):\n"
        "    if sq is None:\n"
        "        sq = jnp.zeros(x.shape[0])\n"
        "    flag = jnp.issubdtype(x.dtype, jnp.floating)\n"
        "    if flag and x.ndim == 2:\n"
        "        return jnp.sum(x) + sq\n"
        "    return sq\n"
    )
    assert lint_one(src, select=["GL104"]) == []


# ---------------------------------------------------------------------------
# GL2xx retrace
# ---------------------------------------------------------------------------

def test_gl201_scalar_param_not_static_flagged():
    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, static_argnames=('k',))\n"
        "def f(x, k: int, width: int):\n"
        "    return x[:width] * k\n"
    )
    found = lint_one(src, select=["GL201"])
    assert rules_of(found) == ["GL201"]
    assert "width" in found[0].message


def test_gl201_all_scalars_static_clean():
    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, static_argnames=('k', 'width'))\n"
        "def f(x, k: int, width: int):\n"
        "    return x[:width] * k\n"
    )
    assert lint_one(src, select=["GL201"]) == []


def test_gl201_segment_kernel_budget_discipline():
    """ISSUE 4: the segmented walk's compile-key contract — iteration
    BUDGETS ride as traced arrays (t_limit) while only shape-defining
    ints (L, B, S) are static, so mixed-MaxCheck slot pools share one
    compiled program.  A budget demoted to a plain scalar param is
    exactly the recompile-per-value hazard GL201 exists for; this pins
    both directions so the kernel shape buckets stay retrace-clean."""
    clean = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit,"
        " static_argnames=('L', 'B', 'S'))\n"
        "def segment(state, t_limit, L: int, B: int, S: int):\n"
        "    return state\n"
    )
    assert lint_one(clean, select=["GL201"]) == []
    hazard = clean.replace("('L', 'B', 'S')", "('L', 'B')")
    found = lint_one(hazard, select=["GL201"])
    assert rules_of(found) == ["GL201"]
    assert "S" in found[0].message


def test_gl202_fstring_in_jitted_body_flagged():
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    name = f'size-{x.shape[0]}'\n"
        "    return x\n"
    )
    assert rules_of(lint_one(src, select=["GL202"])) == ["GL202"]


def test_gl202_fstring_outside_jit_clean():
    src = (
        "def describe(x):\n"
        "    return f'size-{x.shape[0]}'\n"
    )
    assert lint_one(src, select=["GL202"]) == []


def test_gl203_shape_branch_in_jitted_body_flagged():
    src = (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    if x.shape[0] > 128:\n"
        "        return jnp.sum(x)\n"
        "    return jnp.max(x)\n"
    )
    assert rules_of(lint_one(src, select=["GL203"])) == ["GL203"]


def test_gl203_shape_branch_on_host_clean():
    src = (
        "def dispatch(x):\n"
        "    if x.shape[0] > 128:\n"
        "        return 'big'\n"
        "    return 'small'\n"
    )
    assert lint_one(src, select=["GL203"]) == []


# ---------------------------------------------------------------------------
# GL3xx concurrency
# ---------------------------------------------------------------------------

_GL301_POSITIVE = (
    "import threading\n"
    "class Worker:\n"
    "    def __init__(self):\n"
    "        self._lock = threading.Lock()\n"
    "        self._state = 0\n"
    "    def start(self):\n"
    "        threading.Thread(target=self._run, daemon=True).start()\n"
    "    def set_state(self, v):\n"
    "        with self._lock:\n"
    "            self._state = v\n"
    "    def _run(self):\n"
    "        self._state = 1\n"
)


def test_gl301_unlocked_mutation_on_thread_path_flagged():
    found = lint_one(_GL301_POSITIVE, select=["GL301"])
    assert rules_of(found) == ["GL301"]
    assert found[0].symbol == "Worker._run"


def test_gl301_locked_mutation_clean():
    src = _GL301_POSITIVE.replace(
        "    def _run(self):\n        self._state = 1\n",
        "    def _run(self):\n        with self._lock:\n"
        "            self._state = 1\n")
    assert lint_one(src, select=["GL301"]) == []


def test_gl302_late_binding_capture_flagged():
    src = (
        "def fan_out(pool, items, work):\n"
        "    for item in items:\n"
        "        pool.add(lambda: work(item))\n"
    )
    found = lint_one(src, select=["GL302"])
    assert rules_of(found) == ["GL302"]
    assert "item" in found[0].message


def test_gl302_default_bound_capture_clean():
    src = (
        "def fan_out(pool, items, work):\n"
        "    for item in items:\n"
        "        pool.add(lambda item=item: work(item))\n"
    )
    assert lint_one(src, select=["GL302"]) == []


# ---------------------------------------------------------------------------
# GL4xx error-path (scoped to serve/ and core/)
# ---------------------------------------------------------------------------

def test_gl401_bare_except_flagged():
    src = (
        "def recv(sock):\n"
        "    try:\n"
        "        return sock.read()\n"
        "    except:\n"
        "        pass\n"
    )
    found = lint_one(src, path="sptag_tpu/serve/snippet.py",
                     select=["GL401"])
    assert rules_of(found) == ["GL401"]


def test_gl401_typed_except_clean():
    src = (
        "def recv(sock):\n"
        "    try:\n"
        "        return sock.read()\n"
        "    except OSError:\n"
        "        raise\n"
    )
    assert lint_one(src, path="sptag_tpu/serve/snippet.py",
                    select=["GL401"]) == []


def test_gl402_swallowed_exception_flagged():
    src = (
        "def load(path):\n"
        "    try:\n"
        "        return open(path).read()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    found = lint_one(src, path="sptag_tpu/core/snippet.py",
                     select=["GL402"])
    assert rules_of(found) == ["GL402"]


def test_gl402_handled_exceptions_clean():
    """Logging, ErrorCode conversion, cleanup calls, retry control flow
    and state transitions all count as handling the failure."""
    src = (
        "import logging\n"
        "log = logging.getLogger(__name__)\n"
        "def load(index, path):\n"
        "    try:\n"
        "        return open(path).read()\n"
        "    except FileNotFoundError:\n"
        "        return ErrorCode.FailedOpenFile\n"
        "    except OSError:\n"
        "        log.exception('load failed')\n"
        "def pump(self, sock):\n"
        "    while True:\n"
        "        try:\n"
        "            sock.send(b'hb')\n"
        "        except OSError:\n"
        "            self._sock = None\n"
        "            break\n"
    )
    assert lint_one(src, path="sptag_tpu/serve/snippet.py",
                    select=["GL402"]) == []


def test_gl402_out_of_scope_module_clean():
    """The error-path rules are an ErrorCode-boundary contract — kernels
    and tools keep their idioms."""
    src = (
        "def load(path):\n"
        "    try:\n"
        "        return open(path).read()\n"
        "    except Exception:\n"
        "        pass\n"
    )
    assert lint_one(src, path="sptag_tpu/ops/snippet.py",
                    select=["GL402"]) == []


# ---------------------------------------------------------------------------
# GL5xx dtype parity (scoped to ops/)
# ---------------------------------------------------------------------------

def test_gl501_f32_upcast_before_dot_flagged():
    src = (
        "import jax.numpy as jnp\n"
        "def int8_scores(q, x):\n"
        "    assert q.dtype == jnp.int8\n"
        "    qf = q.astype(jnp.float32)\n"
        "    return jnp.dot(qf, x.astype(jnp.float32).T)\n"
    )
    found = lint_one(src, path="sptag_tpu/ops/snippet.py",
                     select=["GL501"])
    assert rules_of(found) == ["GL501"]


def test_gl501_int32_accumulating_dot_clean():
    """The exact idiom: int32-accumulating contraction, upcast AFTER."""
    src = (
        "import jax.numpy as jnp\n"
        "def int8_scores(q, x):\n"
        "    assert q.dtype == jnp.int8\n"
        "    dot = jnp.dot(q.astype(jnp.int32), x.astype(jnp.int32).T,\n"
        "                  preferred_element_type=jnp.int32)\n"
        "    return dot.astype(jnp.float32)\n"
    )
    assert lint_one(src, path="sptag_tpu/ops/snippet.py",
                    select=["GL501"]) == []


# ---------------------------------------------------------------------------
# GL6xx observability names (metric-cardinality bound)
# ---------------------------------------------------------------------------

def test_gl601_dynamic_span_name_flagged():
    src = (
        "from sptag_tpu.utils import trace\n"
        "def serve_one(index_name, q):\n"
        "    with trace.span(f'serve.{index_name}'):\n"
        "        return q\n"
        "def record_it(stage, dt):\n"
        "    trace.record('stage.' + stage, dt)\n"
    )
    found = lint_one(src, select=["GL601"])
    assert rules_of(found) == ["GL601"]
    assert len(found) == 2
    assert found[0].symbol == "serve_one"


def test_gl601_literal_and_module_constant_clean():
    src = (
        "from sptag_tpu.utils import trace\n"
        "SPAN = 'serve.execute'\n"
        "def serve_one(q):\n"
        "    with trace.span('serve.decode'):\n"
        "        pass\n"
        "    trace.record(SPAN, 0.5)\n"
        "    return q\n"
    )
    assert lint_one(src, select=["GL601"]) == []


def test_gl601_out_of_family_trace_calls_clean():
    """Only span/record carry names; report()/reset() and unrelated
    modules that happen to bind the name `trace` stay out of scope."""
    src = (
        "from sptag_tpu.utils import trace\n"
        "import contextlib as trace2\n"
        "def done(tag):\n"
        "    trace.report()\n"
        "    trace2.suppress(tag)\n"
    )
    assert lint_one(src, select=["GL601", "GL602"]) == []


def test_gl602_dynamic_metrics_name_flagged():
    src = (
        "from sptag_tpu.utils import metrics\n"
        "def count(kind):\n"
        "    metrics.inc('server.%s' % kind)\n"
        "    metrics.histogram(kind).observe(0.1)\n"
    )
    found = lint_one(src, select=["GL602"])
    assert rules_of(found) == ["GL602"]
    assert len(found) == 2
    assert "string literal" in found[0].message


def test_gl602_literal_and_from_import_forms():
    """Literals pass; the from-imported function form is resolved too."""
    clean = (
        "from sptag_tpu.utils import metrics\n"
        "def count():\n"
        "    metrics.inc('server.requests')\n"
        "    metrics.set_gauge('server.queue_depth', 3)\n"
    )
    assert lint_one(clean, select=["GL602"]) == []
    dirty = (
        "from sptag_tpu.utils.metrics import observe\n"
        "def time_it(name, dt):\n"
        "    observe(name, dt)\n"
    )
    assert rules_of(lint_one(dirty, select=["GL602"])) == ["GL602"]


def test_gl603_dynamic_flight_kind_flagged():
    """Flight-event `kind` strings are the cardinality-bounded surface
    (the export keys tracks off them): f-strings, concatenation and
    per-call variables are flagged like GL601/602 names."""
    src = (
        "from sptag_tpu.utils import flightrec\n"
        "def stage(name, rid):\n"
        "    flightrec.record('server', f'stage.{name}', rid)\n"
        "    with flightrec.span('server', name, rid):\n"
        "        pass\n"
    )
    found = lint_one(src, select=["GL603"])
    assert rules_of(found) == ["GL603"]
    assert len(found) == 2
    assert "kind" in found[0].message


def test_gl603_literal_kind_and_dynamic_tier_clean():
    """Literal / module-constant kinds pass; the TIER argument and
    payload values are out of scope (a per-instance tier label like
    server_a is a deployment choice, not unbounded cardinality), as are
    the keyword form and the from-import form with literals."""
    src = (
        "from sptag_tpu.utils import flightrec\n"
        "from sptag_tpu.utils.flightrec import record\n"
        "KIND = 'segment_device'\n"
        "def stage(tier, rid, n):\n"
        "    flightrec.record(tier, 'decode', rid)\n"
        "    flightrec.record(tier, KIND, rid, payload={'n': n})\n"
        "    record(tier, kind='retire', rid=rid)\n"
    )
    assert lint_one(src, select=["GL603"]) == []
    dirty = (
        "from sptag_tpu.utils.flightrec import record\n"
        "def stage(tier, kind, rid):\n"
        "    record(tier, kind, rid)\n"
    )
    assert rules_of(lint_one(dirty, select=["GL603"])) == ["GL603"]


def test_gl606_dynamic_quality_name_flagged():
    """Quality-monitor series names are the cardinality-bounded surface
    (ISSUE 7): the labeled exposition keys series off them and the
    windows never expire a name — f-strings, concatenation and per-call
    variables are flagged like GL601/602/603."""
    src = (
        "from sptag_tpu.utils import qualmon\n"
        "def publish(component, value):\n"
        "    qualmon.gauge(f'graph.{component}', value)\n"
        "def count(kind):\n"
        "    qualmon.inc('health_' + kind)\n"
    )
    found = lint_one(src, select=["GL606"])
    assert rules_of(found) == ["GL606"]
    assert len(found) == 2
    assert "string literal" in found[0].message


def test_gl606_literal_name_and_dynamic_labels_clean():
    """Literal / module-constant names pass; the mode/shard LABELS are
    out of scope (bounded by deployment — the flightrec tier argument
    rationale), as are keyword and from-import forms with literals."""
    src = (
        "from sptag_tpu.utils import qualmon\n"
        "from sptag_tpu.utils.qualmon import inc\n"
        "NAME = 'graph.reachable_fraction'\n"
        "def publish(shard, mode, value):\n"
        "    qualmon.gauge('graph.mean_degree', value, shard=shard)\n"
        "    qualmon.gauge(NAME, value, mode=mode, shard=shard)\n"
        "    inc(name='health_errors')\n"
    )
    assert lint_one(src, select=["GL606"]) == []
    dirty = (
        "from sptag_tpu.utils.qualmon import gauge\n"
        "def publish(name, value):\n"
        "    gauge(name, value)\n"
    )
    assert rules_of(lint_one(dirty, select=["GL606"])) == ["GL606"]


def test_issue8_overload_defense_names_are_literals():
    """ISSUE 8 CI satellite: GL601/602/603 coverage extends to the
    overload-defense modules — every metric and flight-event name in
    serve/admission.py, utils/faultinject.py and the serve files they
    wired into is a string literal, with NO new baseline entries (the
    files lint clean with no baseline applied at all)."""
    paths = [
        "sptag_tpu/serve/admission.py",
        "sptag_tpu/utils/faultinject.py",
        "sptag_tpu/serve/server.py",
        "sptag_tpu/serve/aggregator.py",
        "sptag_tpu/serve/client.py",
        "sptag_tpu/serve/wire.py",
    ]
    srcs = {}
    for p in paths:
        with open(os.path.join(REPO, p), encoding="utf-8") as fh:
            srcs[p] = fh.read()
    found = lint_sources(srcs, select=["GL601", "GL602", "GL603"])
    assert found == [], "\n".join(f.format() for f in found)


def test_gl607_dynamic_stage_flagged():
    """Host-profiler stage names are cardinality-bounded (ISSUE 10):
    the folded-stack aggregate injects a synthetic stage frame per
    sample and never expires one — f-strings, concatenation and
    per-call variables are flagged like the rest of the GL6xx family,
    for both set_stage and the context-manager form."""
    src = (
        "from sptag_tpu.utils import hostprof\n"
        "def pin(phase, rid):\n"
        "    hostprof.set_stage(f'stage_{phase}', rid)\n"
        "def pin2(phase):\n"
        "    with hostprof.stage('pre_' + phase):\n"
        "        pass\n"
    )
    found = lint_one(src, select=["GL607"])
    assert rules_of(found) == ["GL607"]
    assert len(found) == 2
    assert "string literal" in found[0].message


def test_gl607_literal_stage_and_dynamic_rid_clean():
    """Literal / module-constant stages pass; the rid argument is out
    of scope (bounded LRU by design), as are keyword and from-import
    forms with literals."""
    src = (
        "from sptag_tpu.utils import hostprof\n"
        "from sptag_tpu.utils.hostprof import set_stage\n"
        "STAGE = 'execute'\n"
        "def pin(rid):\n"
        "    hostprof.set_stage('decode', rid)\n"
        "    hostprof.set_stage(STAGE, rid)\n"
        "    set_stage(stage='encode', rid=rid)\n"
        "    with hostprof.stage('merge', rid):\n"
        "        pass\n"
    )
    assert lint_one(src, select=["GL607"]) == []
    dirty = (
        "from sptag_tpu.utils.hostprof import set_stage\n"
        "def pin(name):\n"
        "    set_stage(name)\n"
    )
    assert rules_of(lint_one(dirty, select=["GL607"])) == ["GL607"]


def test_gl607_out_of_family_hostprof_calls_clean():
    """Only set_stage/stage carry stage names; clear_stage, start,
    configure and unrelated modules binding `hostprof` stay out of
    scope."""
    src = (
        "from sptag_tpu.utils import hostprof\n"
        "import contextlib as hostprof2\n"
        "def lifecycle(hz, why):\n"
        "    hostprof.configure(hz=hz)\n"
        "    hostprof.start(hz)\n"
        "    hostprof.clear_stage()\n"
        "    hostprof2.suppress(why)\n"
    )
    assert lint_one(src, select=["GL607"]) == []


def test_issue10_hostprof_wiring_names_are_literals():
    """ISSUE 10 CI satellite: GL601/602/603/607 coverage extends to the
    profiler module and every serve/scheduler file it wired into, with
    NO new baseline entries (the files lint clean with no baseline
    applied at all)."""
    paths = [
        "sptag_tpu/utils/hostprof.py",
        "sptag_tpu/serve/metrics_http.py",
        "sptag_tpu/serve/server.py",
        "sptag_tpu/serve/aggregator.py",
        "sptag_tpu/algo/scheduler.py",
    ]
    srcs = {}
    for p in paths:
        with open(os.path.join(REPO, p), encoding="utf-8") as fh:
            srcs[p] = fh.read()
    found = lint_sources(srcs, select=["GL601", "GL602", "GL603",
                                       "GL607"])
    assert found == [], "\n".join(f.format() for f in found)


def test_gl606_out_of_family_qualmon_calls_clean():
    """Only gauge/inc carry names; record_sample's mode/shard labels,
    note_health's shard, and unrelated modules binding `qualmon` stay
    out of scope."""
    src = (
        "from sptag_tpu.utils import qualmon\n"
        "import contextlib as qualmon2\n"
        "def sample(mode, shard, recall, rid):\n"
        "    qualmon.record_sample(mode, shard, recall, 10, rid=rid)\n"
        "    qualmon.note_health(shard, nodes=5)\n"
        "    qualmon2.suppress(mode)\n"
    )
    assert lint_one(src, select=["GL606"]) == []


# ---------------------------------------------------------------------------
# GL608 timeline-series names (ISSUE 15)
# ---------------------------------------------------------------------------

def test_gl608_dynamic_timeline_name_flagged():
    """Timeline series names are the cardinality-bounded surface
    (ISSUE 15): the store keys fixed-size rings off them and never
    expires one — f-strings, concatenation and per-call variables are
    flagged like GL601/602/603/606/607."""
    src = (
        "from sptag_tpu.utils import timeline\n"
        "def publish(objective, value):\n"
        "    timeline.record(f'slo.{objective}', value)\n"
        "def feed(series, value):\n"
        "    timeline.record(series, value)\n"
    )
    found = lint_one(src, select=["GL608"])
    assert rules_of(found) == ["GL608"]
    assert len(found) == 2
    assert "string literal" in found[0].message


def test_gl608_literal_name_and_dynamic_label_clean():
    """Literal / module-constant names pass; the `label` argument is
    out of scope (deployment-bounded — the qualmon shard-label
    rationale), as are keyword/from-import forms and the read-path
    calls that only LOOK UP series."""
    src = (
        "from sptag_tpu.utils import timeline\n"
        "from sptag_tpu.utils.timeline import record\n"
        "SERIES = 'canary.latency_ms'\n"
        "def publish(idx_label, value, name):\n"
        "    timeline.record('canary.recall', value, label=idx_label)\n"
        "    timeline.record(SERIES, value)\n"
        "    record(name='canary.ok', value=value)\n"
        "    timeline.window_values(name, 60.0)\n"
        "    timeline.latest(name)\n"
    )
    assert lint_one(src, select=["GL608"]) == []
    dirty = (
        "from sptag_tpu.utils.timeline import record\n"
        "def publish(name, value):\n"
        "    record(name, value)\n"
    )
    assert rules_of(lint_one(dirty, select=["GL608"])) == ["GL608"]


def test_issue15_timeline_slo_canary_names_are_literals():
    """ISSUE 15 CI satellite: GL601/602/603/608 coverage extends to the
    timeline store, the SLO engine, the canary prober and the skew
    publishers, with NO new baseline entries (the files lint clean with
    no baseline applied at all)."""
    paths = [
        "sptag_tpu/utils/timeline.py",
        "sptag_tpu/serve/slo.py",
        "sptag_tpu/serve/canary.py",
        "sptag_tpu/serve/metrics_http.py",
        "sptag_tpu/algo/scheduler.py",
        "sptag_tpu/serve/aggregator.py",
    ]
    srcs = {}
    for p in paths:
        with open(os.path.join(REPO, p), encoding="utf-8") as fh:
            srcs[p] = fh.read()
    found = lint_sources(srcs, select=["GL601", "GL602", "GL603",
                                       "GL608"])
    assert found == [], "\n".join(f.format() for f in found)


# ---------------------------------------------------------------------------
# GL609 controller audit rule names (ISSUE 17)
# ---------------------------------------------------------------------------

def test_gl609_dynamic_audit_rule_flagged():
    """The ctlaudit ring is keyed and counted by decision rule; a
    dynamic rule name would make the audit trail unsearchable.  Both
    the module-attribute and from-import call forms are in scope."""
    src = (
        "from sptag_tpu.serve import ctlaudit\n"
        "def decide(rule, knob):\n"
        "    ctlaudit.record(rule, knob=knob)\n"
        "def decide2(outcome):\n"
        "    ctlaudit.record('veto_' + outcome)\n"
    )
    found = lint_one(src, select=["GL609"])
    assert rules_of(found) == ["GL609"]
    assert len(found) == 2
    assert "string literal" in found[0].message
    dirty = (
        "from sptag_tpu.serve.ctlaudit import record\n"
        "def decide(rule):\n"
        "    record(rule)\n"
    )
    assert rules_of(lint_one(dirty, select=["GL609"])) == ["GL609"]


def test_gl609_literal_constant_and_knob_arg_clean():
    """Literal / module-constant rule names pass — positionally or by
    keyword; the `knob` argument is out of scope (knob names come from
    the live-actuation registry, bounded by deployment — the flightrec
    tier rationale)."""
    src = (
        "from sptag_tpu.serve import ctlaudit\n"
        "RULE = 'burn_step_down'\n"
        "def decide(knob_name, old, new):\n"
        "    ctlaudit.record('canary_floor_veto', knob=knob_name)\n"
        "    ctlaudit.record(RULE, knob=knob_name, old=old, new=new)\n"
        "    ctlaudit.record(rule='at_floor_hold')\n"
        "    ctlaudit.set_outcome(1, 'kept')\n"
    )
    assert lint_one(src, select=["GL609"]) == []


def test_issue17_controller_rule_names_are_literals():
    """ISSUE 17 CI satellite: the controller/audit/serving files lint
    GL609-clean with NO baseline applied at all (zero baseline
    entries)."""
    paths = [
        "sptag_tpu/serve/controller.py",
        "sptag_tpu/serve/ctlaudit.py",
        "sptag_tpu/serve/server.py",
        "sptag_tpu/serve/aggregator.py",
        "sptag_tpu/serve/service.py",
    ]
    srcs = {}
    for p in paths:
        with open(os.path.join(REPO, p), encoding="utf-8") as fh:
            srcs[p] = fh.read()
    found = lint_sources(srcs, select=["GL609"])
    assert found == [], "\n".join(f.format() for f in found)


# ---------------------------------------------------------------------------
# baseline machinery + the tier-1 repo gate
# ---------------------------------------------------------------------------

def test_baseline_requires_justification():
    text = (
        '[[suppress]]\n'
        'rule = "GL101"\n'
        'path = "sptag_tpu/algo/engine.py"\n'
    )
    with pytest.raises(BaselineError, match="justification"):
        parse_baseline(text)


def test_baseline_matches_on_rule_path_symbol():
    text = (
        '[[suppress]]\n'
        'rule = "GL101"\n'
        'path = "sptag_tpu/algo/snippet.py"\n'
        'symbol = "f"\n'
        'justification = "test entry"\n'
    )
    sups = parse_baseline(text)
    src = (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.sum().item()\n"
        "@jax.jit\n"
        "def g(x):\n"
        "    return x.max().item()\n"
    )
    findings = lint_one(src, select=["GL101"])
    unsup, sup = apply_baseline(findings, sups)
    assert [f.symbol for f in sup] == ["f"]
    assert [f.symbol for f in unsup] == ["g"]


def test_every_rule_has_an_id_and_description():
    assert set(ALL_RULES) >= {
        "GL101", "GL102", "GL103", "GL104",
        "GL201", "GL202", "GL203",
        "GL301", "GL302",
        "GL401", "GL402",
        "GL501",
        "GL601", "GL602", "GL603",
        "GL701", "GL702", "GL703", "GL704",
    }
    assert all(ALL_RULES[r] for r in ALL_RULES)


def test_every_baseline_entry_names_a_live_rule_and_an_existing_path():
    """A suppression whose rule left the suite, or whose file left the
    tree, waives nothing and would silently rot in baseline.toml."""
    from tools.graftlint.baseline import load_baseline

    entries = load_baseline(DEFAULT_BASELINE)
    assert entries
    dead = [(s.lineno, s.rule, s.path) for s in entries
            if s.rule not in ALL_RULES
            or not os.path.isfile(os.path.join(REPO, s.path))]
    assert dead == []


def test_ci_check_names_only_test_files_that_exist():
    """A standalone gate of tools/ci_check.sh that runs a deleted test
    file exits 4 ("file not found") and stops every gate after it."""
    import re

    with open(os.path.join(REPO, "tools", "ci_check.sh")) as fh:
        named = set(re.findall(r"tests/test_\w+\.py", fh.read()))
    assert named
    assert sorted(n for n in named
                  if not os.path.isfile(os.path.join(REPO, n))) == []


def test_the_program_cites_no_module_that_left_the_tree():
    """A docstring or comment that sends the reader to `utils/x.py` is
    held to the tree: the file is there."""
    import glob
    import re

    cited = re.compile(r"\b((?:sptag_tpu/)?(?:algo|core|graph|io|ops|parallel"
                       r"|serve|tools|utils)/\w+\.py)\b")
    gone = set()
    for path in glob.glob(os.path.join(REPO, "sptag_tpu", "**", "*.py"),
                          recursive=True):
        with open(path, encoding="utf-8") as fh:
            for rel in cited.findall(fh.read()):
                if not any(os.path.isfile(os.path.join(REPO, root, rel))
                           for root in ("", "sptag_tpu")):
                    gone.add((os.path.relpath(path, REPO), rel))
    assert sorted(gone) == []


# ---------------------------------------------------------------------------
# GL7xx lock-order / blocking-under-lock / async hazards / handle leaks
# ---------------------------------------------------------------------------

_TWO_LOCK_INVERSION = (
    "import threading\n"
    "A = threading.Lock()\n"
    "B = threading.Lock()\n"
    "def forward():\n"
    "    with A:\n"
    "        with B:\n"
    "            pass\n"
    "def backward():\n"
    "    with B:\n"
    "        with A:\n"
    "            pass\n"
)


def test_gl701_two_lock_inversion_flagged():
    found = lint_one(_TWO_LOCK_INVERSION, select=["GL701"])
    assert rules_of(found) == ["GL701"]
    msg = found[0].message
    assert ".A" in msg and ".B" in msg and "cycle" in msg


def test_gl701_consistent_order_clean():
    src = (
        "import threading\n"
        "A = threading.Lock()\n"
        "B = threading.Lock()\n"
        "def one():\n"
        "    with A:\n"
        "        with B:\n"
        "            pass\n"
        "def two():\n"
        "    with A:\n"
        "        with B:\n"
        "            pass\n"
    )
    assert lint_one(src, select=["GL701"]) == []


def test_gl701_cycle_through_the_call_graph():
    """f holds A and calls g (which takes B); h holds B and calls k
    (which takes A) — the inversion only exists interprocedurally."""
    src = (
        "import threading\n"
        "A = threading.Lock()\n"
        "B = threading.Lock()\n"
        "def g():\n"
        "    with B:\n"
        "        pass\n"
        "def f():\n"
        "    with A:\n"
        "        g()\n"
        "def k():\n"
        "    with A:\n"
        "        pass\n"
        "def h():\n"
        "    with B:\n"
        "        k()\n"
    )
    found = lint_one(src, select=["GL701"])
    assert rules_of(found) == ["GL701"]
    assert "via call" in found[0].message


def test_gl701_attribute_locks_resolved_through_base_class():
    """self._lock created in a base class and acquired in the subclass is
    ONE lock; a subclass-vs-base order flip must still form a cycle."""
    src = (
        "import threading\n"
        "OTHER = threading.Lock()\n"
        "class Base:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def locked_then_other(self):\n"
        "        with self._lock:\n"
        "            with OTHER:\n"
        "                pass\n"
        "class Sub(Base):\n"
        "    def other_then_locked(self):\n"
        "        with OTHER:\n"
        "            with self._lock:\n"
        "                pass\n"
    )
    found = lint_one(src, select=["GL701"])
    assert rules_of(found) == ["GL701"]
    assert "Base._lock" in found[0].message


def test_gl701_multi_item_with_orders_its_items():
    """`with A, B:` enters sequentially — B under A.  A reversed nested
    pair elsewhere must close the cycle."""
    src = (
        "import threading\n"
        "A = threading.Lock()\n"
        "B = threading.Lock()\n"
        "def one():\n"
        "    with A, B:\n"
        "        pass\n"
        "def two():\n"
        "    with B:\n"
        "        with A:\n"
        "            pass\n"
    )
    assert rules_of(lint_one(src, select=["GL701"])) == ["GL701"]


def test_gl701_self_deadlock_through_callee():
    """Caller holds a non-reentrant Lock; a synchronous callee
    re-acquires it — guaranteed deadlock, only visible across the call."""
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def _inner(self):\n"
        "        with self._lock:\n"
        "            pass\n"
        "    def outer(self):\n"
        "        with self._lock:\n"
        "            self._inner()\n"
    )
    found = lint_one(src, select=["GL701"])
    assert [f.symbol for f in found] == ["C.outer"]
    assert "through call" in found[0].message


def test_gl702_positional_queue_timeout_clean():
    src = (
        "import queue\n"
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._queue = queue.Queue()\n"
        "    def bounded(self):\n"
        "        with self._lock:\n"
        "            return self._queue.get(True, 5.0)\n"
    )
    assert lint_one(src, select=["GL702"]) == []


def test_gl701_nonreentrant_self_acquisition_flagged():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            with self._lock:\n"
        "                pass\n"
    )
    found = lint_one(src, select=["GL701"])
    assert rules_of(found) == ["GL701"]
    assert "self-deadlock" in found[0].message


def test_gl701_rlock_self_acquisition_clean():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.RLock()\n"
        "    def f(self):\n"
        "        with self._lock:\n"
        "            with self._lock:\n"
        "                pass\n"
    )
    assert lint_one(src, select=["GL701"]) == []


def test_gl702_sleep_under_lock_flagged():
    src = (
        "import threading\n"
        "import time\n"
        "L = threading.Lock()\n"
        "def f():\n"
        "    with L:\n"
        "        time.sleep(1.0)\n"
    )
    found = lint_one(src, select=["GL702"])
    assert rules_of(found) == ["GL702"]
    assert "time.sleep" in found[0].message


def test_gl702_sleep_outside_lock_clean():
    src = (
        "import threading\n"
        "import time\n"
        "L = threading.Lock()\n"
        "def f():\n"
        "    with L:\n"
        "        x = 1\n"
        "    time.sleep(1.0)\n"
    )
    assert lint_one(src, select=["GL702"]) == []


def test_gl702_queue_get_without_timeout_under_lock():
    src = (
        "import queue\n"
        "import threading\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._queue = queue.Queue()\n"
        "    def bad(self):\n"
        "        with self._lock:\n"
        "            return self._queue.get()\n"
        "    def good(self):\n"
        "        with self._lock:\n"
        "            return self._queue.get(timeout=1.0)\n"
        "    def also_good(self):\n"
        "        with self._lock:\n"
        "            return self._queue.put_nowait(1)\n"
    )
    found = lint_one(src, select=["GL702"])
    assert [f.symbol for f in found] == ["C.bad"]


def test_gl702_reaches_blocking_call_through_helper():
    src = (
        "import threading\n"
        "class C:\n"
        "    def __init__(self, sock):\n"
        "        self._lock = threading.Lock()\n"
        "        self._sock = sock\n"
        "    def _send(self, data):\n"
        "        self._sock.sendall(data)\n"
        "    def locked_send(self, data):\n"
        "        with self._lock:\n"
        "            self._send(data)\n"
    )
    found = lint_one(src, select=["GL702"])
    assert [f.symbol for f in found] == ["C.locked_send"]
    assert "sendall" in found[0].message


def test_gl702_spawn_target_does_not_count_as_locked_call():
    """A callable PASSED to Thread/add runs later on another thread —
    its blocking ops must not be attributed to the spawner's lock."""
    src = (
        "import threading\n"
        "import time\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def _worker(self):\n"
        "        time.sleep(5)\n"
        "    def start(self):\n"
        "        with self._lock:\n"
        "            t = threading.Thread(target=self._worker)\n"
        "            t.start()\n"
        "            t.join()\n"
    )
    assert lint_one(src, select=["GL702"]) == []


def test_gl703_threading_lock_in_async_def_flagged():
    src = (
        "import threading\n"
        "L = threading.Lock()\n"
        "async def handler():\n"
        "    with L:\n"
        "        return 1\n"
    )
    found = lint_one(src, select=["GL703"])
    assert rules_of(found) == ["GL703"]
    assert "event loop" in found[0].message


def test_gl703_time_sleep_in_async_def_flagged_asyncio_sleep_clean():
    src = (
        "import asyncio\n"
        "import time\n"
        "async def bad():\n"
        "    time.sleep(0.1)\n"
        "async def good():\n"
        "    await asyncio.sleep(0.1)\n"
    )
    found = lint_one(src, select=["GL703"])
    assert [f.symbol for f in found] == ["bad"]


def test_gl703_nonwrite_await_under_asyncio_lock():
    src = (
        "import asyncio\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._wlock = asyncio.Lock()\n"
        "    async def bad(self, fut, writer):\n"
        "        async with self._wlock:\n"
        "            await fut\n"
        "    async def good(self, writer, payload):\n"
        "        async with self._wlock:\n"
        "            writer.write(payload)\n"
        "            await writer.drain()\n"
        "    async def also_good(self, writer):\n"
        "        async with self._wlock:\n"
        "            await asyncio.wait_for(writer.drain(), timeout=5)\n"
    )
    found = lint_one(src, select=["GL703"])
    assert [f.symbol for f in found] == ["C.bad"]


def test_gl703_sync_code_never_flagged():
    src = (
        "import threading\n"
        "import time\n"
        "L = threading.Lock()\n"
        "def plain():\n"
        "    with L:\n"
        "        pass\n"
        "    time.sleep(0.1)\n"
    )
    assert lint_one(src, select=["GL703"]) == []


def test_gl704_unjoined_thread_attribute_flagged():
    src = (
        "import threading\n"
        "class S:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        pass\n"
    )
    found = lint_one(src, select=["GL704"])
    assert rules_of(found) == ["GL704"]
    assert "_t" in found[0].message


def test_gl704_joined_thread_attribute_clean():
    src = (
        "import threading\n"
        "class S:\n"
        "    def start(self):\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def stop(self):\n"
        "        self._t.join(timeout=5)\n"
        "    def _run(self):\n"
        "        pass\n"
    )
    assert lint_one(src, select=["GL704"]) == []


def test_gl704_bare_create_task_flagged_stored_and_cancelled_clean():
    src = (
        "import asyncio\n"
        "class S:\n"
        "    async def fire_and_forget(self):\n"
        "        asyncio.create_task(self._pump())\n"
        "    async def start(self):\n"
        "        self._task = asyncio.create_task(self._pump())\n"
        "    async def stop(self):\n"
        "        self._task.cancel()\n"
        "    async def _pump(self):\n"
        "        pass\n"
    )
    found = lint_one(src, select=["GL704"])
    assert [f.symbol for f in found] == ["S.fire_and_forget"]


def test_gl704_worker_collection_join_loop_clean():
    src = (
        "import threading\n"
        "class Pool:\n"
        "    def __init__(self):\n"
        "        self._workers = []\n"
        "    def init(self, n):\n"
        "        for _ in range(n):\n"
        "            t = threading.Thread(target=self._run)\n"
        "            t.start()\n"
        "            self._workers.append(t)\n"
        "    def stop(self):\n"
        "        workers, self._workers = self._workers, []\n"
        "        for t in workers:\n"
        "            t.join(timeout=10)\n"
        "    def _run(self):\n"
        "        pass\n"
    )
    assert lint_one(src, select=["GL704"]) == []


def test_gl7_order_graph_exposed_for_runtime_crosscheck():
    """build_order_graph is the public surface tests/test_locksan.py
    cross-checks against the runtime-observed graph."""
    from tools.graftlint.core import Project
    from tools.graftlint.lockgraph import build_order_graph
    project = Project({"sptag_tpu/x.py": _TWO_LOCK_INVERSION})
    _model, edges, witness = build_order_graph(project)
    a, b = "sptag_tpu.x.A", "sptag_tpu.x.B"
    assert b in edges[a] and a in edges[b]
    assert witness[(a, b)][2] == "forward"


def test_repo_is_lint_clean_under_baseline():
    """THE gate: zero unsuppressed findings over sptag_tpu/, no stale
    baseline entries.  A new finding means: fix it, or add a JUSTIFIED
    baseline entry as part of the same change."""
    unsup, suppressed, stale = lint_project(
        os.path.join(REPO, "sptag_tpu"), DEFAULT_BASELINE)
    assert not unsup, "new findings:\n" + "\n".join(
        f.format() for f in unsup)
    assert not stale, "stale baseline entries (prune them): " + ", ".join(
        f"{s.rule} {s.path} {s.symbol or '*'}" for s in stale)
    # the shipped baseline is non-trivial and every entry is exercised
    assert suppressed, "baseline expected to suppress accepted findings"


def test_cli_exits_zero_on_clean_tree(capsys):
    from tools.graftlint.runner import main
    rc = main([os.path.join(REPO, "sptag_tpu")])
    assert rc == 0
    err = capsys.readouterr().err
    assert "0 finding(s)" in err


def test_gl201_static_argnums_positional_clean():
    """static_argnums (positional ints) must count as static, both for
    GL201 and for the taint seeding (code-review fix)."""
    src = (
        "import functools\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@functools.partial(jax.jit, static_argnums=(1, 2))\n"
        "def f(x, k: int, width: int):\n"
        "    return jnp.sum(x[:width]) * float(k)\n"
    )
    assert lint_one(src, select=["GL201", "GL102"]) == []


def test_baseline_unterminated_string_is_a_baseline_error():
    text = (
        '[[suppress]]\n'
        'rule = "GL101\n'
        'path = "x.py"\n'
        'justification = "y"\n'
    )
    with pytest.raises(BaselineError, match="unterminated|quoted"):
        parse_baseline(text)


def test_lazy_submodule_import_does_not_hide_jit_roots():
    """`import jax.profiler` binds the name `jax`, not `jax.profiler` —
    it must not break resolution of `jax.jit` in the same module (the
    exact lazy-import idiom utils/trace.py uses)."""
    src = (
        "import jax\n"
        "def start():\n"
        "    import jax.profiler\n"
        "    jax.profiler.start_trace('/tmp/x')\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    return x.sum().item()\n"
    )
    assert rules_of(lint_one(src, select=["GL101"])) == ["GL101"]


def test_subpackage_root_keeps_repo_relative_paths(monkeypatch):
    """Linting sptag_tpu/core directly must still report
    sptag_tpu/core/... paths so path-scoped rules and baseline entries
    keep matching."""
    monkeypatch.chdir(REPO)
    unsup, suppressed, stale = lint_project(
        "sptag_tpu/core", DEFAULT_BASELINE)
    assert not unsup, "\n".join(f.format() for f in unsup)
    # the save_index GL402 entries are found AND suppressed at this root
    assert any(f.path == "sptag_tpu/core/index.py" for f in suppressed)
    # entries for OTHER roots (serve/, ops/) legitimately show stale in a
    # single-root call; none of the core/ entries may
    assert not any(s.path.startswith("sptag_tpu/core/") for s in stale)


def test_gl301_spawn_in_one_class_does_not_taint_another():
    src = (
        "import threading\n"
        "class Spawner:\n"
        "    def start(self):\n"
        "        threading.Thread(target=self._run).start()\n"
        "    def _run(self):\n"
        "        pass\n"
        "class Sync:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "    def set_state(self, v):\n"
        "        with self._lock:\n"
        "            self._state = v\n"
        "    def _run(self):\n"
        "        self._state = 2\n"
    )
    assert lint_one(src, select=["GL301"]) == []


# ---------------------------------------------------------------------------
# GL411 persistence-write funnel (ISSUE 9)
# ---------------------------------------------------------------------------

def test_gl411_write_open_in_core_flagged():
    """A bare write-mode open() in core/ or io/ bypasses the fsync +
    fault-hook funnel (io/atomic.py, io/wal.py) — the implicit
    close-flush contract that loses acked writes on power loss."""
    src = (
        "import os\n"
        "def save(folder, blob):\n"
        "    with open(os.path.join(folder, 'x.bin'), 'wb') as f:\n"
        "        f.write(blob)\n"
    )
    found = lint_one(src, path="sptag_tpu/core/snippet.py",
                     select=["GL411"])
    assert rules_of(found) == ["GL411"]
    assert "atomic" in found[0].message
    # io/ is in scope too
    assert rules_of(lint_one(src, path="sptag_tpu/io/snippet.py",
                             select=["GL411"])) == ["GL411"]


def test_gl411_read_open_and_out_of_scope_clean():
    """Read-mode opens pass; write opens OUTSIDE core//io (algo, serve,
    tools) are out of scope — their durability is owned by the core
    save path they are staged under."""
    read_src = (
        "def load(path):\n"
        "    with open(path, 'rb') as f:\n"
        "        return f.read()\n"
        "def load_default(path):\n"
        "    with open(path) as f:\n"
        "        return f.read()\n"
    )
    assert lint_one(read_src, path="sptag_tpu/core/snippet.py",
                    select=["GL411"]) == []
    write_src = (
        "def save(path, b):\n"
        "    with open(path, 'wb') as f:\n"
        "        f.write(b)\n"
    )
    assert lint_one(write_src, path="sptag_tpu/algo/snippet.py",
                    select=["GL411"]) == []


def test_gl411_helper_modules_exempt_and_modes_covered():
    """The two sanctioned helpers implement the funnel and keep their
    raw opens; append/exclusive/update and computed modes are flagged
    in scoped modules (a computed mode can't be proven read-only)."""
    src = (
        "def raw(path, b, m):\n"
        "    open(path, 'ab').write(b)\n"
        "    open(path, mode='r+b').read()\n"
        "    open(path, m)\n"
    )
    assert lint_one(src, path="sptag_tpu/io/atomic.py",
                    select=["GL411"]) == []
    assert lint_one(src, path="sptag_tpu/io/wal.py",
                    select=["GL411"]) == []
    found = lint_one(src, path="sptag_tpu/io/snippet.py",
                     select=["GL411"])
    assert rules_of(found) == ["GL411"]
    assert len(found) == 3


def test_gl411_registered_and_tree_clean():
    """GL411 is registered with the runner, and the real core//io tree
    needs ZERO baseline entries — every persistence write already rides
    the helpers."""
    assert "GL411" in ALL_RULES
    unsup, _sup, _stale = lint_project(
        os.path.join(REPO, "sptag_tpu"), DEFAULT_BASELINE,
        select=["GL411"])
    assert unsup == [], "\n".join(f.format() for f in unsup)


# ---------------------------------------------------------------------------
# GL80x guarded-by inference (ISSUE 12)
# ---------------------------------------------------------------------------

_GL8_PREAMBLE = (
    "import threading\n"
)


def test_gl801_unguarded_write_to_shared_attr_flagged():
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        with self._lock:\n"
        "            self._n = 1\n"
        "    def poke(self):\n"
        "        self._n = 2\n"
    )
    found = lint_one(src, select=["GL801"])
    assert rules_of(found) == ["GL801"]
    assert found[0].symbol == "C.poke"
    assert "_lock" in found[0].message


def test_gl801_all_writes_locked_clean():
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        with self._lock:\n"
        "            self._n = 1\n"
        "    def poke(self):\n"
        "        with self._lock:\n"
        "            self._n = 2\n"
    )
    assert lint_one(src, select=["GL801", "GL802", "GL803"]) == []


def test_gl801_interprocedural_held_on_entry_clean():
    """A helper only ever called under the lock counts its writes as
    guarded — the template-method `_impl` pattern must not be flagged."""
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        with self._lock:\n"
        "            self._bump()\n"
        "    def update(self):\n"
        "        with self._lock:\n"
        "            self._bump()\n"
        "    def _bump(self):\n"
        "        self._n = self._n + 1\n"
    )
    assert lint_one(src, select=["GL801", "GL802"]) == []


def test_gl801_attr_not_thread_shared_clean():
    """No thread entry anywhere: single-threaded mutation is never
    reported, whatever the locking looks like."""
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._n = 0\n"
        "    def locked(self):\n"
        "        with self._lock:\n"
        "            self._n = 1\n"
        "    def unlocked(self):\n"
        "        self._n = 2\n"
    )
    assert lint_one(src, select=["GL801", "GL802", "GL803"]) == []


def test_gl802_unguarded_rmw_flagged_augassign_and_container():
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._hits = 0\n"
        "        self._seen = {}\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        self._hits += 1\n"
        "        self._seen['k'] = 1\n"
        "        with self._lock:\n"
        "            self._hits += 1\n"
        "            self._seen['j'] = 2\n"
    )
    found = lint_one(src, select=["GL802"])
    assert rules_of(found) == ["GL802"]
    assert len(found) == 2
    assert {f.message.split("`")[1] for f in found} == \
        {"self._hits", "self._seen"}


def test_gl802_check_then_set_assign_flagged():
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._log = ()\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        self._log = self._log + (1,)\n"
    )
    found = lint_one(src, select=["GL802"])
    assert rules_of(found) == ["GL802"]
    assert found[0].symbol == "C._run"


def test_gl803_disjoint_guards_flagged():
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._alock = threading.Lock()\n"
        "        self._block = threading.Lock()\n"
        "        self._n = 0\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        with self._alock:\n"
        "            self._n = 1\n"
        "    def other(self):\n"
        "        with self._block:\n"
        "            self._n = 2\n"
    )
    found = lint_one(src, select=["GL803"])
    assert rules_of(found) == ["GL803"]
    assert "_alock" in found[0].message and "_block" in found[0].message


def test_gl803_condition_wrapping_lock_is_one_guard():
    """`threading.Condition(self._lock)` IS self._lock — writes under
    the condition and under the lock agree on the guard."""
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._cv = threading.Condition(self._lock)\n"
        "        self._n = 0\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        with self._cv:\n"
        "            self._n = 1\n"
        "    def other(self):\n"
        "        with self._lock:\n"
        "            self._n = 2\n"
    )
    assert lint_one(src, select=["GL803", "GL801"]) == []


def test_gl804_epoch_repin_flagged_and_pinned_clean():
    """The planted epoch-repin: a background thread swaps the engine
    under the lock while a reader re-reads `self._engine` mid-call —
    the exact bug class PR 9's _get_engine fix closed."""
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._engine = object()\n"
        "        self._t = threading.Thread(target=self._refresh)\n"
        "        self._t.start()\n"
        "    def _refresh(self):\n"
        "        with self._lock:\n"
        "            self._engine = object()\n"
        "    def search(self, q):\n"
        "        seeds = self._engine.seed(q)\n"
        "        return self._engine.walk(seeds)\n"
    )
    found = lint_one(src, select=["GL804"])
    assert rules_of(found) == ["GL804"]
    assert found[0].symbol == "C.search"
    assert "pin" in found[0].message
    pinned = src.replace(
        "        seeds = self._engine.seed(q)\n"
        "        return self._engine.walk(seeds)\n",
        "        eng = self._engine\n"
        "        return eng.walk(eng.seed(q))\n")
    assert lint_one(pinned, select=["GL804"]) == []


def test_gl804_reads_under_the_swap_lock_clean():
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._engine = object()\n"
        "        self._t = threading.Thread(target=self._refresh)\n"
        "        self._t.start()\n"
        "    def _refresh(self):\n"
        "        with self._lock:\n"
        "            self._engine = object()\n"
        "    def search(self, q):\n"
        "        with self._lock:\n"
        "            seeds = self._engine.seed(q)\n"
        "            return self._engine.walk(seeds)\n"
    )
    assert lint_one(src, select=["GL804"]) == []


def test_gl805_escaping_self_before_init_completes_flagged():
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "        self._ready = True\n"
        "    def _run(self):\n"
        "        pass\n"
    )
    found = lint_one(src, select=["GL805"])
    assert rules_of(found) == ["GL805"]
    assert found[0].symbol == "C.__init__"
    assert "partially-built" in found[0].message


def test_gl805_publish_last_clean():
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self):\n"
        "        self._ready = True\n"
        "        self._t = threading.Thread(target=self._run)\n"
        "        self._t.start()\n"
        "    def _run(self):\n"
        "        pass\n"
    )
    assert lint_one(src, select=["GL805"]) == []


def test_gl805_callable_handed_to_pool_in_init_flagged():
    src = _GL8_PREAMBLE + (
        "class C:\n"
        "    def __init__(self, pool):\n"
        "        pool.add(self._job)\n"
        "        self._state = {}\n"
        "    def _job(self):\n"
        "        pass\n"
    )
    found = lint_one(src, select=["GL805"])
    assert rules_of(found) == ["GL805"]


def test_gl806_plain_lock_flagged_sanctioned_forms_clean():
    src = _GL8_PREAMBLE + (
        "from sptag_tpu.utils import locksan\n"
        "_mod_lock = threading.Lock()\n"
        "class C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.RLock()\n"
        "        self._named = locksan.make_lock('C._named')\n"
        "        self._cv = threading.Condition(self._named)\n"
    )
    found = lint_one(src, select=["GL806"])
    assert rules_of(found) == ["GL806"]
    assert len(found) == 2                    # _mod_lock + self._lock
    # out of scope (tools/) and the sanitizer itself are exempt
    assert lint_one(src, path="tools/snippet.py", select=["GL806"]) == []
    assert lint_one(src, path="sptag_tpu/utils/locksan.py",
                    select=["GL806"]) == []


def test_gl80x_registered_and_repo_clean_with_zero_race_waivers():
    """GL801-806 are registered with the runner; the repo is clean under
    the baseline; and GL801-805 specifically carry ZERO baseline entries
    — every real finding was fixed, not waived (only GL806's
    intentionally-plain infra locks are suppressed, each justified)."""
    for rule in ("GL801", "GL802", "GL803", "GL804", "GL805", "GL806"):
        assert rule in ALL_RULES
    unsup, _sup, _stale = lint_project(
        os.path.join(REPO, "sptag_tpu"), DEFAULT_BASELINE,
        select=["GL80"])
    assert unsup == [], "\n".join(f.format() for f in unsup)
    from tools.graftlint.baseline import load_baseline
    entries = load_baseline(DEFAULT_BASELINE)
    race_waivers = [s for s in entries
                    if s.rule.startswith("GL80") and s.rule != "GL806"]
    assert race_waivers == []
    # every GL806 suppression pins the EXACT lock it accepts — a new
    # plain lock in the same file must still be reported
    loose = [s for s in entries if s.rule == "GL806"
             and "assigned to `" not in s.contains]
    assert loose == []


def test_infer_guards_exposed_for_runtime_crosscheck():
    """The cross-check surface tests/test_racesan.py consumes: guard
    inference over the real tree names the writer lock for the index's
    swappable state."""
    from tools.graftlint import guardedby
    from tools.graftlint.core import Project

    guards = guardedby.infer_guards(
        Project.from_tree(os.path.join(REPO, "sptag_tpu")))
    flat = {(cls.rsplit(".", 1)[-1], attr): g
            for (cls, attr), g in guards.items()}
    eng = flat.get(("BKTIndex", "_engine")) or \
        flat.get(("VectorIndex", "_engine"))
    assert eng and any(c.endswith("VectorIndex._lock") for c in eng), \
        flat.get(("BKTIndex", "_engine"))


# ---------------------------------------------------------------------------
# GL9xx device-program contracts (tracecontract + attrmodel)
# ---------------------------------------------------------------------------

_JIT_PREAMBLE = (
    "import functools\n"
    "import jax\n"
    "import jax.numpy as jnp\n"
    "@functools.partial(jax.jit, static_argnames=(\"k\",))\n"
    "def kernel(x, k):\n"
    "    return x[:k]\n"
)


def test_gl901_float_derived_static_feed_flagged():
    src = _JIT_PREAMBLE + (
        "def caller(x, n):\n"
        "    return kernel(x, k=n / 2)\n"
    )
    found = lint_one(src, select=["GL901"])
    assert rules_of(found) == ["GL901"]
    assert "float-derived" in found[0].message
    assert found[0].symbol == "caller"


def test_gl901_device_value_static_feed_flagged():
    src = _JIT_PREAMBLE + (
        "def caller(x):\n"
        "    kv = jnp.sum(x)\n"
        "    return kernel(x, k=kv)\n"
    )
    found = lint_one(src, select=["GL901"])
    assert rules_of(found) == ["GL901"]
    assert "device value" in found[0].message


def test_gl901_mutable_literal_static_feed_flagged():
    src = _JIT_PREAMBLE + (
        "def caller(x):\n"
        "    return kernel(x, k=[1, 2])\n"
    )
    found = lint_one(src, select=["GL901"])
    assert found and "mutable" in found[0].message


def test_gl901_nonliteral_spec_and_missing_name_flagged():
    src = (
        "import functools\n"
        "import jax\n"
        "STATIC = (\"k\",)\n"
        "@functools.partial(jax.jit, static_argnames=STATIC)\n"
        "def a(x, k):\n"
        "    return x[:k]\n"
        "@functools.partial(jax.jit, static_argnames=(\"k\", \"missing\"))\n"
        "def b(x, k):\n"
        "    return x[:k]\n"
    )
    found = lint_one(src, select=["GL901"])
    msgs = " | ".join(f.message for f in found)
    assert "not a literal" in msgs
    assert "not a parameter" in msgs


def test_gl901_float_typed_static_param_flagged():
    src = (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, static_argnames=(\"scale\",))\n"
        "def dequant(x, scale: float):\n"
        "    return x * scale\n"
    )
    found = lint_one(src, select=["GL901"])
    assert rules_of(found) == ["GL901"]
    assert "float-typed" in found[0].message


def test_gl901_literal_int_static_feed_clean():
    src = _JIT_PREAMBLE + (
        "def caller(x):\n"
        "    return kernel(x, k=8)\n"
    )
    assert lint_one(src, select=["GL901"]) == []


def test_gl902_interprocedural_implicit_transfer_in_hot_path():
    """The taint flows THROUGH a helper: `helper` returns a device
    value, the scheduler-named hot root reads it back with np.asarray —
    the exact pattern the runtime sentinel flags as `__array__`."""
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def helper(q):\n"
        "    return jnp.dot(q, q)\n"
        "def _cycle(pool):\n"
        "    s = helper(pool)\n"
        "    return np.asarray(s)\n"
    )
    found = lint_one(src, select=["GL902"])
    assert rules_of(found) == ["GL902"]
    assert "IMPLICIT device->host transfer" in found[0].message
    assert found[0].symbol == "_cycle"


def test_gl902_while_on_device_flag_flagged():
    src = (
        "import jax.numpy as jnp\n"
        "def run_segment(state):\n"
        "    alive = jnp.any(state)\n"
        "    while alive:\n"
        "        alive = jnp.any(state)\n"
        "    return state\n"
    )
    found = lint_one(src, select=["GL902"])
    assert found and "`while` on a device value" in found[0].message


def test_gl902_blessed_device_get_clean():
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from sptag_tpu.utils import recompile_guard\n"
        "def helper(q):\n"
        "    return jnp.dot(q, q)\n"
        "def _cycle(pool):\n"
        "    s = helper(pool)\n"
        "    h = recompile_guard.device_get(s)\n"
        "    return np.asarray(h)\n"
    )
    assert lint_one(src, select=["GL902"]) == []


def test_gl902_same_body_outside_hot_roots_clean():
    src = (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def summarize(pool):\n"
        "    s = jnp.dot(pool, pool)\n"
        "    return np.asarray(s)\n"
    )
    assert lint_one(src, select=["GL902"]) == []


_SHARD_PREAMBLE = (
    "import jax\n"
    "from jax.experimental.shard_map import shard_map\n"
    "from jax.sharding import Mesh, PartitionSpec as P\n"
    "SHARD_AXIS = \"shard\"\n"
)


def test_gl903_in_specs_arity_mismatch_flagged():
    src = _SHARD_PREAMBLE + (
        "def build(mesh):\n"
        "    def local(a, b):\n"
        "        return a + b\n"
        "    return shard_map(local, mesh,\n"
        "                     in_specs=(P(\"shard\"), P(\"shard\"), P(None)),\n"
        "                     out_specs=P(\"shard\"))\n"
    )
    found = lint_one(src, select=["GL903"])
    assert rules_of(found) == ["GL903"]
    assert "3 spec(s)" in found[0].message and \
        "2 positional" in found[0].message


def test_gl903_out_specs_arity_mismatch_flagged():
    src = _SHARD_PREAMBLE + (
        "def build(mesh):\n"
        "    def local(a, b):\n"
        "        return (a, b)\n"
        "    return shard_map(local, mesh,\n"
        "                     in_specs=(P(\"shard\"), P(None)),\n"
        "                     out_specs=(P(\"shard\"),))\n"
    )
    found = lint_one(src, select=["GL903"])
    assert found and "returns 2 value(s)" in found[0].message


def test_gl903_undeclared_partition_axis_flagged():
    src = _SHARD_PREAMBLE + (
        "def build(mesh):\n"
        "    def local(a):\n"
        "        return a\n"
        "    return shard_map(local, mesh,\n"
        "                     in_specs=(P(\"model\"),),\n"
        "                     out_specs=P(None))\n"
    )
    found = lint_one(src, select=["GL903"])
    assert found and "'model'" in found[0].message and \
        "declared mesh axis" in found[0].message


def test_gl903_gl904_clean_interprocedural_shard_map():
    """The idiomatic mesh kernel: a module-level wrapped fn whose HELPER
    runs the collective over the declared axis, specs matching the
    signature and return arity — zero findings end to end."""
    src = _SHARD_PREAMBLE + (
        "def merge(d):\n"
        "    return jax.lax.all_gather(d, SHARD_AXIS, axis=0, tiled=True)\n"
        "def local(a, b):\n"
        "    return (merge(a + b), b)\n"
        "def build(mesh):\n"
        "    return shard_map(local, mesh,\n"
        "                     in_specs=(P(SHARD_AXIS), P(None)),\n"
        "                     out_specs=(P(None), P(SHARD_AXIS)))\n"
    )
    assert lint_one(src, select=["GL903", "GL904"]) == []


def test_gl904_collective_outside_shard_map_flagged():
    src = (
        "import jax\n"
        "def combine(x):\n"
        "    return jax.lax.psum(x, \"shard\")\n"
    )
    found = lint_one(src, select=["GL904"])
    assert rules_of(found) == ["GL904"]
    assert "never wrapped by shard_map" in found[0].message


def test_gl904_wrong_axis_name_flagged():
    src = _SHARD_PREAMBLE + (
        "def build(mesh):\n"
        "    def local(a):\n"
        "        return jax.lax.psum(a, \"model\")\n"
        "    return shard_map(local, mesh,\n"
        "                     in_specs=(P(SHARD_AXIS),),\n"
        "                     out_specs=P(None))\n"
    )
    found = lint_one(src, select=["GL904"])
    assert found and "'model'" in found[0].message and \
        "no mesh declaration binds" in found[0].message


def test_gl905_never_assigned_read_under_swallow_escalated():
    """The iter_cost1 bug class itself: a typo'd attribute read whose
    AttributeError a broad handler eats forever."""
    src = (
        "class CostTracker:\n"
        "    def __init__(self):\n"
        "        self.slots = 0\n"
        "    def snapshot(self):\n"
        "        try:\n"
        "            return self.slotz + 1\n"
        "        except Exception:\n"
        "            return 0\n"
    )
    found = lint_one(src, select=["GL905"])
    assert rules_of(found) == ["GL905"]
    assert "never assigned" in found[0].message
    assert "GUARANTEED silent" in found[0].message
    assert found[0].symbol == "CostTracker.snapshot"


def test_gl905_plain_never_assigned_read_flagged():
    src = (
        "class CostTracker:\n"
        "    def __init__(self):\n"
        "        self.slots = 0\n"
        "    def snapshot(self):\n"
        "        return self.slotz + 1\n"
    )
    found = lint_one(src, select=["GL905"])
    assert found and "GUARANTEED" not in found[0].message


def test_gl905_assigned_probe_and_external_base_clean():
    src = (
        "from http.server import BaseHTTPRequestHandler\n"
        "class Tracker:\n"
        "    def __init__(self):\n"
        "        self.slots = 0\n"
        "    def read(self):\n"
        "        return self.slots\n"
        "    def probe(self):\n"
        "        try:\n"
        "            return self.cache\n"
        "        except AttributeError:\n"
        "            return None\n"
        "    def start(self):\n"
        "        class Handler(BaseHTTPRequestHandler):\n"
        "            def do_GET(self):\n"
        "                return self.path\n"
        "        return Handler\n"
    )
    assert lint_one(src, select=["GL905"]) == []


def test_gl905_nested_closure_param_is_not_the_receiver():
    """Regression guard for the sharded.py `_pad(f)` false positive: a
    nested callback whose OWN param shadows nothing must not charge its
    attribute reads to the enclosing instance."""
    src = (
        "class Poller:\n"
        "    def __init__(self):\n"
        "        self.done = 0\n"
        "    def wire(self, fut):\n"
        "        def _pad(f):\n"
        "            return (f.exception, f.result, self.done)\n"
        "        return _pad(fut)\n"
    )
    assert lint_one(src, select=["GL905"]) == []


def test_gl906_swallowed_telemetry_publish_flagged():
    src = (
        "from sptag_tpu.utils import metrics\n"
        "def publish(v):\n"
        "    try:\n"
        "        metrics.inc(\"serve.requests\", v)\n"
        "    except Exception:\n"
        "        pass\n"
    )
    found = lint_one(src, select=["GL906"])
    assert rules_of(found) == ["GL906"]
    assert "dies silently" in found[0].message
    assert found[0].symbol == "publish"


def test_gl906_logging_handler_clean():
    src = (
        "import logging\n"
        "from sptag_tpu.utils import metrics\n"
        "log = logging.getLogger(__name__)\n"
        "def publish(v):\n"
        "    try:\n"
        "        metrics.inc(\"serve.requests\", v)\n"
        "    except Exception:\n"
        "        log.warning(\"metrics publish failed\")\n"
    )
    assert lint_one(src, select=["GL906"]) == []


def test_gl90x_registered_and_repo_clean_with_zero_gl905_waivers():
    """GL901-906 are registered with the runner; the repo is clean under
    the baseline; and GL905 specifically ships with a ZERO-entry
    baseline — every never-assigned-attribute read was fixed, not
    waived (the ISSUE 16 acceptance)."""
    for rule in ("GL901", "GL902", "GL903", "GL904", "GL905", "GL906"):
        assert rule in ALL_RULES
    unsup, _sup, _stale = lint_project(
        os.path.join(REPO, "sptag_tpu"), DEFAULT_BASELINE,
        select=["GL9"])
    assert unsup == [], "\n".join(f.format() for f in unsup)
    from tools.graftlint.baseline import load_baseline
    entries = load_baseline(DEFAULT_BASELINE)
    gl905_waivers = [s for s in entries if s.rule == "GL905"]
    assert gl905_waivers == []
    # every GL901 suppression pins the exact static param it accepts —
    # a new float-typed static in the same file must still be reported
    loose = [s for s in entries if s.rule == "GL901"
             and "is float-typed" not in s.contains]
    assert loose == []


# ---------------------------------------------------------------------------
# GL100x observability/config contract graph
# ---------------------------------------------------------------------------

def test_gl1001_timeline_read_of_unpublished_series_flagged():
    src = (
        "from sptag_tpu.utils import timeline\n"
        "def poll():\n"
        "    return timeline.latest(\"ghost.series\")\n"
    )
    found = lint_one(src, select=["GL1001"])
    assert rules_of(found) == ["GL1001"]
    assert found[0].symbol == "poll"
    assert "ghost.series" in found[0].message


def test_gl1001_counter_derivation_satisfies_timeline_read():
    """A counter producer covers the `.rate` timeline derivation the
    consumer reads — the exact dataflow slo.py depends on."""
    src = (
        "from sptag_tpu.utils import metrics, timeline\n"
        "def serve(n):\n"
        "    metrics.inc(\"serve.requests\", n)\n"
        "def poll():\n"
        "    return timeline.latest(\"serve.requests.rate\")\n"
    )
    assert lint_one(src, select=["GL1001"]) == []


def test_gl1001_metric_read_with_wrong_instrument_kind_flagged():
    src = (
        "from sptag_tpu.utils import metrics\n"
        "def serve(n):\n"
        "    metrics.inc(\"serve.requests\", n)\n"
        "def report():\n"
        "    return metrics.gauge_value(\"serve.requests\")\n"
    )
    found = lint_one(src, select=["GL1001"])
    assert rules_of(found) == ["GL1001"]
    assert "counter" in found[0].message


def test_gl1002_published_never_consumed_flagged():
    """In-memory fixtures carry no docs/tests corpus, so an orphan
    producer has no mention anywhere and must be reported."""
    src = (
        "from sptag_tpu.utils import metrics\n"
        "def publish(n):\n"
        "    metrics.inc(\"orphan.counter\", n)\n"
    )
    found = lint_one(src, select=["GL1002"])
    assert rules_of(found) == ["GL1002"]
    assert "orphan.counter" in found[0].message


def test_gl1002_doc_mention_clears_published_name():
    sources = {
        "sptag_tpu/algo/snippet.py": (
            "from sptag_tpu.utils import metrics\n"
            "def publish(n):\n"
            "    metrics.inc(\"orphan.counter\", n)\n"
        ),
        # planted corpus file: a docs mention is a sanctioned consumer
        "docs/NOTES.md": "`orphan.counter` is scraped by the ops board\n",
    }
    assert [f for f in lint_sources(sources, select=["GL1002"])] == []


def test_gl1002_prom_rendered_mention_clears_published_name():
    """Tests grep /metrics in Prometheus form (`sptag_tpu_x_y`) — that
    counts as consumption of the dotted registry name `x.y`."""
    sources = {
        "sptag_tpu/algo/snippet.py": (
            "from sptag_tpu.utils import metrics\n"
            "def publish(n):\n"
            "    metrics.inc(\"orphan.counter\", n)\n"
        ),
        "docs/NOTES.md": "scrape asserts sptag_tpu_orphan_counter > 0\n",
    }
    assert lint_sources(sources, select=["GL1002"]) == []


def test_gl1003_bare_read_of_labeled_only_family_flagged():
    """Every producer publishes `shard.lag` under a label; the bare
    timeline key never receives a point, so the read is dead."""
    src = (
        "from sptag_tpu.utils import metrics, timeline\n"
        "def publish(v, shard):\n"
        "    fam = metrics.Family(\"shard.lag\")\n"
        "    fam.add(v, {\"shard\": shard})\n"
        "def poll():\n"
        "    return timeline.latest(\"shard.lag\")\n"
    )
    found = lint_one(src, select=["GL1003"])
    assert rules_of(found) == ["GL1003"]
    assert "labeled" in found[0].message


def test_gl1003_conflicting_family_label_sets_flagged():
    src = (
        "from sptag_tpu.utils import metrics\n"
        "def publish(v, shard, tier):\n"
        "    fam = metrics.Family(\"shard.lag\")\n"
        "    fam.add(v, {\"shard\": shard})\n"
        "    fam.add(v, {\"tier\": tier})\n"
    )
    found = lint_one(src, select=["GL1003"])
    assert rules_of(found) == ["GL1003"]
    assert "conflicting" in found[0].message


def test_gl1003_consistent_labels_and_unlabeled_aggregate_clean():
    src = (
        "from sptag_tpu.utils import metrics, timeline\n"
        "def publish(v, shard):\n"
        "    fam = metrics.Family(\"shard.lag\")\n"
        "    fam.add(v, {\"shard\": shard})\n"
        "    fam.add(v, {\"shard\": \"all\"})\n"
        "    agg = metrics.Family(\"shard.skew\")\n"
        "    agg.add(v, None)\n"
        "def poll():\n"
        "    return timeline.latest(\"shard.skew\")\n"
    )
    assert lint_one(src, select=["GL1003"]) == []


def test_gl1004_param_spec_without_doc_row_flagged():
    sources = {
        "sptag_tpu/core/params.py": (
            "def _spec(lo, hi, default, name):\n"
            "    return (lo, hi, default, name)\n"
            "SPECS = [_spec(0, 8, 2, \"DocumentedKnob\"),\n"
            "         _spec(0, 8, 2, \"UndocumentedKnob\")]\n"
        ),
        "docs/PARAMETERS.md": (
            "| Parameter | Default | Notes |\n"
            "| --- | --- | --- |\n"
            "| `DocumentedKnob` | 2 | tuned per round |\n"
        ),
    }
    found = lint_sources(sources, select=["GL1004"])
    assert rules_of(found) == ["GL1004"]
    assert len(found) == 1
    assert "UndocumentedKnob" in found[0].message


def test_gl1004_stale_doc_row_flagged():
    sources = {
        "sptag_tpu/core/params.py": (
            "def _spec(lo, hi, default, name):\n"
            "    return (lo, hi, default, name)\n"
            "SPECS = [_spec(0, 8, 2, \"RealKnob\")]\n"
        ),
        "docs/PARAMETERS.md": (
            "| `RealKnob` | 2 | fine |\n"
            "| `GhostKnob` | 7 | removed two rounds ago |\n"
        ),
    }
    found = lint_sources(sources, select=["GL1004"])
    assert rules_of(found) == ["GL1004"]
    assert found[0].path == "docs/PARAMETERS.md"
    assert "GhostKnob" in found[0].message


def test_gl1004_without_planted_doc_silent():
    """No docs/PARAMETERS.md surface (fixture project) -> the doc
    contract simply does not apply; no noise on unit fixtures."""
    src = (
        "def _spec(lo, hi, default, name):\n"
        "    return name\n"
        "SPECS = [_spec(0, 8, 2, \"WhateverKnob\")]\n"
    )
    assert lint_one(src, select=["GL1004"]) == []


def test_gl1005_param_use_without_spec_flagged():
    src = (
        "def _spec(lo, hi, default, name):\n"
        "    return name\n"
        "KNOBS = [_spec(1, 8, 2, \"RealKnob\")]\n"
        "def tune(idx):\n"
        "    idx.set_parameter(\"NoSuchKnob\", 3)\n"
    )
    found = lint_one(src, select=["GL1005"])
    assert rules_of(found) == ["GL1005"]
    assert "NoSuchKnob" in found[0].message


def test_gl1005_case_insensitive_spec_match_clean():
    """set_parameter lowercases on lookup — `realknob` resolves."""
    src = (
        "def _spec(lo, hi, default, name):\n"
        "    return name\n"
        "KNOBS = [_spec(1, 8, 2, \"RealKnob\")]\n"
        "def tune(idx):\n"
        "    idx.set_parameter(\"realknob\", 3)\n"
    )
    assert lint_one(src, select=["GL1005"]) == []


def test_gl1006_route_contract_mismatch_flagged_both_directions():
    server_src = (
        "def handler(q):\n"
        "    return 200\n"
        "class Server:\n"
        "    def __init__(self):\n"
        "        self._routes = {\"/metrics\": handler,\n"
        "                        \"/debug/extra\": handler}\n"
    )
    contract_src = "EXPECTED_ROUTES = [\"/metrics\", \"/debug/ghost\"]\n"
    found = lint_sources({"sptag_tpu/serve/http.py": server_src,
                          "sptag_tpu/serve/contract.py": contract_src},
                         select=["GL1006"])
    assert rules_of(found) == ["GL1006"]
    msgs = "\n".join(f.message for f in found)
    assert "/debug/extra" in msgs        # registered, not expected
    assert "/debug/ghost" in msgs        # expected, not registered
    assert len(found) == 2


def test_gl1006_matching_route_contract_clean():
    server_src = (
        "def handler(q):\n"
        "    return 200\n"
        "class Server:\n"
        "    def __init__(self):\n"
        "        self._routes = {\"/metrics\": handler}\n"
    )
    contract_src = "EXPECTED_ROUTES = [\"/metrics\"]\n"
    assert lint_sources({"sptag_tpu/serve/http.py": server_src,
                         "sptag_tpu/serve/contract.py": contract_src},
                        select=["GL1006"]) == []


def test_gl1001_verdict_produced_but_unregistered_flagged():
    src = (
        "TRIAGE_VERDICTS = (\"beam_budget\", \"unknown\")\n"
        "def classify_low_recall(sample):\n"
        "    return (\"rogue_verdict\", 0.5)\n"
    )
    found = lint_one(src, path="sptag_tpu/utils/qualmon.py",
                     select=["GL1001"])
    assert rules_of(found) == ["GL1001"]
    assert "rogue_verdict" in found[0].message


def test_gl1002_verdict_registered_but_never_returned_flagged():
    src = (
        "TRIAGE_VERDICTS = (\"beam_budget\", \"never_classified\")\n"
        "def classify_low_recall(sample):\n"
        "    return (\"beam_budget\", 0.5)\n"
    )
    found = lint_one(src, path="sptag_tpu/utils/qualmon.py",
                     select=["GL1002"])
    assert rules_of(found) == ["GL1002"]
    assert any("never_classified" in f.message for f in found)


def test_gl100x_silent_on_subpackage_scoped_lint():
    """The contract graph is a whole-package analysis — a scoped lint
    of one subpackage must not report phantom cross-subpackage edges
    (serve/ reads series utils/ publishes, docs rows name core/params
    specs)."""
    for sub in ("core", "serve", "utils"):
        root = os.path.join(REPO, "sptag_tpu", sub)
        if not os.path.isdir(root):
            continue
        unsup, _sup, _stale = lint_project(
            root, DEFAULT_BASELINE, select=["GL10"])
        assert unsup == [], "\n".join(f.format() for f in unsup)


def test_gl100x_registered_and_repo_clean_with_zero_waivers():
    """GL1001-1006 are registered; the repo's observability graph is
    fully closed (every consumer has a producer, every producer a
    consumer or doc, params match docs) with ZERO baseline entries —
    the ISSUE 18 acceptance bar."""
    for rule in ("GL1001", "GL1002", "GL1003", "GL1004", "GL1005",
                 "GL1006"):
        assert rule in ALL_RULES
    unsup, sup, _stale = lint_project(
        os.path.join(REPO, "sptag_tpu"), DEFAULT_BASELINE,
        select=["GL10"])
    assert unsup == [], "\n".join(f.format() for f in unsup)
    assert sup == []                     # nothing waived
    from tools.graftlint.baseline import load_baseline
    gl10_waivers = [s for s in load_baseline(DEFAULT_BASELINE)
                    if s.rule.startswith("GL10")]
    assert gl10_waivers == []            # zero GL10 baseline entries
