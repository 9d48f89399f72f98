"""graftlint --schema-dump — the GL10xx runtime complement.

Same contract as locksan (GL8xx) and tracesan (GL9xx): the static pass
proves the producer/consumer name graph is closed over the *source*;
this harness proves it is closed over the *running system*.  It boots a
search server + aggregator in-process with every telemetry knob armed
(timeline, canary, SLO objectives, qualmon shadow audit, flight
recorder, metrics HTTP), drives real client traffic plus canary probes
through both tiers, forces a timeline tick, scrapes /metrics and every
registered /debug route, and then diffs the live exposition against the
static ObsModel in BOTH directions:

* live → model: every metric, family, timeline series, flight-recorder
  kind, and HTTP route the armed system actually exposes must be
  modeled (a dynamically minted name the static harvest cannot see is
  exactly how the `iter_cost1` gflops attribution died silently);
* model → live: every name a static *consumer* reads — the SLO
  objective sources, the controller inputs — must actually receive
  data in the armed scenario (the PR 15 bug: the SLO engine read
  `aggregator.requests.rate`, which no live tick ever produced), plus
  a curated must-emit core of the serve path; and every statically
  registered route must answer the scrape.

`python -m tools.graftlint --schema-dump` runs it standalone (exit 0 =
empty diff both directions); tests/test_obsgraph.py ships the same
check as a tier-1 test so name drift cannot land.
"""

from __future__ import annotations

import json
import re
import sys
import threading
import time
from typing import Dict, List, Optional, Set, Tuple

#: routes whose handler legitimately answers non-200 in the armed
#: harness (no device traces recorded -> 404); liveness = "the handler
#: ran and answered", not "content exists"
_NON_200_OK = {"/debug/devicetrace"}

#: timeline keys the harness itself mints (test-local series)
_HARNESS_PREFIX = "schemadump."


class SchemaDiff:
    """The two-direction diff result."""

    def __init__(self) -> None:
        self.live_unmodeled: List[str] = []   # live name, no static producer
        self.model_unemitted: List[str] = []  # static must-emit, not live

    @property
    def clean(self) -> bool:
        return not self.live_unmodeled and not self.model_unemitted

    def format(self) -> str:
        lines = []
        for item in self.live_unmodeled:
            lines.append(f"live-but-unmodeled: {item}")
        for item in self.model_unemitted:
            lines.append(f"modeled-but-never-emitted: {item}")
        return "\n".join(lines)


def _http_get(port: int, path: str) -> Tuple[int, str]:
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=15)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read().decode()
    conn.close()
    return resp.status, body


class _LoopThread(threading.Thread):
    """Standalone copy of tests/conftest.py::ServerThread — this module
    must run without tests/ on sys.path.  The stored boot-task
    reference is load-bearing: see the conftest comment."""

    def __init__(self, server) -> None:
        super().__init__(daemon=True,
                         name=f"schemadump-loop-{type(server).__name__}")
        self.server = server
        self.addr: Optional[Tuple[str, int]] = None
        self.loop = None
        self._ready = threading.Event()

    def run(self) -> None:
        import asyncio

        self.loop = asyncio.new_event_loop()
        asyncio.set_event_loop(self.loop)

        async def boot():
            self.addr = await self.server.start("127.0.0.1", 0)
            self._ready.set()

        self._boot_task = self.loop.create_task(boot())
        self.loop.run_forever()

    def wait_ready(self, timeout: float = 60.0) -> Tuple[str, int]:
        if not self._ready.wait(timeout):
            raise RuntimeError("server failed to boot within %ss" % timeout)
        return self.addr

    def stop(self) -> None:
        import asyncio

        if self.loop is None:
            return
        fut = asyncio.run_coroutine_threadsafe(self.server.stop(),
                                               self.loop)
        try:
            fut.result(timeout=10)
        except Exception:                                # noqa: BLE001
            pass
        self.loop.call_soon_threadsafe(self.loop.stop)
        self.join(timeout=10)


def _wait(predicate, deadline_s: float, interval_s: float = 0.05) -> bool:
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(interval_s)
    return False


def _strip_label(series_key: str) -> str:
    return series_key.split("{", 1)[0]


def _base_metric(series_key: str) -> str:
    """Timeline derivation key -> its base registry metric name."""
    name = _strip_label(series_key)
    for suffix in (".rate", ".p50_ms", ".p99_ms"):
        if name.endswith(suffix):
            return name[: -len(suffix)]
    return name


def collect_live(metrics_mod, timeline_mod, flightrec_mod, families
                 ) -> Dict[str, Set[str]]:
    """Structured live-name collection — the dotted-name surfaces the
    static model speaks, not the lossy Prometheus rendering."""
    snap = metrics_mod.snapshot()
    return {
        "counters": set(snap["counters"]),
        "gauges": set(snap["gauges"]),
        "histograms": set(snap["histograms"]),
        "families": {fam.name for fam in families},
        "series": set(timeline_mod.series_names()),
        "flight_kinds": {e["kind"] for e in flightrec_mod.collect()},
    }


def diff_live_vs_model(live: Dict[str, Set[str]], model,
                       live_routes: Dict[str, int]) -> SchemaDiff:
    """Both-direction diff of a live collection against an ObsModel.
    `live_routes` maps scraped route path -> HTTP status."""
    diff = SchemaDiff()

    def modeled_metric(name: str, kind: str) -> bool:
        # xla.backend_compile[label] etc. resolve through prefixes
        return kind in model.metric_kinds(name) or \
            model.matches_prefix(name)

    for kind_key, kind in (("counters", "counter"), ("gauges", "gauge"),
                           ("histograms", "histogram")):
        for name in sorted(live[kind_key]):
            if name.startswith(_HARNESS_PREFIX):
                continue
            if not modeled_metric(name, kind):
                diff.live_unmodeled.append(f"{kind} `{name}`")
    for name in sorted(live["families"]):
        if name not in model.families and not model.matches_prefix(name):
            diff.live_unmodeled.append(f"family `{name}`")
    bare = model.bare_series()
    for key in sorted(live["series"]):
        base = _strip_label(key)
        if base.startswith(_HARNESS_PREFIX):
            continue
        if base in bare or base in model.families \
                or base in model.timeline or model.matches_prefix(base):
            continue
        # derived keys (x.rate / x.p50_ms / x.p99_ms) of modeled metrics
        if model.metric_kinds(_base_metric(key)) \
                or model.matches_prefix(_base_metric(key)):
            continue
        diff.live_unmodeled.append(f"timeline series `{key}`")
    for kind in sorted(live["flight_kinds"]):
        if kind not in model.flight_kinds:
            diff.live_unmodeled.append(f"flightrec kind `{kind}`")
    for path in sorted(live_routes):
        if path not in model.routes:
            diff.live_unmodeled.append(f"route `{path}`")

    # ---- model -> live ---------------------------------------------------
    # every statically harvested timeline READ (the SLO objective
    # sources + controller inputs) must have received live data — this
    # direction is the PR 15 regression test
    for name in sorted({n for n, _site in model.timeline_reads}):
        if name not in live["series"]:
            diff.model_unemitted.append(
                f"consumed timeline series `{name}` (an SLO/controller "
                "source) never received a live point")
    # curated must-emit core of the armed serve path
    for name, kind_key in (("server.requests", "counters"),
                           ("server.responses", "counters"),
                           ("canary.probes", "counters"),
                           ("aggregator.requests", "counters"),
                           ("quality.samples", "counters"),
                           ("server.request", "histograms"),
                           ("aggregator.request", "histograms")):
        if name not in live[kind_key]:
            diff.model_unemitted.append(f"metric `{name}`")
    for fam in ("canary.recall", "slo.state", "flight.recorded",
                "quality.recall_at_k"):
        if fam not in live["families"]:
            diff.model_unemitted.append(f"family `{fam}`")
    for kind in ("request", "execute", "fanout", "merge"):
        if kind not in live["flight_kinds"]:
            diff.model_unemitted.append(f"flightrec kind `{kind}`")
    # every statically registered route answered the scrape
    for path in sorted(model.routes):
        status = live_routes.get(path)
        if status is None:
            diff.model_unemitted.append(f"route `{path}` never scraped")
        elif status != 200 and path not in _NON_200_OK:
            diff.model_unemitted.append(
                f"route `{path}` answered HTTP {status}")
    return diff


def run_schema_dump(root: str = "sptag_tpu",
                    verbose: bool = True) -> SchemaDiff:
    """Boot the armed two-tier scenario, scrape, diff.  Callers own
    process-wide telemetry state: this resets metrics/timeline/
    flightrec on entry (same convention as the locksan/tracesan
    harnesses)."""
    import tempfile

    import numpy as np

    import sptag_tpu as sp
    from sptag_tpu.serve.aggregator import (AggregatorContext,
                                            AggregatorService,
                                            RemoteServer)
    from sptag_tpu.serve.client import AnnClient
    from sptag_tpu.serve.server import SearchServer
    from sptag_tpu.serve.service import ServiceContext, ServiceSettings
    from sptag_tpu.utils import flightrec, metrics, qualmon, timeline

    from tools.graftlint import obsgraph
    from tools.graftlint.core import Project

    model = obsgraph.build_model(Project.from_tree(root))

    metrics.reset()
    timeline.reset()
    flightrec.reset()
    flightrec.configure(enabled=True)

    rng = np.random.default_rng(0)
    data = rng.standard_normal((60, 8)).astype(np.float32)
    idx = sp.create_instance("FLAT", "Float")
    idx.set_parameter("DistCalcMethod", "L2")
    idx.build(data)

    ctx = ServiceContext(ServiceSettings(default_max_result=5,
                                         canary_probes=4,
                                         metrics_port=-1))
    ctx.add_index("main", idx)
    server = SearchServer(ctx, batch_window_ms=1.0,
                          timeline_interval_ms=50.0,
                          canary_interval_ms=30.0,
                          quality_sample_rate=1.0)
    ts = _LoopThread(server)
    ts.start()
    diff = SchemaDiff()
    tg = client = None
    probe_file = tempfile.NamedTemporaryFile(
        mode="w", suffix=".txt", delete=False)
    try:
        hs, ps = ts.wait_ready(60)
        probe_file.write("$resultnum:3 " + "|".join(
            repr(float(x)) for x in data[7]) + "\n")
        probe_file.close()
        agg_ctx = AggregatorContext(
            search_timeout_s=30.0, metrics_port=-1,
            flight_recorder=True,
            timeline_interval_ms=100.0,
            slo_p99_ms=500.0, slo_availability_target=0.99,
            slo_fast_window_s=1.0, slo_slow_window_s=2.5,
            canary_interval_ms=50.0,
            canary_probe_file=probe_file.name)
        agg_ctx.servers = [RemoteServer(hs, ps)]
        agg = AggregatorService(agg_ctx)
        tg = _LoopThread(agg)
        tg.start()
        tg.wait_ready(60)

        # real (non-canary) traffic: qualmon samples only live queries
        client = AnnClient(hs, ps, timeout_s=20.0)
        client.connect()
        for i in range(4):
            q = "|".join(repr(float(x)) for x in data[3 + i])
            client.search(q)
        qualmon.drain()

        # both tiers' canaries must have probed, and at least one live
        # qualmon sample must have landed, before the scrape
        _wait(lambda: metrics.counter_value("canary.probes") >= 4
              and metrics.counter_value("quality.samples") >= 1, 30.0)
        _wait(lambda: (agg._canary is not None
                       and agg._canary.snapshot()["indexes"]
                       .get("aggregator", {}).get("probes", 0) > 0), 30.0)
        # two deterministic ticks so counter rates and family series
        # exist regardless of the samplers' own phase
        timeline.sample_now()
        time.sleep(0.25)
        timeline.sample_now()

        live_routes: Dict[str, int] = {}
        for http in (server._metrics_http, agg._metrics_http):
            if http is None:
                continue
            for path in http.routes():
                status, _body = _http_get(http.port, path)
                # prefer a 200 from either tier (e.g. /debug/slo is
                # only armed on the aggregator)
                prev = live_routes.get(path)
                if prev is None or (prev != 200 and status == 200):
                    live_routes[path] = status

        live = collect_live(metrics, timeline, flightrec,
                            metrics.collect_families())
        diff = diff_live_vs_model(live, model, live_routes)
    finally:
        if client is not None:
            client.close()
        if tg is not None:
            tg.stop()
        ts.stop()
        flightrec.configure(enabled=False)
        timeline.configure(enabled=False)

    if verbose:
        if diff.clean:
            print("schema-dump: live exposition and static ObsModel "
                  "agree (both directions)")
        else:
            print(diff.format())
            print(f"schema-dump: {len(diff.live_unmodeled)} live-but-"
                  f"unmodeled, {len(diff.model_unemitted)} modeled-but-"
                  "never-emitted")
    return diff


def main(roots: List[str]) -> int:
    root = roots[0] if roots else "sptag_tpu"
    try:
        diff = run_schema_dump(root)
    except Exception as e:                               # noqa: BLE001
        print(f"schema-dump: harness failed: {e!r}", file=sys.stderr)
        return 2
    return 0 if diff.clean else 1
