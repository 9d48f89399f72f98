"""Runtime half of the GL10xx observability-contract suite.

The static fixtures live in test_lint.py; this file covers the pieces
that need a live process:

* ``metrics`` cross-kind registration guard (``MetricKindError``) — one
  name must never resolve to two instrument kinds, or the static model
  (and every Prometheus consumer) splits on it;
* the schema dump: boot the armed server+aggregator scenario, scrape
  every exposition surface, and diff live names against the static
  ObsModel in BOTH directions.  This is the e2e proof that the lint's
  dataflow graph matches what the process actually publishes.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from sptag_tpu.utils import metrics  # noqa: E402


# ---------------------------------------------------------------------------
# metrics: one name, one instrument kind
# ---------------------------------------------------------------------------

def test_cross_kind_registration_raises():
    metrics.counter("obsgraphtest.kind_clash")
    with pytest.raises(metrics.MetricKindError):
        metrics.gauge("obsgraphtest.kind_clash")
    with pytest.raises(metrics.MetricKindError):
        metrics.histogram("obsgraphtest.kind_clash")


def test_same_kind_reregistration_is_idempotent():
    c1 = metrics.counter("obsgraphtest.same_kind")
    c2 = metrics.counter("obsgraphtest.same_kind")
    assert c1 is c2


def test_cross_kind_raises_through_convenience_helpers():
    metrics.inc("obsgraphtest.helper_clash", 1)
    with pytest.raises(metrics.MetricKindError):
        metrics.set_gauge("obsgraphtest.helper_clash", 2.0)
    with pytest.raises(metrics.MetricKindError):
        metrics.observe("obsgraphtest.helper_clash", 3.0)


# ---------------------------------------------------------------------------
# schema dump: live exposition == static model, both directions
# ---------------------------------------------------------------------------

def test_schema_dump_live_matches_static_model():
    from tools.graftlint import schemadump

    diff = schemadump.run_schema_dump(
        root=os.path.join(REPO, "sptag_tpu"), verbose=False)
    assert diff.clean, diff.format()
