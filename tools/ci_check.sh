#!/usr/bin/env bash
# The whole local gate: graftlint (static) + tier-1 pytest (runtime).
# Mirrors what the driver runs; see docs/DESIGN.md §7.
#
#   tools/ci_check.sh                # lint + tier-1
#   tools/ci_check.sh --lint-only    # fast pre-commit check
set -euo pipefail

REPO="$(cd "$(dirname "${BASH_SOURCE[0]}")/.." && pwd)"
cd "$REPO"

# the full suite includes the GL7xx lock-order pass, the GL8xx
# guarded-by pass and the GL9xx device-program contract pass;
# `--select GL7` / `--select GL8` / `--select GL9` scope a rerun
echo "== graftlint (GL1xx-GL9xx) =="
python -m tools.graftlint sptag_tpu/

if [[ "${1:-}" == "--lint-only" ]]; then
    exit 0
fi

# the ISSUE 4 correctness gate, standalone and first: the segmented walk
# (and the scheduler built on it) must return bit-identical results to
# the monolithic walk — if this fails, nothing else about the beam
# numbers means anything
echo "== beam segmented-vs-monolithic parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_beam_segmented.py -q \
    -p no:cacheprovider -k "parity or segment_param"

# the ISSUE 5 observability gate, standalone: with FlightRecorder=off
# (the default) the serve tier's wire bytes stay byte-identical to the
# reference layout and the hot path performs zero recorder work
echo "== flight recorder off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_flightrec.py -q \
    -p no:cacheprovider -k "off_parity"

# the ISSUE 7 observability gate, standalone: with QualitySampleRate=0
# (the default) the serve tier's wire bytes stay byte-identical and the
# hot path performs one flag test per query — the quality monitor's
# analog of the flight-recorder parity contract
echo "== quality monitor off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_qualmon.py -q \
    -p no:cacheprovider -k "off_parity"

# the ISSUE 7 lint gate, standalone: quality gauge/counter names passed
# to qualmon must be string literals (GL606, the GL6xx cardinality
# family) — a dynamic name would grow the labeled exposition unbounded
echo "== GL606 quality-name lint (standalone) =="
python -m tools.graftlint sptag_tpu/ --select GL606

# the ISSUE 8 robustness gate, standalone: with every overload-defense
# knob at its default (AdmissionControl off, DeadlineMs 0, HedgeBudget
# 0, FaultInject empty) the serve tier's wire bytes stay byte-identical
# to the reference layout and the defense path performs zero work
echo "== overload defense off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_admission.py -q \
    -p no:cacheprovider -k "off_parity"

# the ISSUE 8 lint gate, standalone: the overload-defense modules'
# metric/flight-event names are literals (GL601/602/603 extend to the
# new modules with no new baseline entries)
echo "== GL601/602/603 overload-defense names (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_lint.py -q \
    -p no:cacheprovider -k "issue8"

# the ISSUE 9 robustness gate, standalone: with every mutation knob at
# its default (WalEnabled 0, DeltaShardCapacity 0, AutoRefineThreshold
# 0) the serve tier's wire bytes stay byte-identical and the mutation
# subsystem performs zero work
echo "== mutation knobs off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_mutation.py -q \
    -p no:cacheprovider -k "off_parity"

# the ISSUE 9 recovery drill, standalone: every injected storage-fault/
# crash point (mid-WAL append, mid-snapshot blob, pre-rename,
# post-rename) yields a loadable index containing exactly the acked
# writes, checksums verified — if this fails, the durability contract
# is broken and no mutation feature on top of it matters
echo "== crash-recovery drill (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_mutation.py -q \
    -p no:cacheprovider -k "crash_matrix or manifest or wal"

# the ISSUE 9 lint gate, standalone: persistence writes in core//io
# ride the atomic-write/WAL helpers (GL411, zero baseline entries)
echo "== GL411 persistence-path lint (standalone) =="
python -m tools.graftlint sptag_tpu/ --select GL411

# the ISSUE 10 observability gate, standalone: with HostProfHz=0 (the
# default) the serve tier's wire bytes stay byte-identical, the sampler
# thread is never started and the stage pins are one flag test
echo "== host profiler off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_hostprof.py -q \
    -p no:cacheprovider -k "off_parity"

# the ISSUE 10 regression sentinel, self-tested: identical artifacts
# pass; a doctored 20% loadgen-p99 regression fails with a table naming
# the regressed metric — if this breaks, the perf gate is asleep
echo "== benchdiff self-test (identity + doctored regression) =="
python -m tools.benchdiff tests/fixtures/bench_artifact.json \
    tests/fixtures/bench_artifact.json
python - <<'PYEOF'
import copy, json, os, subprocess, sys, tempfile
base = json.load(open("tests/fixtures/bench_artifact.json"))
cur = copy.deepcopy(base)
broot = base["parsed"] if isinstance(base.get("parsed"), dict) else base
croot = cur["parsed"] if isinstance(cur.get("parsed"), dict) else cur
broot["loadgen"] = {"qps_at_slo": 512.0, "p50_ms": 20.0, "p99_ms": 100.0}
croot["loadgen"] = {"qps_at_slo": 512.0, "p50_ms": 20.0, "p99_ms": 120.0}
d = tempfile.mkdtemp()
bp, cp = os.path.join(d, "b.json"), os.path.join(d, "c.json")
json.dump(base, open(bp, "w")); json.dump(cur, open(cp, "w"))
r = subprocess.run([sys.executable, "-m", "tools.benchdiff", bp, cp],
                   capture_output=True, text=True)
assert r.returncode == 1, \
    f"doctored regression must exit nonzero: rc={r.returncode}\n{r.stdout}"
assert "loadgen.p99_ms" in r.stdout and "REGRESSED" in r.stdout, r.stdout
print("benchdiff self-test OK (doctored -20% p99 headroom fails)")
PYEOF

# the ISSUE 10 lint gate, standalone: host-profiler stage names are
# string literals (GL607, the GL6xx cardinality family)
echo "== GL607 hostprof-stage lint (standalone) =="
python -m tools.graftlint sptag_tpu/ --select GL607

# the ISSUE 11 serving gate, standalone: with MeshServe at its default
# (off) a server over a mesh adapter produces byte-identical wire
# responses and never builds a mesh scheduler; the same module holds
# the merge-contract parity (in-mesh ids == socket fan-out + host
# merge over identical shard contents)
echo "== mesh serve off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_mesh_serve.py -q \
    -p no:cacheprovider -k "off_parity"

# the ISSUE 12 lint gate, standalone: guarded-by inference (GL801-805
# fixed or justified, GL806 plain-lock migration) — an unguarded write
# to epoch-swapped serving state is the bug class every later roadmap
# item (autotuner, tiered pipeline) would otherwise ship
echo "== GL8 guarded-by / race lint (standalone) =="
python -m tools.graftlint sptag_tpu/ --select GL8

# the ISSUE 12 runtime gate, standalone: with RaceSanitizer off (the
# default) the tracked hot classes are completely untouched and the
# serve tier's wire bytes stay byte-identical
echo "== race sanitizer off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_racesan.py -q \
    -p no:cacheprovider -k "off_parity"

# the ISSUE 12 armed smoke: mutation + epoch-swap + scheduler tests
# under SPTAG_RACESAN=1 — the conftest per-test probe fails any test
# that observes a data race, so a green run IS racesan.races == 0; the
# static/runtime guard cross-check rides in test_racesan.py
echo "== racesan-armed smoke (mutate/swap/scheduler, races must be 0) =="
env JAX_PLATFORMS=cpu SPTAG_RACESAN=1 python -m pytest \
    tests/test_mutation.py tests/test_concurrent.py \
    tests/test_beam_segmented.py tests/test_racesan.py -q \
    -p no:cacheprovider -m 'not slow'

# the ISSUE 13 perf gate, standalone: with BinnedTopK at its default
# (off) every engine resolves bins=0 and compiles the byte-identical
# exact kernels, and a served response matches the reference wire
# layout; the same module holds the binned-on parity contracts
# (segmented/monolithic bit-parity, scheduler ids, mesh ids) and the
# recall-floor property tests of the bin-reduction primitive
echo "== binned top-k off: parity + golden bytes (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_binned_topk.py -q \
    -p no:cacheprovider -k "off_parity or parity"

# the ISSUE 14 capacity gate, standalone: with CascadeSearch at its
# default (off) no cascade state is ever built, FLAT results and served
# wire bytes stay byte-identical, and the parity contracts hold —
# host-tier fp re-rank bit-identical to device-resident, host-tier
# beam segmented/scheduler parity, mesh scheduler-vs-monolithic ids
echo "== cascade off: parity + golden bytes (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_cascade.py -q \
    -p no:cacheprovider -k "off_parity or parity"

# the ISSUE 15 observability gate, standalone: with the serving
# timeline, SLO engine and canary prober at their defaults (all off)
# the serve tier's wire bytes stay byte-identical, no sampler/prober
# thread exists and the timeline counters read zero
echo "== timeline/SLO/canary off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_timeline.py -q \
    -p no:cacheprovider -k "off_parity"

# the ISSUE 15 lint gate, standalone: timeline/SLO/canary series names
# are string literals (GL608, the GL6xx cardinality family) with ZERO
# baseline entries — a dynamic series name would grow the bounded
# time-series store without limit
echo "== GL608 timeline-series name lint (standalone) =="
python -m tools.graftlint sptag_tpu/ --select GL608

# the ISSUE 16 lint gate, standalone: the device-program contract pass
# (GL901 recompile hazards, GL902 hot-path transfers, GL903/904
# shard_map spec + collective axis contracts, GL905 never-assigned
# attribute reads with a ZERO-entry baseline, GL906 dead-telemetry
# handlers)
echo "== GL9 device-program contract lint (standalone) =="
python -m tools.graftlint sptag_tpu/ --select GL9

# the ISSUE 16 runtime gate, standalone: with TraceSanitizer off (the
# default outside the suite — SPTAG_TRACESAN= empty defeats conftest's
# suite-wide arming) jax's ArrayImpl readback dunders are untouched and
# the serve tier's wire bytes stay byte-identical
echo "== trace sentinel off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu SPTAG_TRACESAN= python -m pytest \
    tests/test_tracesan.py -q -p no:cacheprovider -k "off_parity"

# the ISSUE 16 armed smoke: scheduler + mesh-serve + sentinel tests
# under SPTAG_TRACESAN=1 — the conftest per-test probe fails any test
# whose hot sections observed an implicit device->host transfer, so a
# green run IS tracesan.transfers == 0; the static/runtime contract
# cross-check rides in test_tracesan.py
echo "== tracesan-armed smoke (scheduler/mesh, transfers must be 0) =="
env JAX_PLATFORMS=cpu SPTAG_TRACESAN=1 python -m pytest \
    tests/test_beam_segmented.py tests/test_mesh_serve.py \
    tests/test_tracesan.py -q -p no:cacheprovider -m 'not slow'

# the ISSUE 17 serving gate, standalone: with Controller=0 and no
# AutotuneConfig (the defaults) the serve tier's wire bytes stay
# byte-identical, no controller object or audit entry exists and the
# decision counter reads zero — the closed loop is provably open when
# not asked for
echo "== controller off: serve byte parity (standalone) =="
env JAX_PLATFORMS=cpu python -m pytest tests/test_controller.py -q \
    -p no:cacheprovider -k "off_parity"

# the ISSUE 17 lint gate, standalone: controller decision-rule names
# passed to ctlaudit.record are string literals (GL609, the GL6xx
# cardinality family) with ZERO baseline entries — a dynamic rule name
# would make the bounded audit ring unsearchable
echo "== GL609 controller audit-rule lint (standalone) =="
python -m tools.graftlint sptag_tpu/ --select GL609

# the ISSUE 18 contract-graph gate, standalone: the GL10xx
# observability/config dataflow pass — every consumed metric/series/
# route/param has a producer (GL1001), every producer a consumer or a
# doc mention (GL1002), label sets agree (GL1003), params match
# docs/PARAMETERS.md (GL1004/1005), routes match EXPECTED_ROUTES
# (GL1006) — with ZERO baseline entries
echo "== GL10 observability contract graph (standalone) =="
python -m tools.graftlint sptag_tpu/ --select GL10

# the ISSUE 18 runtime gate, standalone: boot the armed server+
# aggregator scenario in-process, scrape /metrics + every debug route +
# the timeline, and diff the live exposition against the static
# ObsModel in BOTH directions — a name published but unmodeled, or
# modeled/consumed but never emitted, fails here
echo "== schema dump: live exposition vs static ObsModel =="
env JAX_PLATFORMS=cpu python -m tools.graftlint --schema-dump

echo "== tier-1 pytest (CPU backend) =="
exec env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
    --continue-on-collection-errors -p no:cacheprovider
