"""Device capability registry + roofline arithmetic.

The second pillar of the roofline-observability subsystem (ISSUE 6):
`capability()` answers "what are THIS device's peak FLOP/s and memory
bandwidth", so every achieved-GFLOP/s number the cost ledger
(utils/costmodel.py) produces can be stated as a fraction of peak — the
metric the TPU-KNN line of work (arXiv:2206.14286) and classic
hardware-conscious ANN (arXiv:1712.02912) both report.

Two sources, in order:

* **Static table** for known TPU generations, keyed by
  ``jax.devices()[0].device_kind`` substrings.  Numbers are per-chip
  public spec-sheet peaks; f32 matmul on the MXU runs the multi-pass
  bf16 algorithm at ~1/4 the bf16 rate, which is the convention the
  table encodes (v5e: ``49e12`` — the constant lives HERE, once, with
  provenance).
* **Measured micro-probe** for cpu/gpu/unknown kinds: a timed f32
  matmul (compute peak) and a timed device-to-device copy (memory
  bandwidth), disk-cached keyed on (device kind, jax version) with an
  age gate — the PR-4 probe-cache pattern (bench tpu_probe.json), so a
  bench or serve process pays the ~1 s probe once per machine, not per
  run.  The probe is strictly opt-in (`RooflineProbe` parameter /
  ``probe=True``): importing this module or resolving a TPU capability
  never runs device work beyond reading ``device_kind``.

A capability of ``None`` peaks is a legal answer (unknown device, probe
disabled): consumers publish achieved GFLOP/s / GB/s unconditionally and
the %-of-peak gauges only when a peak exists.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import logging
import os
import threading
import time
from typing import Optional

log = logging.getLogger(__name__)

#: probe-cache age limit (seconds); 0 disables the disk cache
PROBE_CACHE_S = float(os.environ.get("SPTAG_TPU_ROOFLINE_CACHE_S",
                                     7 * 24 * 3600.0))


@dataclasses.dataclass(frozen=True)
class Capability:
    """Per-device peaks.  ``None`` = unknown on that axis."""

    device_kind: str
    platform: str
    peak_flops_f32: Optional[float]      # FLOP/s
    peak_flops_bf16: Optional[float]     # FLOP/s (matmul dtype peak)
    hbm_gbps: Optional[float]            # bytes/s / 1e9
    source: str                          # "table" | "probe" | "none"
    #: int8 matmul OP/s — 2x bf16 on generations with a doubled int8
    #: path (v5e/v5p/v6e); None falls back to the bf16 peak.  Using the
    #: bf16 peak for int8 on those chips would OVERSTATE %-of-peak ~2x,
    #: violating the never-fabricate-utilization contract.
    peak_flops_int8: Optional[float] = None

    def peak_flops(self, dtype: str = "f32") -> Optional[float]:
        if dtype == "int8":
            return self.peak_flops_int8 or self.peak_flops_bf16
        if dtype == "bf16":
            return self.peak_flops_bf16
        return self.peak_flops_f32

    def pct_of_peak(self, achieved_flops_s: float, achieved_bytes_s: float,
                    dtype: str = "f32") -> Optional[float]:
        """Roofline utilization: the achieved fraction of whichever
        resource the kernel is USING harder (max of compute and
        bandwidth fractions), in percent.  None when no peak is known."""
        fracs = []
        pf = self.peak_flops(dtype)
        if pf:
            fracs.append(achieved_flops_s / pf)
        if self.hbm_gbps:
            fracs.append(achieved_bytes_s / (self.hbm_gbps * 1e9))
        return 100.0 * max(fracs) if fracs else None


# Public spec-sheet peaks per chip (bf16 matmul TFLOP/s, HBM GB/s, int8
# multiplier — 2.0 where the generation ships a doubled int8 path);
# f32 = bf16/4 (the MXU's multi-pass f32-accurate algorithm).  Substring
# match against device_kind, FIRST match wins — order matters ("v5p"
# before "v5", "v5 lite"/"v5e" before "v5").
_TPU_TABLE = (
    ("v6e", 918e12, 1640.0, 2.0), ("v6 lite", 918e12, 1640.0, 2.0),
    ("v5e", 197e12, 819.0, 2.0), ("v5 lite", 197e12, 819.0, 2.0),
    ("v5p", 459e12, 2765.0, 2.0), ("v5", 459e12, 2765.0, 2.0),
    ("v4", 275e12, 1228.0, 1.0),
    ("v3", 123e12, 900.0, 1.0),
    ("v2", 45e12, 700.0, 1.0),
)


def _table_lookup(device_kind: str, platform: str) -> Optional[Capability]:
    if platform != "tpu":
        return None
    kind = device_kind.lower()
    for sub, bf16, gbps, i8_mult in _TPU_TABLE:
        if sub in kind:
            return Capability(device_kind, platform, bf16 / 4.0, bf16,
                              gbps, "table", peak_flops_int8=bf16 * i8_mult)
    return None


# ---------------------------------------------------------------------------
# measured micro-probe (cpu/gpu/unknown fallback)
# ---------------------------------------------------------------------------

def _cache_path() -> str:
    d = os.environ.get("SPTAG_TPU_ROOFLINE_CACHE",
                       os.path.join("/tmp", "sptag_tpu_roofline"))
    return d


def _cache_key(device_kind: str) -> str:
    import jax

    return hashlib.sha256(
        f"{device_kind}|{jax.__version__}".encode()).hexdigest()[:16]


def _load_probe_cache(device_kind: str) -> Optional[dict]:
    if PROBE_CACHE_S <= 0:
        return None
    path = os.path.join(_cache_path(), f"probe-{_cache_key(device_kind)}.json")
    try:
        if time.time() - os.path.getmtime(path) > PROBE_CACHE_S:
            return None
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _save_probe_cache(device_kind: str, outcome: dict) -> None:
    if PROBE_CACHE_S <= 0:
        return
    d = _cache_path()
    try:
        os.makedirs(d, exist_ok=True)
        tmp = os.path.join(d, f".tmp-{os.getpid()}")
        with open(tmp, "w") as f:
            json.dump(outcome, f)
        os.replace(tmp,
                   os.path.join(d, f"probe-{_cache_key(device_kind)}.json"))
    except OSError:
        pass                     # cache is an optimization, never a failure


def _run_probe() -> dict:
    """~1 s of device work: peak f32 matmul rate + copy bandwidth.
    Small enough to run inside a test suite; honest enough to rank
    compute- vs bandwidth-bound kernels on an unknown machine."""
    import jax
    import jax.numpy as jnp

    n = 1024
    a = jnp.ones((n, n), jnp.float32)
    mm = jax.jit(lambda x, y: x @ y)
    jax.block_until_ready(mm(a, a))                       # compile
    reps, best = 3, 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(mm(a, a))
        dt = time.perf_counter() - t0
        best = max(best, (2.0 * n * n * n) / dt)
    big = jnp.ones((32 << 20) // 4, jnp.float32)          # 32 MB
    cp = jax.jit(lambda x: x + 1.0)                       # read + write
    jax.block_until_ready(cp(big))
    bw = 0.0
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(cp(big))
        dt = time.perf_counter() - t0
        bw = max(bw, 2.0 * big.nbytes / dt)
    return {"peak_flops_f32": best, "hbm_gbps": bw / 1e9}


def _probe(device_kind: str, platform: str) -> Optional[Capability]:
    cached = _load_probe_cache(device_kind)
    if cached is None:
        try:
            cached = _run_probe()
        except Exception as e:                            # noqa: BLE001
            log.warning("roofline micro-probe failed: %r", e)
            return None
        _save_probe_cache(device_kind, cached)
    return Capability(device_kind, platform,
                      cached.get("peak_flops_f32"),
                      cached.get("peak_flops_f32"),   # no native bf16 peak
                      cached.get("hbm_gbps"), "probe")


# ---------------------------------------------------------------------------
# public surface
# ---------------------------------------------------------------------------

_lock = threading.Lock()
_cached_cap: Optional[Capability] = None
_cached_probe_flag: Optional[bool] = None


def capability(probe: bool = False) -> Capability:
    """The default device's capability.  `probe=True` permits the
    disk-cached measured fallback when the static table has no entry
    (the `RooflineProbe` parameter); with `probe=False` unknown devices
    get a ``source="none"`` capability with None peaks.  The result is
    cached per process (the device does not change under us)."""
    global _cached_cap, _cached_probe_flag
    with _lock:
        # a TABLE capability is probe-independent; a PROBED one is only
        # valid for probe=True — RooflineProbe=0 must actually turn
        # %-of-peak off on unknown kinds (the documented contract), so a
        # probe-flag downgrade re-resolves to the table/none answer
        if _cached_cap is not None and (
                _cached_probe_flag == probe
                or _cached_cap.source == "table"):
            return _cached_cap
    import jax

    dev = jax.devices()[0]
    kind = getattr(dev, "device_kind", "unknown")
    platform = getattr(dev, "platform", "unknown")
    cap = _table_lookup(kind, platform)
    if cap is None and probe:
        cap = _probe(kind, platform)
    if cap is None:
        cap = Capability(kind, platform, None, None, None, "none")
    with _lock:
        _cached_cap, _cached_probe_flag = cap, probe
    return cap


def reset() -> None:
    """Drop the per-process capability cache (test isolation)."""
    global _cached_cap, _cached_probe_flag
    with _lock:
        _cached_cap = None
        _cached_probe_flag = None


def roofline_row(family: str, per_query_flops: float,
                 per_query_bytes: float, qps: float,
                 cap: Optional[Capability] = None,
                 dtype: str = "f32") -> dict:
    """One bench/report roofline row: achieved rates from a measured QPS
    and the ledger's per-query work, peak fractions when peaks exist."""
    achieved_f = qps * per_query_flops
    achieved_b = qps * per_query_bytes
    row = {
        "family": family,
        "flops_per_query": int(per_query_flops),
        "hbm_bytes_per_query": int(per_query_bytes),
        "achieved_gflops": round(achieved_f / 1e9, 3),
        "achieved_gbps": round(achieved_b / 1e9, 3),
    }
    if cap is not None:
        pf = cap.peak_flops(dtype)
        if pf:
            row["pct_peak_flops"] = round(100.0 * achieved_f / pf, 4)
        if cap.hbm_gbps:
            row["pct_peak_hbm"] = round(
                100.0 * achieved_b / (cap.hbm_gbps * 1e9), 4)
        fpcts = [row.get("pct_peak_flops"), row.get("pct_peak_hbm")]
        fpcts = [p for p in fpcts if p is not None]
        if fpcts:
            row["pct_peak"] = max(fpcts)
            row["bound"] = ("compute"
                            if row.get("pct_peak_flops", -1.0)
                            >= row.get("pct_peak_hbm", -1.0)
                            else "bandwidth")
    return row
