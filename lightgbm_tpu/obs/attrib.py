"""Roofline attribution: join the static FLOP/byte ledger with the
fenced phase spans and a per-device peak table.

The per-phase question ROADMAP's perf frontier needs answered
continuously — "is this phase compute- or memory-bound, and how far
from peak?" — computed as ``perf.*`` keys from three ingredients that
already exist separately:

- ``flops.total`` / ``flops.hbm_bytes`` counters (obs/flops.py ledger,
  recorded per iteration by ``ObsSession.record_flops``),
- ``train.phase_seconds{phase=...}`` histograms (fenced spans: wall
  time attributed to the phase that queued the work),
- the peak table below (FLOP/s and HBM bandwidth, so the roofline has
  both axes).

``perf_summary`` is a pure function of a metrics snapshot, so the
static keys (flops, hbm_bytes) inherit the snapshot's dp == serial
determinism and the whole join is unit-testable without a device.
Surfaced in ``Booster.telemetry_snapshot()`` and the serve ``/metrics``
endpoint.
"""

from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

# bf16/f32 MXU peak FLOP/s and HBM bandwidth (bytes/s) per chip, by
# device-kind substring.  Bandwidth from the public TPU system specs (v4 1228 GB/s, v5e
# 819 GB/s, v5p 2765 GB/s, v6e 1640 GB/s).  Unknown kinds report raw
# FLOP/s with no MFU/verdict — or the caller pins peaks via the
# ``telemetry_peak_flops`` / ``telemetry_peak_hbm_gbs`` params.
PEAKS: Dict[str, Tuple[float, float]] = {
    "v5lite": (197e12, 819e9), "v5e": (197e12, 819e9),
    "v5p": (459e12, 2765e9),
    "v4": (275e12, 1228e9),
    "v6e": (918e12, 1640e9), "v6lite": (918e12, 1640e9),
}


def device_peaks(devices=None) -> Tuple[Optional[float], Optional[float]]:
    """(peak FLOP/s, peak HBM bytes/s) for the first visible device,
    (None, None) when the kind is unknown (CPU, new TPU gens)."""
    if devices is None:
        try:
            import jax
            devices = jax.devices()
        except Exception:
            return None, None
    if not devices:
        return None, None
    kind = getattr(devices[0], "device_kind", "").lower().replace(" ", "")
    for key, peaks in PEAKS.items():
        if key in kind:
            return peaks
    return None, None


def config_peaks(config) -> Tuple[Optional[float], Optional[float]]:
    """Peaks from the ``telemetry_peak_flops`` / ``telemetry_peak_hbm_gbs``
    params (0 = auto), falling back to :func:`device_peaks` — the
    escape hatch for device kinds the table does not know."""
    pf = float(getattr(config, "telemetry_peak_flops", 0.0) or 0.0) or None
    pb = float(getattr(config, "telemetry_peak_hbm_gbs", 0.0) or 0.0)
    pb = pb * 1e9 if pb else None
    if pf is None or pb is None:
        dpf, dpb = device_peaks()
        pf = pf if pf is not None else dpf
        pb = pb if pb is not None else dpb
    return pf, pb


def roofline(flops: float, hbm_bytes: float, seconds: float,
             peak_flops: Optional[float] = None,
             peak_bw: Optional[float] = None) -> Dict[str, object]:
    """Achieved rates + roofline verdict for one phase.

    ``bound`` compares the workload's arithmetic intensity (FLOPs per
    HBM byte) against the machine's ridge point (peak FLOP/s / peak
    bytes/s): above the ridge the phase can saturate the MXU before
    the memory system (compute-bound), below it HBM bandwidth is the
    ceiling (memory-bound).  Requires both peaks; ``mfu`` requires the
    FLOP peak; achieved rates require measured seconds."""
    out: Dict[str, object] = {}
    if seconds and seconds > 0:
        out["flops_per_s"] = flops / seconds
        out["hbm_bytes_per_s"] = hbm_bytes / seconds
        if peak_flops:
            out["mfu"] = flops / seconds / peak_flops
        if peak_bw:
            out["hbm_util"] = hbm_bytes / seconds / peak_bw
    if hbm_bytes and hbm_bytes > 0:
        intensity = flops / hbm_bytes
        out["intensity_flops_per_byte"] = round(intensity, 3)
        if peak_flops and peak_bw:
            out["bound"] = ("compute" if intensity >= peak_flops / peak_bw
                            else "memory")
    return out


_FLOPS_KEY = re.compile(r"^flops\.(total|hbm_bytes)\{(.*)\}$")


def _labels(body: str) -> Dict[str, str]:
    return dict(p.split("=", 1) for p in body.split(",") if "=" in p)


def perf_summary(snap: Dict[str, dict],
                 peaks: Tuple[Optional[float], Optional[float]]
                 = (None, None)) -> Dict[str, object]:
    """Derive the ``perf.*`` key block from a metrics snapshot.

    Reads the ``flops.total{phase=..,site=..}`` /
    ``flops.hbm_bytes{...}`` counters and the
    ``train.phase_seconds{phase=..}`` histograms; emits, per phase and
    for the total:

    - ``perf.<phase>.flops`` / ``.hbm_bytes`` — cumulative static
      accounting (deterministic, dp == serial),
    - ``.seconds`` — fenced wall time from the phase spans,
    - ``.flops_per_s`` / ``.hbm_bytes_per_s`` / ``.mfu`` /
      ``.hbm_util`` / ``.intensity_flops_per_byte`` /
      ``.bound`` (compute|memory) — the roofline join (present when
      the required timing/peaks exist).
    """
    pf, pb = peaks or (None, None)
    phases: Dict[str, Dict[str, float]] = {}
    sites: Dict[str, Dict[str, float]] = {}
    for key, rec in snap.items():
        m = _FLOPS_KEY.match(key)
        if not m or not isinstance(rec, dict):
            continue
        labels = _labels(m.group(2))
        ph = labels.get("phase", "other")
        kind = "flops" if m.group(1) == "total" else "hbm_bytes"
        site = labels.get("site")
        if site:
            ds = sites.setdefault(site, {"flops": 0.0, "hbm_bytes": 0.0})
            ds[kind] += float(rec.get("value", 0.0))
        if ph == "pad":
            # MXU lane-pad MACs (obs/flops.hist_pad_flops_bytes): real
            # hardware cycles but not useful work — surfaced per-site
            # (perf.hist_pad.*) yet EXCLUDED from phase and total
            # aggregation so perf.*.mfu never counts channel padding
            # as achieved FLOPs
            continue
        d = phases.setdefault(ph, {"flops": 0.0, "hbm_bytes": 0.0})
        d[kind] += float(rec.get("value", 0.0))
    if not phases:
        return {}
    out: Dict[str, object] = {}
    # per-SITE keys (perf.hist.*, perf.split_scan.*, ...): no fenced
    # wall time exists at site granularity (spans are per phase), so
    # only the static accounting + the timing-free roofline verdict —
    # intensity and bound are exactly what the quantized-training
    # acceptance instrument reads to show the histogram's memory bound
    # moving (docs/Quantized-Training.md)
    for site in sorted(sites):
        d = sites[site]
        pre = f"perf.{site}."
        out[pre + "flops"] = d["flops"]
        out[pre + "hbm_bytes"] = d["hbm_bytes"]
        for k, v in roofline(d["flops"], d["hbm_bytes"], 0.0,
                             pf, pb).items():
            out[pre + k] = v
    tot = {"flops": 0.0, "hbm_bytes": 0.0, "seconds": 0.0}
    for ph in sorted(phases):
        d = phases[ph]
        ph_hist = snap.get(f"train.phase_seconds{{phase={ph}}}")
        sec = float(ph_hist.get("sum", 0.0)) \
            if isinstance(ph_hist, dict) else 0.0
        pre = f"perf.{ph}."
        out[pre + "flops"] = d["flops"]
        out[pre + "hbm_bytes"] = d["hbm_bytes"]
        out[pre + "seconds"] = round(sec, 6)
        for k, v in roofline(d["flops"], d["hbm_bytes"], sec,
                             pf, pb).items():
            out[pre + k] = v
        tot["flops"] += d["flops"]
        tot["hbm_bytes"] += d["hbm_bytes"]
        tot["seconds"] += sec
    out["perf.total.flops"] = tot["flops"]
    out["perf.total.hbm_bytes"] = tot["hbm_bytes"]
    out["perf.total.seconds"] = round(tot["seconds"], 6)
    for k, v in roofline(tot["flops"], tot["hbm_bytes"], tot["seconds"],
                         pf, pb).items():
        out["perf.total." + k] = v
    if pf:
        out["perf.device.peak_flops_per_s"] = pf
    if pb:
        out["perf.device.peak_hbm_bytes_per_s"] = pb
    return out
