"""Deterministic fault injection for the fault-tolerance test suite.

Named injection sites are compiled into the hot paths as ONE dict-empty
check (zero cost when inactive) and fire according to a spec from the
``LGBM_TPU_FAULTS`` environment variable or :func:`configure`::

    LGBM_TPU_FAULTS="device_claim:1-2,nan_grads:3"

Spec grammar — comma-separated ``site:hits[:action]`` entries:

- ``hits``: which occurrences of the site fire, counted from 1 —
  ``3`` (exactly the 3rd hit), ``1-2`` (hits 1 and 2), ``4-`` (hit 4
  onward).  For per-iteration sites (``nan_grads``) the hit index IS the
  iteration number.
- ``action`` (optional): ``raise`` (default — :class:`InjectedFault`, a
  RuntimeError whose message matches the resilience layer's retryable
  patterns), ``kill`` (:class:`InjectedKill`, a BaseException that
  normal ``except Exception`` recovery cannot swallow — simulates the
  process dying at the site), ``exit`` (``os._exit(23)``, a REAL
  death for subprocess tests), or ``hang`` (the site blocks for
  ``LGBM_TPU_FAULT_HANG_S`` seconds, default 30 — the hung-collective
  / hung-claim simulation the elastic deadline layer exists to
  bound; the sleeping thread is abandoned by the watchdog exactly like
  a real wedge), or ``bitflip`` (one deterministic bit of the named
  device array flips at the site — only meaningful at the SDC sites
  wired through :func:`maybe_bitflip`).  Site ``snapshot_kill``
  defaults to ``kill``; sites ``collective_hang`` and ``claim_wedge``
  default to ``hang``; sites ``hist_sdc`` and ``score_sdc`` default to
  ``bitflip``.

Sites wired into the codebase:

==================  ========================================================
``device_claim``    device/backend bring-up (``GBDTModel._resolve_mesh``,
                    ``parallel/launch.init``, ``parallel/mesh
                    .init_distributed``) — exercises retry/backoff and
                    ``dist_fallback_serial``
``collective``      data-parallel grower dispatch
                    (``parallel/data_parallel.make_dp_grower``)
``snapshot_write``  entry of ``utils/resilience.atomic_write`` (every
                    model/binary/manifest write)
``snapshot_kill``   after the temp file is durable, before ``os.replace``
                    — the kill-before-rename crash window
``nan_grads``       gradient poisoning at iteration k
                    (``models/gbdt.GBDTModel.train_one_iter``) —
                    exercises ``finite_check_policy``
``serve_batch``     serve batch execution (``serve/server.Server
                    ._predict_batch``) — exercises the batcher's
                    transient-retry path and the serving circuit
                    breaker (tools/soak_serve.py chaos windows)
``serve_reload``    model load/hot-swap entry (``serve/registry
                    .ModelRegistry.load``) — a failed reload must leave
                    the current version serving
``continual_*``     the continual-boosting pipeline's stage boundaries
                    (``pipeline/continual.py``): ``continual_append``
                    (data-chunk ingest), ``continual_boost`` (boost k
                    rounds from the newest snapshot),
                    ``continual_publish`` (SHA-pinned artifact write),
                    ``continual_promote`` (gated registry promotion) —
                    each stage retries transients and rolls back to the
                    incumbent on exhaustion
``shadow_probe``    inside the shadow-traffic parity probe
                    (``pipeline/continual.py shadow_parity_probe``) —
                    a firing probe is a GATE FAILURE: the candidate is
                    quarantined, the incumbent keeps serving
``collective_hang`` inside the elastic collective-deadline fetch
                    (``parallel/elastic.guarded_get`` worker, i.e. the
                    training loop's one per-iteration host sync) —
                    default action ``hang``: the fetch wedges and the
                    deadline must classify + abandon it
``host_loss``       the elastic per-iteration liveness check
                    (``parallel/elastic.check_peers``) — a firing site
                    simulates a peer process's heartbeat going stale
                    (the kill -9 subprocess tests exercise the real
                    stale-file detection)
``claim_wedge``     device claim under elastic
                    (``models/gbdt.GBDTModel._resolve_mesh``) —
                    default action ``hang``: the claim wedges and the
                    bring-up deadline must turn it into a classified
                    ``ElasticFailure`` instead of a silent hang
``ingest_read``     chunk read+parse entry of the streaming ingest
                    pipeline (``ingest.IngestRunner``) — exercises the
                    per-chunk retry/backoff; ``exit`` between chunk
                    commits is the kill -9 resume test
``ingest_checksum`` chunk validation (``ingest.IngestRunner``) — a
                    firing site simulates a CORRUPT chunk (sha
                    mismatch class, not transient): quarantined per
                    ``ingest_bad_chunk``, never retried
``ingest_hang``     inside the chunk read (``ingest.IngestRunner``) —
                    default action ``hang``: a reader hung on a dead
                    filesystem; the ``ingest_read_timeout_s`` watchdog
                    must abandon + classify it
``hist_sdc``        silent-data-corruption injection into the grower's
                    histogram-derived output (``models/gbdt
                    .GBDTModel.train_one_iter`` via
                    :func:`maybe_bitflip`) — default action
                    ``bitflip``: ONE deterministic bit of the new
                    tree's leaf-count array flips, simulating a
                    marginal chip; exercises the integrity layer's
                    detect / transient-absorb / rewind / quarantine
                    ladder (lightgbm_tpu/integrity.py)
``score_sdc``       silent-data-corruption injection into the
                    per-iteration score-update delta (``models/gbdt
                    .GBDTModel.train_one_iter``) — default action
                    ``bitflip``; exercises the integrity layer's
                    score-path verification
==================  ========================================================
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

ENV_VAR = "LGBM_TPU_FAULTS"

KNOWN_SITES = ("device_claim", "collective", "snapshot_write",
               "snapshot_kill", "nan_grads", "serve_batch",
               "serve_reload", "serve_self_check", "continual_append",
               "continual_boost", "continual_publish",
               "continual_promote", "shadow_probe", "collective_hang",
               "host_loss", "claim_wedge", "ingest_read",
               "ingest_checksum", "ingest_hang", "hist_sdc",
               "score_sdc")

# sites whose realistic failure mode is a WEDGE, not an error
_HANG_DEFAULT_SITES = ("collective_hang", "claim_wedge", "ingest_hang")

# sites whose realistic failure mode is SILENT data corruption — the
# chip keeps running and hands back a wrong number (maybe_bitflip)
_BITFLIP_DEFAULT_SITES = ("hist_sdc", "score_sdc")

# how long a firing ``hang`` action blocks: long enough that any sane
# deadline fires first, short enough that an abandoned daemon thread
# does not outlive a test session
HANG_ENV_VAR = "LGBM_TPU_FAULT_HANG_S"


def _hang_seconds() -> float:
    try:
        return float(os.environ.get(HANG_ENV_VAR, "") or 30.0)
    except ValueError:
        return 30.0


class InjectedFault(RuntimeError):
    """Raised by a firing site.  The message deliberately matches the
    resilience classifier's retryable patterns (UNAVAILABLE / claim) so
    injected bring-up failures exercise the REAL retry path."""

    def __init__(self, site: str, hit: int):
        self.site = site
        self.hit = hit
        super().__init__(
            f"injected fault at site '{site}' (hit {hit}): UNAVAILABLE: "
            "simulated device claim/backend failure")


class InjectedKill(BaseException):
    """Simulated process death at a site.  Derives from BaseException so
    ``except Exception`` recovery paths (snapshot skip-and-warn) cannot
    swallow it — only the test harness catches it."""

    def __init__(self, site: str, hit: int):
        self.site = site
        self.hit = hit
        super().__init__(f"injected kill at site '{site}' (hit {hit})")


# site -> (first_hit, last_hit_or_None_for_open_end, action)
_spec: Dict[str, Tuple[int, Optional[int], str]] = {}
_hits: Dict[str, int] = {}


def configure(spec: Optional[str]) -> None:
    """Install a fault spec (replacing any active one) and reset all hit
    counters.  ``None``/empty disables injection entirely."""
    _spec.clear()
    _hits.clear()
    if not spec:
        return
    for entry in spec.split(","):
        entry = entry.strip()
        if not entry:
            continue
        parts = entry.split(":")
        if len(parts) not in (2, 3):
            raise ValueError(f"bad fault spec entry {entry!r} "
                             "(want site:hits[:action])")
        site, hits = parts[0].strip(), parts[1].strip()
        if len(parts) == 3:
            action = parts[2].strip()
        elif site == "snapshot_kill":
            action = "kill"
        elif site in _HANG_DEFAULT_SITES:
            action = "hang"
        elif site in _BITFLIP_DEFAULT_SITES:
            action = "bitflip"
        else:
            action = "raise"
        if site not in KNOWN_SITES:
            raise ValueError(f"unknown fault site {site!r} "
                             f"(known: {', '.join(KNOWN_SITES)})")
        if action not in ("raise", "kill", "exit", "hang", "bitflip"):
            raise ValueError(f"unknown fault action {action!r}")
        if "-" in hits:
            lo_s, hi_s = hits.split("-", 1)
            lo = int(lo_s)
            hi = int(hi_s) if hi_s else None
        else:
            lo = hi = int(hits)
        if lo < 1 or (hi is not None and hi < lo):
            raise ValueError(f"bad hit range in {entry!r}")
        _spec[site] = (lo, hi, action)


def clear() -> None:
    """Disable injection and reset counters (test teardown)."""
    configure(None)


def enabled() -> bool:
    """Whether ANY site is armed (used to gate zero-cost fast paths,
    e.g. the scanned program which cannot host per-iteration
    injection)."""
    return bool(_spec)


def hits(site: str) -> int:
    """How many times ``site`` was reached since configure()."""
    return _hits.get(site, 0)


def _advance(site: str) -> Tuple[bool, int, str]:
    """Count a hit; return (fires, hit_index, action)."""
    if site not in _spec:
        return False, 0, "raise"
    n = _hits.get(site, 0) + 1
    _hits[site] = n
    lo, hi, action = _spec[site]
    return (n >= lo and (hi is None or n <= hi)), n, action


def check(site: str) -> None:
    """Raise/exit/hang if ``site`` fires on this hit; no-op otherwise."""
    if not _spec:
        return
    fire, n, action = _advance(site)
    if not fire:
        return
    if action == "exit":
        os._exit(23)
    if action == "kill":
        raise InjectedKill(site, n)
    if action == "hang":
        # the wedge simulation: block like a hung collective/claim
        # would.  Bounded (HANG_ENV_VAR) so an abandoned thread cannot
        # outlive the test session; any sane deadline fires well before
        import time
        time.sleep(_hang_seconds())
        return
    raise InjectedFault(site, n)


def fires(site: str) -> bool:
    """Non-raising variant for corruption sites (``nan_grads``): counts
    the hit and reports whether it fires, leaving the action to the call
    site (e.g. writing NaN into the gradient array)."""
    if not _spec:
        return False
    fire, _n, _action = _advance(site)
    return fire


def maybe_bitflip(site: str, arr, index: Optional[int] = None):
    """SDC injection: count a hit at ``site``; when it fires with action
    ``bitflip``, return ``arr`` with exactly ONE bit flipped.  Element
    and bit are chosen deterministically from ``crc32(site:hit)`` so a
    given spec replays the identical corruption run to run; ``index``
    pins the element instead (e.g. ``hist_sdc`` flips leaf 0's count —
    a slot that is always live).  For int32 operands the bit is drawn
    from [0, 31); for float32 from [8, 31) — at least 256 ulps, so a
    flip is never hidden inside ``integrity_ulp_tol`` — and the sign
    bit is left alone either way so a float flip stays a plausible
    wrong *number*, not a sign glitch.

    Returns ``arr`` unchanged — the SAME object, no device work — when
    injection is off, the site is unarmed, or this hit does not fire.
    A non-``bitflip`` action on an armed SDC site still applies (e.g.
    ``hist_sdc:3:kill`` dies at the site instead of corrupting it).
    """
    if site not in _spec:
        return arr
    fire, n, action = _advance(site)
    if not fire:
        return arr
    if action != "bitflip":
        if action == "exit":
            os._exit(23)
        if action == "kill":
            raise InjectedKill(site, n)
        if action == "hang":
            import time
            time.sleep(_hang_seconds())
            return arr
        raise InjectedFault(site, n)
    import zlib

    import jax
    import jax.numpy as jnp
    seed = zlib.crc32(f"{site}:{n}".encode())
    flat = jnp.ravel(arr)
    size = max(int(flat.shape[0]), 1)
    idx = (seed if index is None else int(index)) % size
    bit = (seed >> 8) % 31
    if jnp.issubdtype(flat.dtype, jnp.floating):
        bit = 8 + (seed >> 8) % 23      # >= 256 ulps: never tol-masked
    mask = jnp.int32(1 << bit)
    if jnp.issubdtype(flat.dtype, jnp.floating):
        iv = jax.lax.bitcast_convert_type(
            flat.astype(jnp.float32), jnp.int32)
        iv = iv.at[idx].set(iv[idx] ^ mask)
        flat = jax.lax.bitcast_convert_type(
            iv, jnp.float32).astype(arr.dtype)
    elif jnp.issubdtype(flat.dtype, jnp.integer):
        flat = flat.at[idx].set(flat[idx] ^ mask.astype(flat.dtype))
    else:
        raise TypeError(f"maybe_bitflip: unsupported dtype "
                        f"{flat.dtype} at site '{site}'")
    return flat.reshape(jnp.shape(arr))


# arm from the environment at import (subprocess tests)
configure(os.environ.get(ENV_VAR))
