"""Crash-safe training snapshots + auto-resume.

The reference's ``snapshot_freq`` (gbdt.cpp:279-284) writes the model
text mid-training but never reads it back — resuming means the operator
hand-wiring ``input_model``.  A run that loses its device must be able
to continue where it stopped; this module closes the loop:

- :func:`write_snapshot` — the model text, a ``.state.npz`` sidecar (the
  f32 training score, so a resumed run continues from the EXACT device
  state rather than a re-predicted approximation of it) and a
  ``.manifest.json`` sidecar (iteration, params signature, data
  fingerprint, SHA-256 checksums of the model and state bytes — readers
  verify the artifacts they find are the artifacts the manifest
  describes).  All three go through ``resilience.atomic_write``; the
  manifest is written LAST, so its presence marks a complete snapshot —
  a crash mid-snapshot leaves the previous snapshot as the newest valid
  one.  Old snapshots are pruned to ``snapshot_keep``.
- :func:`find_latest_snapshot` — newest snapshot whose manifest parses,
  whose params signature matches the current run (so a changed learning
  rate can't silently splice into an old model), and whose data
  fingerprint matches the current dataset.  Invalid/mismatched
  candidates are warned about and skipped in favor of older ones.
- :func:`params_signature` — canonicalized-params hash with
  resume-control keys (``resume``, ``snapshot_freq`` …) excluded, so
  toggling snapshot bookkeeping never invalidates a snapshot.

``engine.train`` consumes these when ``resume=true``: the found model
feeds the existing ``init_model`` continued-training path, the state
score becomes the dataset's init score, and the booster's
iteration-keyed RNG streams are fast-forwarded
(``GBDTModel.set_resume_state``) — train-straight and crash-then-resume
produce byte-identical model text (tests/test_fault_tolerance.py).
"""

from __future__ import annotations

import contextlib
import glob
import hashlib
import io
import json
import os
import re
import threading
from typing import Any, Dict, Optional, Set, Tuple

import numpy as np

from .utils.log import Log
from .utils.resilience import atomic_write

_FORMAT = 1

# params that control snapshot/resume bookkeeping rather than the trained
# model — excluded from the signature so (a) toggling them between runs
# never invalidates a snapshot and (b) resuming with a LARGER
# num_iterations ("train 1M more") is allowed
_VOLATILE = {
    "resume", "snapshot_freq", "snapshot_keep", "num_iterations",
    "output_model", "input_model", "verbosity", "task", "data", "valid",
    "config", "machines", "machine_list_filename",
    # bring-up resilience knobs never affect the trained model, and
    # raising them is the NATURAL response to the crash being resumed
    # from — they must not invalidate the snapshot
    "dist_init_retries", "dist_init_timeout_s", "dist_fallback_serial",
    # computation-integrity knobs (lightgbm_tpu/integrity.py): checks
    # and transient-absorbed re-runs are byte-identical to unchecked
    # training, and turning detection ON is the natural response to
    # the corruption being resumed from
    "integrity_check_freq", "integrity_policy", "integrity_ulp_tol",
}

# Topology keys, volatile ONLY under elastic training
# (elastic_enable=true): the recovery ladder's whole premise is that
# the data-parallel owner-shard reduce makes global histograms
# shard-count invariant (dp == serial), so a run that started on an
# 8-wide mesh may legitimately resume on 4, 2, or serially — the
# topology is where the run executes, not what it trains.  Outside
# elastic these keys stay signature-relevant (voting's per-shard
# votes, for one, are topology-dependent).
_TOPOLOGY_VOLATILE = {"tree_learner", "num_machines", "mesh_shape",
                      "dp_owner_shard"}


def params_signature(params: Dict[str, Any]) -> str:
    """Stable hash of the training-relevant parameter surface."""
    from .config import _coerce, canonical_params
    cp = canonical_params(params)
    elastic = bool(_coerce("elastic_enable", bool,
                           cp.get("elastic_enable", False)))
    for k in _VOLATILE:
        cp.pop(k, None)
    for k in list(cp):
        # every elastic_* knob is run control (deadlines, heartbeat
        # cadence, ladder budgets) — never the trained model
        if k.startswith("elastic_"):
            cp.pop(k)
    if elastic:
        for k in _TOPOLOGY_VOLATILE:
            cp.pop(k, None)
    blob = json.dumps(cp, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def sha256_hex(data) -> str:
    """SHA-256 of ``data`` (str encoded as UTF-8)."""
    if isinstance(data, str):
        data = data.encode()
    return hashlib.sha256(data).hexdigest()


def file_sha256(path) -> str:
    """Streamed SHA-256 of a file's bytes."""
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def verify_snapshot_artifacts(path: str, man: Dict[str, Any],
                              state: bool = True) -> Optional[str]:
    """Check the snapshot's files against the checksums its manifest
    recorded; returns an error string on mismatch/unreadable, None when
    everything matches.  Manifests written before checksums existed
    record none — they verify vacuously (presence of the
    manifest-written-last marker is still the completeness signal).
    ``state=False`` skips the ``.state.npz`` sidecar: serving never
    reads it, so a reader that only needs the model must neither pay
    its hashing I/O nor refuse an otherwise servable snapshot over it."""
    pairs = [("model_sha256", path)]
    if state:
        pairs.append(("state_sha256", path + ".state.npz"))
    for key, p in pairs:
        want = man.get(key)
        if not want:
            continue
        try:
            got = file_sha256(p)
        except OSError as e:
            return f"{os.path.basename(p)} unreadable ({e})"
        if got != want:
            return (f"{os.path.basename(p)} checksum mismatch "
                    f"(file {got[:12]}…, manifest {want[:12]}…)")
    return None


# -- reader pins: close the find->open TOCTOU window -----------------------
# A reader (serving hot-load, training resume) locates a snapshot with a
# finder and only then opens its files; a concurrent writer's
# prune_snapshots could delete that very generation in between (a
# continual pipeline publishes + prunes while a registry loads).  Readers
# pin the path for the duration; prune holds newest-N PLUS every pinned
# generation.
_pin_lock = threading.Lock()
_pinned: Dict[str, int] = {}


@contextlib.contextmanager
def pin_snapshot(path: str):
    """Hold ``path`` (a snapshot model file) against
    :func:`prune_snapshots` while a reader is between locating it and
    finishing reading its files.  Re-entrant across threads (counted)."""
    key = os.path.abspath(path)
    with _pin_lock:
        _pinned[key] = _pinned.get(key, 0) + 1
    try:
        yield path
    finally:
        with _pin_lock:
            n = _pinned.get(key, 0) - 1
            if n <= 0:
                _pinned.pop(key, None)
            else:
                _pinned[key] = n


def pinned_snapshots() -> Set[str]:
    """Absolute paths currently pinned by active readers."""
    with _pin_lock:
        return set(_pinned)


def _snapshot_path(output_model: str, iteration: int) -> str:
    return f"{output_model}.snapshot_iter_{iteration}"


def _list_snapshots(output_model: str):
    """[(iteration, model_path)] for existing snapshot MODEL files,
    newest first.  Sidecars and atomic-write temp debris are ignored."""
    pat = re.compile(re.escape(os.path.basename(output_model))
                     + r"\.snapshot_iter_(\d+)$")
    out = []
    for path in glob.glob(glob.escape(output_model) + ".snapshot_iter_*"):
        m = pat.match(os.path.basename(path))
        if m:
            out.append((int(m.group(1)), path))
    out.sort(reverse=True)
    return out


def write_snapshot(booster, prev_booster, cfg, iteration: int,
                   signature: str, train_set) -> None:
    """Persist one snapshot (model + state + manifest, in that order)
    and prune to ``cfg.snapshot_keep``.  ``prev_booster`` (continued
    training / an earlier resume) contributes its leading trees so the
    snapshot is the FULL model, not just this run's suffix."""
    base = _snapshot_path(cfg.output_model, iteration)
    trees, weights = booster.trees, booster.tree_weights
    if prev_booster is not None:
        booster.trees = prev_booster.trees + trees
        booster.tree_weights = list(prev_booster.tree_weights) + list(weights)
    try:
        text = booster.model_to_string()
    finally:
        booster.trees, booster.tree_weights = trees, weights
    # under elastic multi-process training the model supplies GLOBAL
    # state (all-process score in global row order + the full-data
    # fingerprint) so a shrunk — even single-process — relaunch can
    # resume this snapshot; everywhere else this is exactly the local
    # score and the train set's own fingerprint
    fp_override = None
    state_fn = getattr(booster._model, "snapshot_state", None)
    if state_fn is not None:
        score, fp_override = state_fn()
        score = np.asarray(score, np.float32)
    else:
        score = np.asarray(booster._model.score, np.float32)
    buf = io.BytesIO()
    np.savez_compressed(buf, score=score)
    # encode ONCE and write binary: the hashed bytes must be the
    # written bytes (text mode would re-encode under the locale's
    # charset / newline rules, desynchronizing the checksum)
    text_bytes = text.encode("utf-8")
    manifest = {
        "format": _FORMAT,
        "iteration": int(iteration),
        "params_signature": signature,
        "data_fingerprint": fp_override or train_set.fingerprint(),
        "num_data": int(score.shape[0]),
        "num_class": int(score.shape[1]) if score.ndim > 1 else 1,
        "model_file": os.path.basename(base),
        "state_file": os.path.basename(base) + ".state.npz",
        # artifact checksums, computed from the EXACT bytes written
        # below: a reader (training resume, serving hot-load) can prove
        # the files it found are the files this manifest describes —
        # bit rot and torn/foreign files are refused, not loaded
        "model_sha256": sha256_hex(text_bytes),
        "state_sha256": sha256_hex(buf.getvalue()),
    }
    # computation-integrity stamp (lightgbm_tpu/integrity.py): present
    # only when integrity_check_freq > 0, so manifests stay
    # byte-identical to pre-integrity ones with the layer off.
    # ``verified`` means the snapshot's newest tree passed a shadow
    # compare (engine runs integrity_boundary_check first) — the stamp
    # find_latest_snapshot prefers when choosing a rewind target
    int_fn = getattr(booster._model, "integrity_manifest", None)
    if int_fn is not None:
        stamp = int_fn(int(iteration))
        if stamp is not None:
            manifest["integrity"] = stamp
    atomic_write(base, text_bytes, binary=True)
    atomic_write(base + ".state.npz", buf.getvalue(), binary=True)
    # manifest last: its presence marks the snapshot complete
    atomic_write(base + ".manifest.json",
                 json.dumps(manifest, indent=1, sort_keys=True))
    prune_snapshots(cfg.output_model, cfg.snapshot_keep)


def prune_snapshots(output_model: str, keep: int) -> None:
    """Delete all but the ``keep`` newest snapshots (model + sidecars);
    ``keep <= 0`` keeps everything.  Generations pinned by an active
    reader (:func:`pin_snapshot` — a registry hot-load or resume that
    located the snapshot but has not finished reading it) are held
    regardless of age; they become prunable again at the next prune
    after the reader unpins."""
    if keep <= 0:
        return
    pinned = pinned_snapshots()
    for _it, path in _list_snapshots(output_model)[keep:]:
        if os.path.abspath(path) in pinned:
            continue
        for p in (path + ".manifest.json", path + ".state.npz", path):
            try:
                os.unlink(p)
            except OSError:
                pass


def find_latest_complete_snapshot(output_model: str, verify: bool = True
                                  ) -> Optional[Tuple[int, str]]:
    """Newest snapshot of ``output_model`` whose manifest is present,
    parseable and format-matching, as ``(iteration, model_path)`` — the
    SERVING-side lookup (serve/registry.py hot reload): unlike
    :func:`find_latest_snapshot`, no params-signature or
    data-fingerprint check applies because a serving process has
    neither; the manifest-written-last marker alone distinguishes a
    complete snapshot from an interrupted write.  ``verify`` gates the
    manifest-checksum pass over the candidate's MODEL file — the
    ``.state.npz`` training sidecar is never hashed here because
    serving never reads it (a bit-rotted state must not block serving
    an intact model).  ``serve_verify_artifacts=false`` skips the
    hashing to shave load latency — corrupt candidates are then only
    caught if they fail to parse.  The find-time hash selects a clean
    candidate (bit-rotted newest falls back to an older complete
    snapshot); the loader's pinned re-hash of the same file
    (registry.load ``expected_sha256``) is a different job — the
    TOCTOU guarantee that the bytes activated are the bytes verified."""
    for it, path in _list_snapshots(output_model):
        try:
            with open(path + ".manifest.json", encoding="utf-8") as f:
                man = json.load(f)
        except (OSError, ValueError) as e:
            Log.warning(f"snapshot {path} skipped: manifest unreadable "
                        f"({e})")
            continue
        if man.get("format") != _FORMAT:
            Log.warning(f"snapshot {path} skipped: unknown manifest "
                        f"format {man.get('format')!r}")
            continue
        if verify:
            err = verify_snapshot_artifacts(path, man, state=False)
            if err is not None:
                Log.warning(f"snapshot {path} skipped: {err}")
                continue
        return it, path
    return None


def find_latest_snapshot(output_model: str, signature: str,
                         train_set) -> Optional[Tuple[int, str, np.ndarray]]:
    """Newest VALID snapshot as ``(iteration, model_path, score)``, or
    None.  Valid = manifest present and parseable, params signature and
    data fingerprint match, state loads.  Invalid candidates are skipped
    with a warning (an interrupted snapshot write leaves a model file
    with no manifest — exactly the case this walks past).

    ``elastic_global_fingerprint`` on the train set (set by
    ``parallel/elastic.elastic_train`` on multi-process shard datasets)
    overrides the shard's own fingerprint: elastic multi-process
    manifests are stamped with the GLOBAL data fingerprint
    (``GBDTModel.snapshot_state``), which the shard hash would never
    match.

    Integrity preference (lightgbm_tpu/integrity.py): among valid
    candidates, the newest whose manifest carries an
    ``integrity.verified == true`` stamp wins over a NEWER valid but
    unverified one — an SDC rewind must never land on a snapshot whose
    history could itself be corrupt.  With no verified candidate (or
    no integrity stamps at all, the ``integrity_check_freq=0`` world)
    the newest valid snapshot is returned exactly as before."""
    fp = getattr(train_set, "elastic_global_fingerprint", None) \
        or train_set.fingerprint()
    fallback: Optional[Tuple[int, str, np.ndarray]] = None
    for it, path in _list_snapshots(output_model):
        man_path = path + ".manifest.json"
        try:
            with open(man_path, encoding="utf-8") as f:
                man = json.load(f)
        except (OSError, ValueError) as e:
            Log.warning(f"snapshot {path} skipped: manifest unreadable "
                        f"({e})")
            continue
        if man.get("format") != _FORMAT:
            Log.warning(f"snapshot {path} skipped: unknown manifest "
                        f"format {man.get('format')!r}")
            continue
        if man.get("params_signature") != signature:
            Log.warning(f"snapshot {path} skipped: training parameters "
                        "differ from the run that wrote it")
            continue
        if man.get("data_fingerprint") != fp:
            Log.warning(f"snapshot {path} skipped: dataset fingerprint "
                        "differs from the run that wrote it")
            continue
        err = verify_snapshot_artifacts(path, man)
        if err is not None:
            Log.warning(f"snapshot {path} skipped: {err}")
            continue
        try:
            with np.load(path + ".state.npz") as z:
                score = np.asarray(z["score"], np.float32)
        except (OSError, ValueError, KeyError) as e:
            Log.warning(f"snapshot {path} skipped: state sidecar "
                        f"unreadable ({e})")
            continue
        if int(man.get("iteration", -1)) != it:
            Log.warning(f"snapshot {path} skipped: manifest iteration "
                        f"{man.get('iteration')} != filename {it}")
            continue
        stamp = man.get("integrity")
        if isinstance(stamp, dict) and not stamp.get("verified", False):
            # valid but integrity-UNVERIFIED: hold as the fallback and
            # keep walking for an older verified snapshot
            if fallback is None:
                fallback = (it, path, score)
            Log.warning(f"snapshot {path} is not integrity-verified; "
                        "looking for an older verified snapshot")
            continue
        if fallback is not None:
            Log.warning(
                f"resuming from integrity-verified snapshot iter {it} "
                f"instead of newer unverified iter {fallback[0]}")
        return it, path, score
    if fallback is not None:
        Log.warning(f"no integrity-verified snapshot found; resuming "
                    f"from unverified iter {fallback[0]}")
    return fallback
