"""Sync lint: flag raw host-sync calls in the library hot paths.

Every blocking host fetch is a sync: the host waits for the device to
drain its queue, and the device then idles until the host dispatches
again.  A stray ``jax.device_get`` / ``block_until_ready`` / ``.item()``
in the training path is a silent per-iteration regression, and
intentional fences go through the one definition, ``obs.trace.fence``.
This lint keeps both properties true structurally:

- every raw sync call in ``lightgbm_tpu/`` (outside ``obs/trace.py``,
  the one module allowed to own the primitive) must be listed in
  ``tools/sync_allowlist.txt``;
- the allowlist pins (file, exact stripped source line), so MOVING a
  legitimate sync is cheap (re-pin) but ADDING one is a conscious act.

Comments and string literals are ignored (tokenize-based), so
documentation may mention the calls freely.

Run via the unified driver (``python tools/lint.py``; tier-1), or
standalone (``python tools/check_syncs.py``; exit 1 on findings), or
in-process (tests/test_observability.py calls ``find_raw_syncs``).
The parsing/stale-entry plumbing lives in ``tools/analyze/lintlib.py``,
shared with the retrace/race/purity lints.
"""

from __future__ import annotations

import os
import re
import sys
from typing import List, Set, Tuple

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from analyze import lintlib                              # noqa: E402

REPO = lintlib.REPO
PACKAGE = lintlib.PACKAGE
ALLOWLIST = os.path.join(REPO, "tools", "sync_allowlist.txt")

# the module that owns the fence primitive; everything inside may sync
EXEMPT = {os.path.join("lightgbm_tpu", "obs", "trace.py")}

_SYNC_RE = re.compile(
    r"device_get\s*\(|block_until_ready\b|\.item\s*\(\s*\)")


def load_allowlist(path: str = ALLOWLIST) -> Set[Tuple[str, str]]:
    """Entries are ``relative/path.py | exact stripped source line``."""
    return {key for key, _ in lintlib.parse_pins(path, 2)}


def find_raw_syncs(root: str = PACKAGE,
                   allowlist_path: str = ALLOWLIST) -> List[str]:
    """All unallowlisted raw sync call sites, as
    ``path:lineno: stripped line`` strings (empty list = lint green).
    Also reports allowlist entries that no longer match anything, so
    the list cannot rot."""
    allow = load_allowlist(allowlist_path)
    used: Set[Tuple[str, str]] = set()
    findings: List[str] = []
    for path in lintlib.iter_py(root):
        rel = lintlib.rel_to_root(path, root)
        if rel in EXEMPT:
            continue
        for lineno, code in sorted(lintlib.code_lines(path).items()):
            if not _SYNC_RE.search(code):
                continue
            # the allowlist pins the ORIGINAL stripped line text
            with open(path) as f:
                stripped = f.read().splitlines()[lineno - 1].strip()
            key = (rel, stripped)
            if key in allow:
                used.add(key)
                continue
            findings.append(f"{rel}:{lineno}: {stripped}")
    findings.extend(lintlib.stale_pins(allow, used, "allowlist"))
    return findings


def main() -> int:
    findings = find_raw_syncs()
    if findings:
        print("sync lint: raw device_get/block_until_ready/.item() "
              "outside obs.trace.fence:", file=sys.stderr)
        for f in findings:
            print(f"  {f}", file=sys.stderr)
        print(f"\n{len(findings)} finding(s).  Route fences through "
              "lightgbm_tpu.obs.trace.fence, or pin a genuinely "
              "necessary sync in tools/sync_allowlist.txt",
              file=sys.stderr)
        return 1
    print("sync lint: clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
