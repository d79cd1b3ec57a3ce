"""The documents name only files that exist.

README.md, docs/*.md and the verify skill send a reader to scripts, tests
and other documents by path; a path that names nothing (a tool deleted, a
test renamed) is found here and not by the reader.
"""

import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# tools/x.py, tests/x.py, docs/X.md, subdirectories too; and a root x.py,
# which prose names in backticks or after ``python``
PATHED = re.compile(
    r"(?<![\w/.-])((?:tools|tests|docs)/[\w/.-]*\w\.(?:py|md))\b")
BARE = re.compile(r"(?:`|python3? )([A-Za-z_]\w*\.py)\b")


def _documents():
    docs = [os.path.join(REPO, "README.md"),
            os.path.join(REPO, ".claude", "skills", "verify", "SKILL.md")]
    docs += sorted(glob.glob(os.path.join(REPO, "docs", "*.md")))
    return [d for d in docs if os.path.exists(d)]


def _basenames():
    """Every tracked-looking ``*.py`` below the repo, by basename: a bare
    ``grower.py`` in prose names ``lightgbm_tpu/grower.py``."""
    names = set()
    for top in ("lightgbm_tpu", "tools", "tests", "benchmarks", "bindings"):
        for _root, _dirs, files in os.walk(os.path.join(REPO, top)):
            names.update(f for f in files if f.endswith(".py"))
    names.update(os.path.basename(p)
                 for p in glob.glob(os.path.join(REPO, "*.py")))
    return names


def test_docs_name_only_files_that_exist():
    known = _basenames()
    missing = []
    for doc in _documents():
        text = open(doc, encoding="utf-8").read()
        rel = os.path.relpath(doc, REPO)
        for path in sorted(set(PATHED.findall(text))):
            # a path into a directory this repo never had is the
            # reference's own tree (tests/c_api_test/, tests/distributed/)
            if os.path.isdir(os.path.join(REPO, os.path.dirname(path))) \
                    and not os.path.exists(os.path.join(REPO, path)):
                missing.append(f"{rel}: {path}")
        for name in sorted(set(BARE.findall(text))):
            if name not in known:
                missing.append(f"{rel}: {name}")
    assert len(_documents()) > 10
    assert not missing, "documents name files that do not exist:\n" \
        + "\n".join(missing)
