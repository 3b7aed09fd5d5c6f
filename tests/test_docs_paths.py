"""README.md names files; every one it names is in the tree.

A backticked token that ends in `.py`, `.json`, `.md` or `.sh` is a path:
relative to the repository's root, or to the package (`idx/knn.py` is
`surrealdb_tpu/idx/knn.py`, as the README's Layout section lays it out).
Absolute paths (`/tmp/...`) and patterns (`<round>`, `*`) name nothing in
the tree and are left alone.
"""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = ("", "surrealdb_tpu")
PATH = re.compile(r"`([^`\s]+?\.(?:py|json|md|sh))(?:::[^`]*)?`")


def test_every_path_the_readme_names_exists():
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        named = set(PATH.findall(f.read()))
    named = {p for p in named if not p.startswith("/") and not set("<>*…") & set(p)}
    assert len(named) > 40, sorted(named)  # the pattern still finds them
    missing = sorted(
        p for p in named
        if not any(os.path.exists(os.path.join(REPO, root, p)) for root in ROOTS)
    )
    assert not missing, missing


KNOB = re.compile(r"SURREAL_[A-Z0-9_]*[A-Z0-9]")
# where an option is read: the package, the scripts, the benchmark, the
# test session's own set-up (not the test files, which name dead options
# to hold them dead)
KNOB_ROOTS = ("surrealdb_tpu", "scripts", "benchmarks", "chip_smoke.py", "tests/conftest.py")


def test_every_knob_the_readme_names_is_read():
    with open(os.path.join(REPO, "README.md"), encoding="utf-8") as f:
        named = set(KNOB.findall(f.read()))
    assert len(named) > 40, sorted(named)  # the pattern still finds them
    read = set()
    for root in KNOB_ROOTS:
        top = os.path.join(REPO, root)
        files = [top] if os.path.isfile(top) else [
            os.path.join(d, n) for d, _, names in os.walk(top) for n in names
        ]
        for path in files:
            if path.endswith((".py", ".sh")):
                with open(path, encoding="utf-8") as f:
                    read |= set(KNOB.findall(f.read()))
    # a whole name has to match a whole name (`SURREAL_ADVISOR` was read,
    # `SURREAL_ADVISOR_INTERVAL_SECS` never was); a suffix the README
    # spells alone (`_DISPATCH_S`) is not looked at
    assert sorted(named - read) == []
