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
