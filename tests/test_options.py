"""The package's settable surface, held to ``docs/OPTIONS.md``.

Two checks, both plain text searches: the ``LAH_*`` environment variables
named in the package's source are exactly the rows of the table in
``docs/OPTIONS.md``, and no file names a switch that PR 59 deleted (the
legacy dispatch arm, the protocol pin, the native transport, the sketch
toggle and the root-level harness with its variables)."""

import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "learning_at_home_tpu")

# whole names only: ``dispatch_mode`` also covers ``set_dispatch_mode``, and
# the harness's variables and file name match at a word's start, so another
# identifier that merely ends in them is free
DELETED = re.compile(
    r"LAH_CLIENT_PIPELINE|LAH_PROTO\b|LAH_DISPATCH_WATCHDOG|\bBENCH_[A-Z]"
    r"|dispatch_mode|dispatch_wait_watchdog|reset_dispatch_watchdog"
    r"|force_protocol_v1|set_sketch_backing"
    r"|transport=\"native\"|(?<![\w/-])bench\.py"
)


def _files(root, suffixes):
    for folder, _, names in os.walk(root):
        for name in names:
            if name.endswith(suffixes):
                yield os.path.join(folder, name)


def _read(path):
    with open(path, encoding="utf-8") as fh:
        return fh.read()


def test_the_packages_variables_are_the_documented_ones():
    named = set()
    for path in _files(PACKAGE, (".py", ".cpp")):
        named.update(re.findall(r"LAH_[A-Z0-9_]+", _read(path)))
    # a name that ends in "_" is a prefix in a pattern (analysis/lint.py's
    # ``LAH_GW_``), not a variable
    named = {name for name in named if not name.endswith("_")}
    table = _read(os.path.join(REPO, "docs", "OPTIONS.md"))
    rows = re.findall(r"^\| `(LAH_[A-Z0-9_]+)` \|", table, flags=re.MULTILINE)
    assert len(rows) == len(set(rows)), "a variable has two rows"
    assert named == set(rows), (
        f"in the source without a row: {sorted(named - set(rows))}; "
        f"rows the source no longer names: {sorted(set(rows) - named)}"
    )


def test_nothing_names_a_deleted_switch():
    paths = [os.path.join(REPO, "README.md")]
    paths += _files(os.path.join(REPO, "docs"), (".md",))
    for folder in ("learning_at_home_tpu", "tools", "experiments", "tests"):
        paths += _files(os.path.join(REPO, folder), (".py",))
    found = []
    for path in paths:
        if os.path.samefile(path, __file__):
            continue
        found += [
            f"{os.path.relpath(path, REPO)}: {name}"
            for name in sorted(set(DELETED.findall(_read(path))))
        ]
    assert not found, found
