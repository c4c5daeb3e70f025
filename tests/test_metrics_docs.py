"""Metrics/doc parity (tier-1, stdlib-only): the README's `babble_*`
series catalogue and the code's metric registrations must not drift.

Both directions are enforced:

- every metric named in the README "Key series" table is actually
  registered somewhere in babble_tpu (a renamed or deleted metric
  fails here, not in a dashboard at 3am);
- every metric the code registers is documented in the README —
  verbatim with its `babble_` prefix, bare-backticked in the table
  (the table states the prefix once), or covered by an explicit
  `babble_foo_*` glob mention.

Registrations are collected statically (ast), so the test needs no
node, no registry instance and no jax: a name counts when it is the
first argument of a ``.counter(...)`` / ``.gauge(...)`` /
``.histogram(...)`` call, or the first element of a
``("babble_x", "stats_key")`` mirror tuple (node/core.py registers the
/Stats mirror gauges from such a table).

The README and the verify skill must also name only files that exist:
a deleted script or record named in backticks fails here.
"""

import ast
import fnmatch
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "babble_tpu")
README = os.path.join(REPO, "README.md")

_NAME_RE = re.compile(r"babble_[a-z0-9_]+\Z")


def _registered_metrics():
    names = set()
    for root, _dirs, files in os.walk(PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            path = os.path.join(root, fn)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            for node in ast.walk(tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("counter", "gauge",
                                               "histogram")
                        and node.args
                        and isinstance(node.args[0], ast.Constant)
                        and isinstance(node.args[0].value, str)
                        and _NAME_RE.match(node.args[0].value)):
                    names.add(node.args[0].value)
                if (isinstance(node, ast.Tuple)
                        and len(node.elts) == 2
                        and all(isinstance(e, ast.Constant)
                                and isinstance(e.value, str)
                                for e in node.elts)
                        and _NAME_RE.match(node.elts[0].value)):
                    names.add(node.elts[0].value)
    return names


def _readme_text():
    with open(README, encoding="utf-8") as f:
        return f.read()


def _table_metric_names(text):
    """Backticked names from the first column of the Key series table
    (label suffixes like ``{phase=...}`` stripped)."""
    names = set()
    in_table = False
    for line in text.splitlines():
        if line.startswith("| metric |"):
            in_table = True
            continue
        if in_table:
            if not line.startswith("|"):
                break
            first_cell = line.split("|")[1]
            for tok in re.findall(r"`([^`]+)`", first_cell):
                tok = tok.split("{")[0].strip()
                if re.fullmatch(r"[a-z0-9_]+", tok):
                    names.add(tok)
    assert in_table, "README Key series table not found"
    assert names, "README Key series table parsed to nothing"
    return names


def test_readme_table_metrics_are_registered():
    registered = _registered_metrics()
    assert registered, "no metric registrations found in babble_tpu"
    missing = sorted(
        name for name in _table_metric_names(_readme_text())
        if f"babble_{name}" not in registered
    )
    assert missing == [], (
        "README Key series table names metrics no code registers "
        f"(renamed or deleted?): {missing}"
    )


def test_registered_metrics_are_documented():
    text = _readme_text()
    globs = [g[:-1] for g in re.findall(r"babble_[a-z0-9_]*_\*", text)]

    def documented(name):
        if name in text:
            return True
        bare = name[len("babble_"):]
        if re.search(r"`%s[`{]" % re.escape(bare), text):
            return True
        return any(name.startswith(g) for g in globs)

    undocumented = sorted(
        n for n in _registered_metrics() if not documented(n)
    )
    assert undocumented == [], (
        "metrics registered by code but absent from README "
        f"(document them in the Key series table): {undocumented}"
    )


_DOC_FILE_EXTS = (".py", ".json", ".md", ".cpp")
# files the program writes at run time, named in the docs as outputs
_RUNTIME_FILES = {"peers.json", "babble_aot_manifest.json"}


def _tree_files():
    out = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d != ".git"]
        out.extend(files)
    return out


def _backticked_file_names(text):
    """Backtick spans that are one file name (no command around it),
    any ``::test`` suffix dropped."""
    names = set()
    for span in re.findall(r"`([^`\s]+)`", text):
        name = span.split("::")[0]
        if (name.endswith(_DOC_FILE_EXTS) and "<" not in name
                and name not in _RUNTIME_FILES):
            names.add(name)
    return names


def _named_file_exists(name, tree_files):
    if "/" not in name:
        return any(fnmatch.fnmatch(f, name) for f in tree_files)
    return any(
        glob.glob(os.path.join(REPO, base, name))
        for base in ("", "babble_tpu", "benchmark")
    )


@pytest.mark.parametrize(
    "doc", ["README.md", os.path.join(".claude", "skills", "verify",
                                      "SKILL.md")])
def test_doc_named_files_exist(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        names = _backticked_file_names(f.read())
    assert names, f"{doc} names no file in backticks"
    tree_files = _tree_files()
    missing = sorted(n for n in names
                     if not _named_file_exists(n, tree_files))
    assert missing == [], (
        f"{doc} names files that do not exist (deleted or renamed?): "
        f"{missing}"
    )
