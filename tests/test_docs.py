"""The documents say what is: every file they name exists, and the table
of environment names in docs/DESIGN.md is the set the library reads."""

import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "celestia_app_tpu")


def _read(*parts: str) -> str:
    with open(os.path.join(REPO, *parts), encoding="utf-8") as f:
        return f.read()


def test_every_environment_name_the_library_reads_is_documented():
    """The `CELESTIA_*` literals under celestia_app_tpu/ equal the first
    column of DESIGN.md's "Environment names" table, each row names a file
    that holds the literal, and there are 20 of them: a 21st is an option
    someone has to argue for (ROADMAP, Design aim)."""
    in_code: dict[str, set[str]] = {}
    for path in glob.glob(os.path.join(PACKAGE, "**", "*.py"), recursive=True):
        with open(path, encoding="utf-8") as f:
            for name in re.findall(r"CELESTIA_[A-Z0-9_]+", f.read()):
                in_code.setdefault(name, set()).add(
                    os.path.relpath(path, PACKAGE))
    section = _read("docs", "DESIGN.md").split("## Environment names")[1]
    section = section.split("\n## ")[0]
    rows = re.findall(r"^\| `(CELESTIA_[A-Z0-9_]+)` \| ([^|]+) \|", section,
                      flags=re.M)
    assert sorted(name for name, _ in rows) == sorted(in_code)
    assert len(rows) == 20
    for name, modules in rows:
        for module in re.findall(r"`([^`]+)`", modules):
            assert module in in_code[name], (name, module)


@pytest.mark.parametrize("doc", ["README.md", "docs/DESIGN.md",
                                 "docs/FORMATS.md"])
def test_documents_name_only_files_that_exist(doc):
    """Every `*.py` / `*.json` / `*.cc` path inside backticks resolves
    from the repo root or from celestia_app_tpu/ (a `*` as a glob).
    Placeholders (`<home>/config.json`, `@/path.json`) and paths outside
    the repo (absolute) are not the repo's to hold."""
    prose = re.sub(r"```.*?```", "", _read(doc), flags=re.S)
    named, missing = 0, []
    for span in re.findall(r"`([^`]+)`", prose):
        for token in re.findall(r"[\w./*<>{}@-]+\.(?:py|json|cc)\b", span):
            if token.startswith("/") or set(token) & set("<>{}@"):
                continue
            named += 1
            if not (glob.glob(os.path.join(REPO, token))
                    or glob.glob(os.path.join(PACKAGE, token))):
                missing.append(token)
    assert named > 20           # the pattern still finds the paths
    assert not missing, sorted(set(missing))
