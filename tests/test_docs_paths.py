"""README.md and the Makefile name only what exists.

Every ``make <target>`` the README shows is a target of the Makefile, and
every ``*.py`` / ``*.json`` / ``*.md`` path the README or a Makefile recipe
names is a tracked file: the guard that neither can describe a file or a
command that is gone. Pure file reading, no jax.
"""

import os
import re
import subprocess

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "deeplearning4j_tpu"

_PATH = re.compile(r"[\w./-]*\w\.(?:py|json|md)\b")


def _read(name):
    with open(os.path.join(REPO, name), encoding="utf-8") as f:
        return f.read()


def _tracked():
    """The files git would commit; where the checkout is not a repository
    (the driver's copy holds exactly those files) whatever is on disk."""
    try:
        out = subprocess.run(
            ["git", "ls-files", "--cached", "--others", "--exclude-standard"],
            cwd=REPO, capture_output=True, text=True, timeout=60)
        if out.returncode == 0 and out.stdout.strip():
            return set(out.stdout.splitlines())
    except (OSError, subprocess.TimeoutExpired):
        pass
    found = set()
    for root, _, files in os.walk(REPO):
        for f in files:
            found.add(os.path.relpath(os.path.join(root, f), REPO))
    return found


def _missing(text, tracked):
    """Paths named in ``text`` that are tracked neither from the root of
    the checkout nor from the package (the README's surface map writes
    ``ops/registry.py`` for ``deeplearning4j_tpu/ops/registry.py``)."""
    return sorted(p for p in set(_PATH.findall(text))
                  if p not in tracked and f"{PACKAGE}/{p}" not in tracked)


def test_every_make_target_the_readme_shows_exists():
    targets = set(re.findall(r"^([\w-]+):", _read("Makefile"), re.M))
    shown = set(re.findall(r"\bmake ([a-z][\w-]*)", _read("README.md")))
    assert shown, "README.md shows no make target: the pattern is stale"
    assert sorted(shown - targets) == []


def test_every_file_the_readme_and_the_makefile_name_is_tracked():
    tracked = _tracked()
    recipes = "\n".join(line for line in _read("Makefile").splitlines()
                        if line.startswith("\t"))
    assert "tools/graftlint.py" in recipes, "no recipe read: pattern stale"
    assert _missing(_read("README.md"), tracked) == []
    assert _missing(recipes, tracked) == []
