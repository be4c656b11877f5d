"""Lint: every HVDTPU_* env var referenced anywhere must be declared,
and every declared knob must be documented.

Two directions, so knobs can neither drift IN undocumented nor drift
OUT of the docs:

* **reference lint** (:func:`check`) — every ``HVDTPU_*`` token
  referenced in the source trees must be declared (below);
* **docs lint** (:func:`check_docs`) — every knob declared in
  ``horovod_tpu/utils/env.py`` (knob constants + the
  ``DECLARED_ENV_VARS`` plumbing list) must appear *by exact name* in
  ``docs/api.md``'s knob tables. Wildcard/glob mentions of knob
  families deliberately do not count — the exact-name table is what
  the lint keeps honest.

Ground truth for declarations is two sites:

* ``horovod_tpu/utils/env.py`` — knob constants (resolved as
  ``HVDTPU_<value>``) plus the explicit ``DECLARED_ENV_VARS`` plumbing
  list (``declared_env_vars()`` merges both);
* ``csrc/env_parser.cc`` — native-side knobs, read as the string
  literals passed to ``Knob*``/``GetEnv*`` (scanned here as
  ``"<NAME>"`` arguments, prefixed ``HVDTPU_`` by ``KnobEnv``'s
  namespace loop).

The scan walks every ``.py``/``.cc``/``.h`` under ``horovod_tpu/``,
``csrc/``, ``tools/`` and the repo-root scripts for ``HVDTPU_[A-Z0-9_]+``
tokens; any token not declared fails the lint — so a new metrics knob
(or any knob) cannot ship undocumented. Wired into the test tier via
``tests/test_obs.py`` (``test_env_vars_all_declared``); also runnable
standalone::

    python tools/check_env_vars.py
"""

from __future__ import annotations

import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TOKEN = re.compile(r"\bHVDTPU_[A-Z0-9_]+\b")
# String literals handed to the C++ knob lookups; KnobEnv prefixes them.
CC_KNOB = re.compile(r'Knob(?:Int|Double|Bool|Str|Env)\(\s*"([A-Z0-9_]+)"')
CC_GETENV = re.compile(r'GetEnv(?:Int|Double|Bool|Str)\(\s*"(HVDTPU_[A-Z0-9_]+)"')

SCAN_DIRS = ("horovod_tpu", "csrc", "tools", "examples", "tests")
SCAN_ROOT_FILES = ("__graft_entry__.py",)
SCAN_EXT = (".py", ".cc", ".h")


def declared() -> set:
    sys.path.insert(0, REPO)
    try:
        from horovod_tpu.utils import env as _env

        names = set(_env.declared_env_vars())
    finally:
        sys.path.pop(0)
    cc = open(os.path.join(REPO, "csrc", "env_parser.cc")).read()
    names.update("HVDTPU_" + m for m in CC_KNOB.findall(cc))
    names.update(CC_GETENV.findall(cc))
    return names


def referenced() -> dict:
    """token -> sorted list of 'path:line' references."""
    refs: dict = {}
    paths = []
    for d in SCAN_DIRS:
        for root, _, files in os.walk(os.path.join(REPO, d)):
            if "__pycache__" in root:
                continue
            paths.extend(
                os.path.join(root, f) for f in files if f.endswith(SCAN_EXT)
            )
    paths.extend(os.path.join(REPO, f) for f in SCAN_ROOT_FILES)
    for path in paths:
        try:
            text = open(path, encoding="utf-8", errors="replace").read()
        except OSError:
            continue
        rel = os.path.relpath(path, REPO)
        for i, line in enumerate(text.splitlines(), 1):
            for tok in TOKEN.findall(line):
                refs.setdefault(tok, []).append(f"{rel}:{i}")
    return refs


def check() -> list:
    """Undeclared references as (token, [locations]) pairs."""
    decl = declared()
    return sorted(
        (tok, locs)
        for tok, locs in referenced().items()
        if tok not in decl
    )


def declared_python() -> set:
    """Just the ``utils/env.py`` declarations (the docs-lint ground
    truth; csrc-only knobs document themselves in ``env_parser.cc``)."""
    sys.path.insert(0, REPO)
    try:
        from horovod_tpu.utils import env as _env

        return set(_env.declared_env_vars())
    finally:
        sys.path.pop(0)


def check_docs() -> list:
    """Declared-but-undocumented knobs: every name from
    ``utils/env.py`` must appear verbatim in ``docs/api.md``."""
    text = open(os.path.join(REPO, "docs", "api.md"), encoding="utf-8").read()
    documented = set(TOKEN.findall(text))
    return sorted(declared_python() - documented)


def main() -> int:
    rc = 0
    bad = check()
    if bad:
        rc = 1
        print(
            "undeclared HVDTPU_* env vars (declare in "
            "horovod_tpu/utils/env.py — knob constant or DECLARED_ENV_VARS — "
            "or csrc/env_parser.cc):",
            file=sys.stderr,
        )
        for tok, locs in bad:
            print(f"  {tok}: {', '.join(locs[:5])}", file=sys.stderr)
    else:
        print(f"env lint OK: {len(referenced())} HVDTPU_* tokens all declared")
    undoc = check_docs()
    if undoc:
        rc = 1
        print(
            "declared HVDTPU_* knobs missing from docs/api.md (add to the "
            "knob tables — wildcards don't count):",
            file=sys.stderr,
        )
        for tok in undoc:
            print(f"  {tok}", file=sys.stderr)
    else:
        print(
            f"docs lint OK: {len(declared_python())} declared knobs all "
            "documented in docs/api.md"
        )
    return rc


if __name__ == "__main__":
    sys.exit(main())
