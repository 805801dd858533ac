#!/usr/bin/env python
"""Snapshot the public API surface (exported names + signatures).

The redesigned surface (``repro.service`` sessions + the ``repro.domains``
registry + the config dataclasses) is a contract: future PRs must not
drift it by accident.  This script renders every public name of the
public modules into a deterministic text snapshot; the committed copy
lives at ``docs/api_surface.txt`` and ``tests/test_api_surface.py`` diffs
a fresh render against it in tier-1.

    python scripts/api_surface.py            # print to stdout
    python scripts/api_surface.py --write    # refresh docs/api_surface.txt

Intentional surface changes re-run ``--write`` (``make api-snapshot``)
and commit the diff — the point is that the diff SHOWS UP in review.
"""

from __future__ import annotations

import argparse
import inspect
import re
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
SNAPSHOT = REPO_ROOT / "docs" / "api_surface.txt"

# the public modules of the redesigned surface, in render order
PUBLIC_MODULES = [
    "repro.service",
    "repro.domains",
    "repro.core",
    "repro.core.config",
    "repro.problems",
    "repro.serve",
    "repro.sched",
    "repro.tuning",
    "repro.obs",
]

_ADDR = re.compile(r" at 0x[0-9a-fA-F]+")


def _sig(obj) -> str:
    try:
        return _ADDR.sub("", str(inspect.signature(obj)))
    except (ValueError, TypeError):
        return "(...)"


def _public_names(mod) -> list:
    if hasattr(mod, "__all__"):
        return list(mod.__all__)
    return sorted(n for n in vars(mod) if not n.startswith("_"))


def _render_class(qual: str, cls) -> list:
    lines = [f"class {qual}{_sig(cls)}"]
    for name, member in sorted(vars(cls).items()):
        if name.startswith("_"):
            continue
        if isinstance(member, (staticmethod, classmethod)):
            member = member.__func__
        if inspect.isfunction(member):
            lines.append(f"  {qual}.{name}{_sig(member)}")
        elif isinstance(member, property):
            lines.append(f"  {qual}.{name} [property]")
    return lines


def render() -> str:
    lines = [
        "# Public API surface snapshot — generated by "
        "scripts/api_surface.py --write",
        "# (make api-snapshot).  tests/test_api_surface.py diffs a fresh "
        "render against",
        "# this file: intentional surface changes regenerate it so the "
        "diff shows up in review.",
    ]
    import importlib
    for mod_name in PUBLIC_MODULES:
        mod = importlib.import_module(mod_name)
        lines.append("")
        lines.append(f"== {mod_name} ==")
        for name in _public_names(mod):
            obj = getattr(mod, name)
            qual = f"{mod_name}.{name}"
            if inspect.isclass(obj):
                lines.extend(_render_class(qual, obj))
            elif callable(obj):
                lines.append(f"{qual}{_sig(obj)}")
            elif inspect.ismodule(obj):
                lines.append(f"{qual} [module]")
            else:
                lines.append(f"{qual}: {type(obj).__name__}")
    return "\n".join(lines) + "\n"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--write", action="store_true",
                    help=f"write the snapshot to {SNAPSHOT}")
    args = ap.parse_args()
    sys.path.insert(0, str(REPO_ROOT / "src"))
    text = render()
    if args.write:
        SNAPSHOT.parent.mkdir(parents=True, exist_ok=True)
        SNAPSHOT.write_text(text)
        print(f"wrote {SNAPSHOT} ({len(text.splitlines())} lines)")
    else:
        sys.stdout.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
