"""Docs dead-link check (CI `docs-links` job; stdlib only).

Three classes of reference are verified against the working tree:

1. markdown links `[text](target)` in README.md / DESIGN.md whose target
   is a local path (http(s) links are skipped -- CI must not flake on
   third-party outages);
2. backticked repo paths like `src/repro/core/rfftn.py`,
   `tests/test_rfftn.py`, or `bench/harness.py` -- the docs
   lean on these heavily as the architecture map;
3. DESIGN.md section anchors: every `§k` the README cites must exist as
   a `## §k` heading in DESIGN.md.

Exit code 1 with a per-reference report on any miss.
"""

from __future__ import annotations

import pathlib
import re
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCS = ["README.md", "DESIGN.md"]
# docs reference library files relative to the repo root OR to src/repro
# (`core/mds.py`, `kernels/coded_pipeline.py`, ...)
BASES = [ROOT, ROOT / "src", ROOT / "src" / "repro"]
# backticked tokens that are file paths: a slash plus a real extension
# (math like `L/2`, dotted attrs like `mod.fn`, and bare dirs are prose)
PATHLIKE = re.compile(
    r"`([A-Za-z0-9_.-]+(?:/[A-Za-z0-9_.-]+)+\.(?:py|md|json|yml|toml))`")
DIRLIKE = re.compile(r"`([A-Za-z0-9_-]+(?:/[A-Za-z0-9_-]+)*/)`")
MDLINK = re.compile(r"\[[^\]]+\]\(([^)#\s]+)[^)]*\)")
SECTION = re.compile(r"§(\d+)")


def exists(token: str) -> bool:
    return any((base / token).exists() for base in BASES)


def main() -> int:
    errors: list[str] = []
    design = (ROOT / "DESIGN.md").read_text()
    sections = {int(m) for m in SECTION.findall(
        "\n".join(line for line in design.splitlines()
                  if line.startswith("## ")))}

    for name in DOCS:
        text = (ROOT / name).read_text()
        for target in MDLINK.findall(text):
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            if not (ROOT / target).exists():
                errors.append(f"{name}: markdown link -> missing {target!r}")
        for token in PATHLIKE.findall(text) + DIRLIKE.findall(text):
            if not exists(token):
                errors.append(f"{name}: path reference -> missing {token!r}")
        for num in {int(m) for m in SECTION.findall(text)}:
            if num not in sections:
                errors.append(
                    f"{name}: cites DESIGN.md §{num}, no such section")

    if errors:
        print("\n".join(errors))
        print(f"\n{len(errors)} dead reference(s).")
        return 1
    print(f"docs link check OK ({', '.join(DOCS)}; "
          f"{len(sections)} DESIGN sections)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
