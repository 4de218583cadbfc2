#!/usr/bin/env python
"""Docs honesty checks (run by CI's docs job).

1. Every relative markdown link in README.md, docs/*.md, and
   examples/README.md must resolve to an existing file (anchors stripped).
2. Every metric name cataloged in docs/scaling.md (backticked
   ``tier.metric_name`` tokens under the known tier prefixes) must appear
   literally somewhere in src/ — the catalog can't drift from the code.

Exit status 0 on success; 1 with a per-failure report otherwise.
Stdlib only:  python tools/check_docs.py
"""
from __future__ import annotations

import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
DOC_FILES = sorted(
    [REPO / "README.md", REPO / "examples" / "README.md"]
    + list((REPO / "docs").glob("*.md"))
)
METRIC_PREFIXES = (
    "service.", "forwarder.", "endpoint.", "executor.", "warming.",
    "autoscaler.", "workflow.", "trigger.", "container.", "journal.",
    "data.", "predictor.", "fair.", "serving.", "telemetry.", "worker.",
)

# [text](target) — excluding images; target split from any #anchor / title
LINK_RE = re.compile(r"(?<!\!)\[[^\]]+\]\(([^)\s]+)(?:\s+\"[^\"]*\")?\)")
METRIC_RE = re.compile(r"`([a-z_]+\.[a-z0-9_]+)`")


def check_links() -> list[str]:
    failures = []
    for doc in DOC_FILES:
        if not doc.exists():
            failures.append(f"{doc.relative_to(REPO)}: file missing")
            continue
        for m in LINK_RE.finditer(doc.read_text()):
            target = m.group(1)
            if target.startswith(("http://", "https://", "mailto:")):
                continue
            path = target.split("#", 1)[0]
            if not path:  # pure in-page anchor
                continue
            resolved = (doc.parent / path).resolve()
            if not resolved.exists():
                failures.append(
                    f"{doc.relative_to(REPO)}: broken link -> {target}"
                )
    return failures


def check_metrics_catalog() -> list[str]:
    catalog = REPO / "docs" / "scaling.md"
    if not catalog.exists():
        return ["docs/scaling.md missing (metrics catalog)"]
    names = {
        m.group(1)
        for m in METRIC_RE.finditer(catalog.read_text())
        if m.group(1).startswith(METRIC_PREFIXES)
    }
    if not names:
        return ["docs/scaling.md lists no metric names — catalog gutted?"]
    src_blob = "\n".join(
        p.read_text() for p in (REPO / "src").rglob("*.py")
    )
    return [
        f"docs/scaling.md: metric `{name}` not found anywhere in src/"
        for name in sorted(names)
        if name not in src_blob
    ]


def main() -> int:
    failures = check_links() + check_metrics_catalog()
    if failures:
        print(f"{len(failures)} docs check failure(s):")
        for f in failures:
            print(f"  - {f}")
        return 1
    n_links = sum(
        len(LINK_RE.findall(d.read_text())) for d in DOC_FILES if d.exists()
    )
    print(f"docs checks passed: {len(DOC_FILES)} files, {n_links} links, "
          f"metrics catalog consistent with src/")
    return 0


if __name__ == "__main__":
    sys.exit(main())
