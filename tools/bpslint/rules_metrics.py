"""Rule ``metric-name``: established-metric-name drift, bidirectionally.

Contract (docs/dev_invariants.md):

1. every literal name passed to the MetricsRegistry facades —
   ``counters.inc(...)``, ``gauges.set(...)``,
   ``histograms.observe(...)``, or the registry's own
   ``inc``/``set``/``observe`` — must have a row in the
   ``docs/observability.md`` "Established metric names" table; and
2. every name in that table must appear as a string literal somewhere in
   the package, so a renamed or deleted metric cannot leave a
   live-looking doc row behind.

Dynamically built names (f-strings, name maps) are skipped on the code
side — which is exactly why direction 2 exists: the full name must
still appear *somewhere* as a literal (e.g. a module-level name table),
keeping dynamic emitters greppable and the doc row checkable.

Doc-table grammar: names are backtick spans in the first column; a
label suffix ``{k=,v=}`` is stripped; a name without a dot inherits the
dotted prefix of the previous name in the same row
(```integrity.nonfinite_rejected` / `nonfinite_skipped``` documents
``integrity.nonfinite_skipped``).
"""

from __future__ import annotations

import re
from typing import Dict, List, Set, Tuple

from .core import Finding, LintTree, call_target, first_str_arg

_FACADES = {"counters": {"inc"},
            "gauges": {"set"},
            "histograms": {"observe", "observe_many"},
            "registry": {"inc", "set", "observe", "observe_many"}}

_NAME_SPAN = re.compile(r"`([^`]+)`")
_METRIC_SHAPE = re.compile(r"^[a-z0-9_.]+$")


def doc_names(lines: List[str]) -> Dict[str, int]:
    """``{metric name: line}`` from the table whose header row starts
    with ``| Name |``."""
    out: Dict[str, int] = {}
    in_table = False
    for i, line in enumerate(lines, 1):
        stripped = line.strip()
        if not stripped.startswith("|"):
            in_table = False
            continue
        cells = [c.strip() for c in stripped.strip("|").split("|")]
        if cells and cells[0] == "Name":
            in_table = True
            continue
        if not in_table or not cells:
            continue
        if set(cells[0]) <= set("-: "):
            continue
        prefix = ""
        for span in _NAME_SPAN.findall(cells[0]):
            name = re.sub(r"\{[^}]*\}", "", span).strip()
            if not _METRIC_SHAPE.match(name):
                continue
            if "." in name:
                prefix = name.rsplit(".", 1)[0] + "."
            elif prefix:
                name = prefix + name
            out.setdefault(name, i)
    return out


def check(tree: LintTree) -> List[Finding]:
    cfg = tree.cfg
    lines = tree.doc_text(cfg.metrics_doc)
    if lines is None:
        return [Finding("metric-name", cfg.metrics_doc, 1,
                        "metrics doc missing — the metric-name rule has "
                        "no documentation source")]
    documented = doc_names(lines)
    if not documented:
        return [Finding("metric-name", cfg.metrics_doc, 1,
                        "no `| Name | Kind | Meaning |` table found — "
                        "the metric-name rule has nothing to check "
                        "against")]

    findings: List[Finding] = []
    pkg = cfg.package.rstrip("/") + "/"
    pkg_files = [f for f in tree.py_files if f.rel.startswith(pkg)]

    all_literals: Set[str] = set()
    emitted: List[Tuple[str, str, int]] = []   # (name, rel, line)
    for pf in pkg_files:
        for s, _ in pf.string_constants():
            all_literals.add(s)
        if not pf.requested:
            continue
        for call in pf.calls():
            recv, meth = call_target(call)
            if recv not in _FACADES or meth not in _FACADES[recv]:
                continue
            lit = first_str_arg(call)
            if lit is None:
                continue   # dynamic name: covered by direction 2
            emitted.append((lit[0], pf.rel, lit[1]))

    seen: Set[Tuple[str, str]] = set()
    for name, rel, line in emitted:
        if name in documented:
            continue
        key = (name, rel)
        if key in seen:
            continue
        seen.add(key)
        findings.append(Finding(
            "metric-name", rel, line,
            f"metric {name!r} is emitted here but has no row in the "
            f"{cfg.metrics_doc} established-names table — document it "
            f"(dashboards and bps_top are built from that table)"))

    if tree.requested_path(cfg.metrics_doc):
        for name, line in sorted(documented.items()):
            if name not in all_literals:
                findings.append(Finding(
                    "metric-name", cfg.metrics_doc, line,
                    f"documented metric {name!r} appears nowhere in "
                    f"{cfg.package} as a string literal — dead doc row "
                    f"(delete it, or emit the metric; dynamically built "
                    f"names should come from a literal name table)"))
    return findings
