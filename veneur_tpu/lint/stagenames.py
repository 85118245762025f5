"""Stage-name registry pass: every stage string documented, every
trace-bearing route contracted.

The flush-timeline's value rests on its vocabulary staying legible:
``docs/observability.md`` carries the stage table operators read a
timeline against, and the fleet trace plane's header contract lists
which routes carry ``X-Veneur-Trace``. Both drift silently — a new
``maybe_stage("...")`` call ships a stage nobody can look up, a new
traced route ships an undocumented contract — so this pass walks the
package for:

- every **stage string literal** passed to the StageRecorder surface
  (``stage`` / ``maybe_stage`` / ``record_abs`` / ``record_late`` /
  ``record_child``) and
  to ``sample_self_timing`` (the self-telemetry stage vocabulary).
  F-string holes normalize to ``<hole>`` and match any documented
  ``<...>`` placeholder (``f"post.{sink.name}"`` ↔ ``post.<sink>``).
  Nested calls record leaf names (``fetch``), which match as trailing
  path segments of documented dotted stages (``store.<group>.fetch``).
- every route in ``obs/tracectx.py``'s ``TRACED_ROUTES`` registry (the
  declared set of ``X-Veneur-Trace``-bearing endpoints).

Each must appear in ``docs/observability.md``; a miss is an
``undocumented-stage`` / ``undocumented-route`` finding against the
empty baseline. Non-literal stage names (variables like the per-group
``gen_name``) are unknowable statically and skipped — their documented
form is the ``<group>``-holed row.
"""

from __future__ import annotations

import ast
import re
from dataclasses import dataclass
from typing import List, Optional

from veneur_tpu.lint.framework import Finding, Project, dotted, register

_STAGE_FNS = ("stage", "maybe_stage", "record_abs", "record_late",
              "record_child", "sample_self_timing")
_TRACECTX_FILE = "veneur_tpu/obs/tracectx.py"
_DOCS_FILE = "docs/observability.md"


@dataclass
class StageSite:
    name: str       # normalized: f-string holes -> <hole>
    file: str
    line: int
    fn: str


def _normalize(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            elif isinstance(v, ast.FormattedValue):
                inner = dotted(v.value)
                hole = inner.split(".")[-1] if inner else "hole"
                parts.append(f"<{hole}>")
        return "".join(parts)
    return None


def _call_fn_name(node: ast.Call) -> Optional[str]:
    if isinstance(node.func, ast.Name):
        return node.func.id
    if isinstance(node.func, ast.Attribute):
        return node.func.attr
    return None


def collect_stages(project: Project) -> List[StageSite]:
    sites: List[StageSite] = []
    for sf in project.files.values():
        if sf.relpath.startswith("veneur_tpu/lint/"):
            continue  # this pass's own fixtures/docstrings don't count
        for node in sf.nodes:
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = _call_fn_name(node)
            if fn not in _STAGE_FNS:
                continue
            name = _normalize(node.args[0])
            if name is None or not name:
                continue
            sites.append(StageSite(name=name, file=sf.relpath,
                                   line=node.lineno, fn=fn))
    return sites


def collect_traced_routes(project: Project) -> List[StageSite]:
    """The TRACED_ROUTES registry (obs/tracectx.py) via AST — the
    declared list of X-Veneur-Trace-bearing endpoints."""
    sf = project.files.get(_TRACECTX_FILE)
    if sf is None:
        return []
    out: List[StageSite] = []
    for node in sf.nodes:
        if not isinstance(node, ast.Assign):
            continue
        targets = [t.id for t in node.targets if isinstance(t, ast.Name)]
        if "TRACED_ROUTES" not in targets:
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) \
                        and isinstance(elt.value, str):
                    out.append(StageSite(name=elt.value,
                                         file=sf.relpath,
                                         line=elt.lineno,
                                         fn="TRACED_ROUTES"))
    return out


def _doc_pattern(name: str) -> "re.Pattern":
    """A stage name as a docs regex: literal segments escaped, ``<x>``
    holes match any documented ``<...>`` placeholder, and the whole
    name may sit as a trailing segment of a longer dotted stage (leaf
    names nest under their runtime parents)."""
    body = "".join(
        r"<[A-Za-z0-9_*]+>" if part.startswith("<") else re.escape(part)
        for part in re.split(r"(<[A-Za-z0-9_]+>)", name))
    return re.compile(r"(?<![A-Za-z0-9_])" + body + r"(?![A-Za-z0-9_])")


@register("stage-registry")
def run(project: Project) -> List[Finding]:
    docs = project.read(_DOCS_FILE) or ""
    findings: List[Finding] = []
    seen = set()
    for site in collect_stages(project):
        if site.name in seen:
            continue
        seen.add(site.name)
        if _doc_pattern(site.name).search(docs):
            continue
        sf = project.files[site.file]
        if sf.suppressed(site.line, "undocumented-stage"):
            continue
        findings.append(Finding(
            pass_name="stage-registry", code="undocumented-stage",
            file=site.file, line=site.line, anchor=site.name,
            message=(f"stage `{site.name}` ({site.fn} call) is not in "
                     f"the {_DOCS_FILE} stage table — every stage an "
                     f"operator can see in /debug/flush-timeline must "
                     f"be documented there")))
    for site in collect_traced_routes(project):
        if _doc_pattern(site.name).search(docs):
            continue
        sf = project.files[site.file]
        if sf.suppressed(site.line, "undocumented-route"):
            continue
        findings.append(Finding(
            pass_name="stage-registry", code="undocumented-route",
            file=site.file, line=site.line, anchor=site.name,
            message=(f"X-Veneur-Trace route `{site.name}` "
                     f"(TRACED_ROUTES) is not in the {_DOCS_FILE} "
                     f"header-contract table — the hop contract cannot "
                     f"grow undocumented")))
    return findings
