"""Device-flow lint: donation safety and transfer budgets.

The flush/merge hot path is re-expressed as *donating* XLA programs
(``donate_argnums``): the program consumes its input buffers, so any
host-side handle to a donated buffer is deleted the moment the dispatch
lands. The two nastiest bugs of the rebuild so far were exactly this
shape — a raw snapshot capture deleted under a donating drain (PR 9)
and the retired-twin release order (PR 5) — and both were found by
hand. These passes machine-check the discipline, in the suite's
static+runtime-twin pattern (the twin is ``lint/buffer_census.py``).

**donation-safety** — builds a registry of donating programs (every
``donate_argnums`` jit def or jit-binding, auto-discovered and
drift-checked as a generated docs table, like the compiled-program
inventory) and checks each call site:

* ``stale-donated-read`` — a name bound to a donated argument is read
  after the dispatch on some lexical path without being refreshed
  (rebound to the program's output, ``jnp.copy``'d, or re-read from
  ``self`` after the owner swapped it).
* ``donated-param-escape`` — a bare function *parameter* is passed into
  a donating dispatch and never rebound: the deleted buffer escapes to
  the caller, who has no way to know its handle died.
* ``raw-donated-capture`` — inside a two-phase ``snapshot_begin`` of a
  class whose planes are donation-prone (:data:`DONATION_PRONE_PLANES`),
  a captured ref is the live buffer instead of an op output: a drain
  landing between the locked begin and the off-lock ``finish()`` would
  delete the capture under ``jax.device_get`` (the PR 9 bug, statically
  closed across the dense/slab/tiered/mesh/standby snapshot paths).
* ``duplicate-donation`` — one expression donated at two positions of
  the same call (XLA rejects donating one buffer twice).
* ``shared-init-buffer`` — a registered init constructor
  (:data:`DISTINCT_BUFFER_INITS`) returns the same name for two fields;
  ingest donates the whole tuple, so shared buffers are double-donated.
* ``preflight-after-dispatch`` — a registered compute-ladder function
  (:data:`PREFLIGHT_CONTRACT`) calls the fault-injection ``preflight``
  after the rung-1 dispatch in the same suite: the injected fault must
  raise BEFORE dispatch so the donated buffers survive for rung 2.

**transfer-budget** — flags ``jax.device_get`` transfer sites inside
loops over series/slabs/shards (``per-row-transfer``) unless the loop
lives in a registered batched-fetch choke point
(:data:`CHOKE_POINTS` — the PR 14 ``_flush_collect`` contract). The
choke-point registry is generated and drift-checked with the donation
table, so a future per-row fetch regression cannot land silently.

Both registries regenerate with ``python -m veneur_tpu.lint
--donation-table`` and are pinned to live code by the
``device-registry`` pass (lint/devregistry.py).
"""

from __future__ import annotations

import ast
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Set, Tuple

from veneur_tpu.lint.framework import (Finding, Project, SourceFile,
                                       dotted, enclosing_function,
                                       qualname, register)
from veneur_tpu.lint.purity import _jax_aliases, _jit_decoration

# ---------------------------------------------------------------------------
# The checked registries (converted from prose guards; devregistry.py
# pins every entry to live code)
# ---------------------------------------------------------------------------

#: Donation-prone device planes per class: attributes that donating
#: programs consume in place. Two-phase ``snapshot_begin`` methods of
#: these classes must capture OP OUTPUTS (``jnp.copy``, a slice, a
#: reshape), never the live buffer — a drain landing between the locked
#: begin and the off-lock ``finish()`` deletes a raw capture under
#: ``jax.device_get``. This is the checked form of the prose guard that
#: used to live only as a comment in ``fleet/mesh_tiered.py``.
DONATION_PRONE_PLANES: Dict[str, Dict[str, Tuple[str, ...]]] = {
    "veneur_tpu/core/store.py": {
        "DigestGroup": ("digest", "temp"),
        "SetGroup": ("registers",),
        "HeavyHitterGroup": ("sketch",),
    },
    "veneur_tpu/core/slab.py": {
        "SlabDigestGroup": ("digests", "temps"),
    },
    "veneur_tpu/core/sets.py": {
        "SparseSetGroup": ("entries", "slot", "pool", "counters"),
    },
    "veneur_tpu/core/tiered.py": {
        "TieredDigestGroup": ("pools",),
    },
    "veneur_tpu/core/mesh_store.py": {
        "MeshDigestGroup": ("digest", "temp"),
        "MeshSetGroup": ("registers",),
        "MeshHeavyHitterGroup": ("sketch",),
    },
    "veneur_tpu/fleet/mesh_tiered.py": {
        "MeshTieredDigestGroup": ("pools",),
    },
}

#: Init constructors whose every field must get its OWN buffer: the
#: ingest programs donate the whole tuple, and XLA rejects donating one
#: buffer twice (the checked form of the ``ops/tdigest.py`` NB guard).
DISTINCT_BUFFER_INITS: Dict[Tuple[str, str], str] = {
    ("veneur_tpu/ops/tdigest.py", "init_temp"):
        "ingest donates the whole TempCentroids tuple; XLA rejects "
        "donating one buffer twice, so every field needs its own zeros",
}

#: Compute-ladder functions where the injected fault must raise BEFORE
#: the rung-1 dispatch, so the donated device buffers survive for the
#: XLA rung (the checked form of the ``resilience/compute.py`` guard).
#: Values: (attempt-callable parameter name, justification).
PREFLIGHT_CONTRACT: Dict[Tuple[str, str], Tuple[str, str]] = {
    ("veneur_tpu/core/store.py", "run_compute_ladder"): (
        "attempt", "rung 2 re-runs the COMPLETE attempt on the same "
        "donated inputs — only a pre-dispatch fault leaves them alive"),
    ("veneur_tpu/core/store.py", "begin_compute_ladder"): (
        "dispatch", "the two-phase ladder re-dispatches on the XLA "
        "rung inside finish(); donated inputs must survive dispatch"),
}

#: Legal batched-fetch choke points: the ONLY loops allowed to carry a
#: ``jax.device_get`` per iteration. Every entry is an interval-end
#: batched fetch (one transfer per slab/group, never per row) — the
#: PR 14 ``_flush_collect`` contract. qualname -> justification.
CHOKE_POINTS: Dict[Tuple[str, str], str] = {
    ("veneur_tpu/core/slab.py", "SlabDigestGroup._flush_collect"):
        "one batched device_get per retired SLAB (slabs hold 2^14 "
        "rows; the loop is over slabs, not rows)",
    ("veneur_tpu/core/slab.py", "SlabDigestGroup.snapshot_begin.finish"):
        "off-lock snapshot fetch: one device_get per captured slab "
        "tuple, dispatched under the lock in phase 1",
    ("veneur_tpu/core/tiered.py", "TieredDigestGroup._flush_fetch"):
        "one batched device_get per pool slab at interval end",
    ("veneur_tpu/core/tiered.py",
     "TieredDigestGroup.snapshot_begin.finish"):
        "off-lock snapshot fetch over captured (copied) pool slabs",
    ("veneur_tpu/fleet/mesh_tiered.py",
     "MeshTieredDigestGroup._flush_fetch"):
        "one full-slab device_get per sharded pool slab; the host-side "
        "permutation gather restores interner order after the fetch",
    ("veneur_tpu/fleet/mesh_tiered.py",
     "MeshTieredDigestGroup.snapshot_begin.finish"):
        "off-lock snapshot fetch over captured (copied) sharded slabs",
}

_FRESHNESS_HINT = (
    "capture a fresh value instead (jnp.copy, a slice/reshape op "
    "output, or re-read from self after the owner swaps it)")


# ---------------------------------------------------------------------------
# Donating-program discovery
# ---------------------------------------------------------------------------


@dataclass
class DonatingProgram:
    """One auto-discovered ``donate_argnums`` program."""

    relpath: str
    name: str                       # def qualname, or the bound name
    line: int
    donated: Tuple[int, ...]        # positional donated indices
    params: Tuple[str, ...]         # donated parameter names, if known
    kind: str                       # "decorator" | "binding"
    call_sites: int = 0


@dataclass
class _Inventory:
    programs: List[DonatingProgram] = field(default_factory=list)
    # (relpath, bare def name) -> program, for same-file Name calls
    by_def: Dict[Tuple[str, str], DonatingProgram] = \
        field(default_factory=dict)
    # (relpath, attr name) -> program, for `self.<attr> = jax.jit(...)`
    by_attr: Dict[Tuple[str, str], DonatingProgram] = \
        field(default_factory=dict)
    # (relpath, name) -> program, for `name = jax.jit(...)` bindings
    by_name: Dict[Tuple[str, str], DonatingProgram] = \
        field(default_factory=dict)


def _const_ints(node) -> Optional[Tuple[int, ...]]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for e in node.elts:
            if not (isinstance(e, ast.Constant)
                    and isinstance(e.value, int)):
                return None
            out.append(e.value)
        return tuple(out)
    return None


def _donated_indices(kwargs: List[ast.keyword]) -> Tuple[int, ...]:
    for kw in kwargs:
        if kw.arg == "donate_argnums":
            idx = _const_ints(kw.value)
            if idx:
                return idx
    return ()


def _is_jit_name(node, jax_names: Set[str]) -> bool:
    name = dotted(node)
    if name is None:
        return False
    parts = name.split(".")
    return parts[-1] in ("jit", "pmap") and (
        len(parts) == 1 or parts[0] in jax_names)


def collect_programs(project: Project) -> _Inventory:
    """Auto-discover every donating program in the tree: decorated defs
    (``@partial(jax.jit, donate_argnums=...)``) and jit bindings
    (``self._x = jax.jit(fn, donate_argnums=...)``)."""
    inv = _Inventory()
    for rel in sorted(project.files):
        sf = project.files[rel]
        jax_names = _jax_aliases(sf)
        for node in sf.nodes:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                kwargs = _jit_decoration(node)
                if kwargs is None:
                    continue
                donated = _donated_indices(kwargs)
                if not donated:
                    continue
                args = [a.arg for a in (node.args.posonlyargs
                                        + node.args.args)]
                params = tuple(args[i] for i in donated
                               if i < len(args))
                prog = DonatingProgram(
                    relpath=rel, name=qualname(node, sf.parents),
                    line=node.lineno, donated=donated, params=params,
                    kind="decorator")
                inv.programs.append(prog)
                inv.by_def[(rel, node.name)] = prog
            elif isinstance(node, ast.Assign):
                call = node.value
                if not (isinstance(call, ast.Call)
                        and _is_jit_name(call.func, jax_names)):
                    continue
                donated = _donated_indices(call.keywords)
                if not donated:
                    continue
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute) and \
                            isinstance(tgt.value, ast.Name) and \
                            tgt.value.id == "self":
                        inner = dotted(call.args[0]) if call.args \
                            else None
                        prog = DonatingProgram(
                            relpath=rel,
                            name=f"{qualname(node, sf.parents)}"
                                 f"::self.{tgt.attr}"
                            if inner is None else
                            f"self.{tgt.attr} = jit({inner})",
                            line=node.lineno, donated=donated,
                            params=(), kind="binding")
                        inv.programs.append(prog)
                        inv.by_attr[(rel, tgt.attr)] = prog
                    elif isinstance(tgt, ast.Name):
                        prog = DonatingProgram(
                            relpath=rel, name=tgt.id,
                            line=node.lineno, donated=donated,
                            params=(), kind="binding")
                        inv.programs.append(prog)
                        inv.by_name[(rel, tgt.id)] = prog
    return inv


def _program_for_call(inv: _Inventory, rel: str,
                      call: ast.Call) -> Optional[DonatingProgram]:
    func = call.func
    if isinstance(func, ast.Name):
        return inv.by_def.get((rel, func.id)) \
            or inv.by_name.get((rel, func.id))
    if isinstance(func, ast.Attribute) and \
            isinstance(func.value, ast.Name) and func.value.id == "self":
        return inv.by_attr.get((rel, func.attr))
    return None


# ---------------------------------------------------------------------------
# Expression helpers
# ---------------------------------------------------------------------------


def _capture_text(expr) -> Optional[str]:
    """Normalized text of a Name/Attribute/Subscript handle expression;
    None for anything whose outermost node already produces a fresh
    value (a call result, an arithmetic op, a literal)."""
    if isinstance(expr, (ast.Name, ast.Attribute, ast.Subscript)):
        return ast.unparse(expr)
    return None


def _enclosing_stmt(node, parents):
    cur = node
    while cur is not None and not isinstance(cur, ast.stmt):
        cur = parents.get(cur)
    return cur


def _target_texts(stmt) -> Set[str]:
    """Unparse texts of every assignment target (tuple targets
    flattened) of a statement; empty for non-assignments."""
    out: Set[str] = set()

    def flatten(t):
        if isinstance(t, (ast.Tuple, ast.List)):
            for e in t.elts:
                flatten(e)
        else:
            try:
                out.add(ast.unparse(t))
            except Exception:  # pragma: no cover - exotic targets
                pass

    if isinstance(stmt, ast.Assign):
        for t in stmt.targets:
            flatten(t)
    elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
        flatten(stmt.target)
    return out


def _reads_of(node, text: str, exclude=None) -> List[ast.AST]:
    """Load-context nodes under ``node`` whose unparse text is ``text``
    or extends it (``x.f``/``x[i]`` after ``x`` was donated). The
    ``exclude`` subtree (the donating call itself) is skipped."""
    hits: List[ast.AST] = []
    stack = [node]
    while stack:
        cur = stack.pop()
        if cur is exclude:
            continue
        if isinstance(cur, (ast.Name, ast.Attribute, ast.Subscript)) \
                and isinstance(getattr(cur, "ctx", None), ast.Load):
            t = ast.unparse(cur)
            if t == text or t.startswith(text + ".") \
                    or t.startswith(text + "["):
                hits.append(cur)
                continue  # the whole chain matched; don't re-report parts
        stack.extend(ast.iter_child_nodes(cur))
    return hits


def _forward_stmts(stmt, fn, parents):
    """Statements that may execute after ``stmt`` within ``fn``:
    later siblings at every nesting level up to (not beyond) fn.
    Branch-accurate in the cheap direction — a statement inside a
    sibling branch of an enclosing ``if`` is never yielded."""
    cur = stmt
    while cur is not fn:
        parent = parents.get(cur)
        if parent is None:
            return
        for fname in ("body", "orelse", "finalbody"):
            suite = getattr(parent, fname, None)
            if isinstance(suite, list) and cur in suite:
                idx = suite.index(cur)
                for later in suite[idx + 1:]:
                    yield later
        cur = parent
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and cur is not fn:
            return  # never climb out of a nested def


def _enclosing_loop(stmt, fn, parents):
    cur = parents.get(stmt)
    while cur is not None and cur is not fn:
        if isinstance(cur, (ast.For, ast.While)):
            return cur
        cur = parents.get(cur)
    return None


# ---------------------------------------------------------------------------
# donation-safety
# ---------------------------------------------------------------------------


def _fn_param_names(fn) -> Set[str]:
    a = fn.args
    return {p.arg for p in (a.posonlyargs + a.args + a.kwonlyargs)}


def _check_call_site(sf: SourceFile, rel: str, fn, stmt, call,
                     prog: DonatingProgram,
                     findings: List[Finding]) -> None:
    qn = qualname(fn, sf.parents)
    params = _fn_param_names(fn)
    targets = _target_texts(stmt)
    donated_texts: List[str] = []
    for pos in prog.donated:
        if pos >= len(call.args):
            continue
        text = _capture_text(call.args[pos])
        if text is None:
            continue  # a call/op result: a fresh temp, nothing to alias
        if text in donated_texts:
            if not sf.suppressed(call.lineno, "duplicate-donation"):
                findings.append(Finding(
                    pass_name="donation-safety",
                    code="duplicate-donation", file=rel,
                    line=call.lineno, anchor=f"{qn}:{text}",
                    message=(
                        f"`{text}` is donated at two positions of one "
                        f"`{prog.name}` dispatch — XLA rejects donating "
                        f"one buffer twice")))
            continue
        donated_texts.append(text)
        arg = call.args[pos]
        if isinstance(arg, ast.Name) and arg.id in params \
                and text not in targets:
            if not sf.suppressed(call.lineno, "donated-param-escape"):
                findings.append(Finding(
                    pass_name="donation-safety",
                    code="donated-param-escape", file=rel,
                    line=call.lineno, anchor=f"{qn}:{text}",
                    message=(
                        f"parameter `{text}` is donated to "
                        f"`{prog.name}` and never rebound: the caller "
                        f"still holds the deleted buffer — rebind the "
                        f"parameter to the program's output, or pragma "
                        f"with the caller-side contract")))
            continue
        if text in targets:
            continue  # refreshed by this very statement
        # stale reads: the rest of an enclosing loop body runs again
        # before any refresh, then every lexically-later statement
        reads: List[ast.AST] = []
        loop = _enclosing_loop(stmt, fn, sf.parents)
        if loop is not None:
            reads.extend(_reads_of(loop, text, exclude=call))
        for later in _forward_stmts(stmt, fn, sf.parents):
            if reads:
                break
            reads.extend(_reads_of(later, text, exclude=call))
            if text in _target_texts(later):
                break  # refreshed on this path; later reads are fine
        for read in reads[:1]:
            line = getattr(read, "lineno", call.lineno)
            if sf.suppressed(line, "stale-donated-read"):
                continue
            findings.append(Finding(
                pass_name="donation-safety", code="stale-donated-read",
                file=rel, line=line,
                anchor=f"{qn}:{text}",
                message=(
                    f"`{ast.unparse(read)}` is read after "
                    f"`{prog.name}` donated `{text}` (line "
                    f"{call.lineno}): the buffer is deleted at "
                    f"dispatch — {_FRESHNESS_HINT}")))


def _plane_aliases(fn, planes: Tuple[str, ...]) -> Set[str]:
    """Expression texts aliasing a donation-prone plane inside fn:
    ``self.<plane>`` plus loop variables iterating it."""
    texts = {f"self.{p}" for p in planes}
    for node in ast.walk(fn):
        if not isinstance(node, ast.For):
            continue
        it = node.iter
        if isinstance(it, ast.Call) and isinstance(it.func, ast.Name) \
                and it.func.id == "enumerate" and it.args:
            it = it.args[0]
        src = None
        try:
            src = ast.unparse(it)
        except Exception:  # pragma: no cover
            continue
        if src not in texts:
            continue
        tgt = node.target
        if isinstance(tgt, ast.Tuple) and len(tgt.elts) == 2 \
                and isinstance(tgt.elts[1], ast.Name):
            texts.add(tgt.elts[1].id)
        elif isinstance(tgt, ast.Name):
            texts.add(tgt.id)
    return texts


def _raw_plane_element(expr, aliases: Set[str]) -> Optional[str]:
    """The alias text if ``expr`` is a RAW live-buffer handle rooted at
    a plane alias: a pure attribute chain (``p.fmin``), or the plane
    container itself / its element (``self.pools``, ``self.pools[i]``).
    A slice/gather (``regs[:n]``), a method call (``p.mq.reshape(...)``)
    or ``jnp.copy(...)`` produce fresh arrays and return None."""
    if isinstance(expr, ast.Subscript):
        base = expr.value
        try:
            if ast.unparse(base) in aliases:
                return ast.unparse(expr)  # plane container indexing
        except Exception:  # pragma: no cover
            return None
        return None  # array gather: fresh
    node = expr
    while isinstance(node, ast.Attribute):
        node = node.value
    if isinstance(node, ast.Name) and isinstance(expr, (ast.Attribute,
                                                        ast.Name)):
        try:
            text = ast.unparse(expr)
        except Exception:  # pragma: no cover
            return None
        root = node.id
        if root in aliases or any(
                text == a or text.startswith(a + ".") for a in aliases):
            return text
    return None


def _closure_reads(fn) -> Set[str]:
    """Names read inside nested defs/lambdas of ``fn`` — anything a
    capture escapes into outlives phase 1's lock."""
    reads: Set[str] = set()
    for node in ast.walk(fn):
        if node is fn or not isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef,
                       ast.Lambda)):
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and isinstance(sub.ctx,
                                                        ast.Load):
                reads.add(sub.id)
    return reads


def _check_snapshot_captures(sf: SourceFile, rel: str, cls_name: str,
                             fn, planes: Tuple[str, ...],
                             findings: List[Finding]) -> None:
    aliases = _plane_aliases(fn, planes)
    qn = qualname(fn, sf.parents)
    escaped = _closure_reads(fn)

    def elements(value):
        if isinstance(value, (ast.Tuple, ast.List)):
            for e in value.elts:
                yield from elements(e)
        else:
            yield value

    # (raw element expr, why it survives past the lock)
    captures: List[Tuple[ast.AST, str]] = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign):
            # a local alias consumed inline under the lock is fine;
            # one an off-lock closure reads is the PR 9 bug
            names = {t.id for tgt in node.targets
                     for t in ([tgt] if isinstance(tgt, ast.Name)
                               else tgt.elts
                               if isinstance(tgt, (ast.Tuple, ast.List))
                               else [])
                     if isinstance(t, ast.Name)}
            if names & escaped:
                for e in elements(node.value):
                    captures.append((e, "the off-lock finish() closure "
                                        "reads it"))
        elif isinstance(node, ast.Return) and node.value is not None:
            for e in elements(node.value):
                captures.append((e, "it is returned past the lock"))
        elif isinstance(node, ast.Expr) \
                and isinstance(node.value, ast.Call):
            call = node.value
            if isinstance(call.func, ast.Attribute) \
                    and call.func.attr in ("append", "extend"):
                for a in call.args:
                    for e in elements(a):
                        captures.append((e, "the holding container "
                                            "outlives the lock"))
    for e, why in captures:
        raw = _raw_plane_element(e, aliases)
        if raw is None:
            continue
        line = getattr(e, "lineno", fn.lineno)
        if sf.suppressed(line, "raw-donated-capture"):
            continue
        findings.append(Finding(
            pass_name="donation-safety",
            code="raw-donated-capture", file=rel, line=line,
            anchor=f"{qn}:{raw}",
            message=(
                f"`{raw}` is captured RAW in the two-phase snapshot "
                f"of {cls_name} ({why}; plane registry: {planes}): a "
                f"donating drain landing between the locked begin and "
                f"the off-lock finish() deletes it under device_get — "
                f"{_FRESHNESS_HINT}")))


def _check_distinct_inits(project: Project,
                          findings: List[Finding]) -> None:
    for (rel, fname), reason in sorted(DISTINCT_BUFFER_INITS.items()):
        sf = project.files.get(rel)
        if sf is None:
            continue
        for node in sf.nodes:
            if not (isinstance(node, ast.FunctionDef)
                    and qualname(node, sf.parents) == fname):
                continue
            for ret in ast.walk(node):
                if not (isinstance(ret, ast.Return)
                        and isinstance(ret.value, ast.Call)):
                    continue
                seen: Dict[str, int] = {}
                exprs = list(ret.value.args) + \
                    [kw.value for kw in ret.value.keywords]
                for e in exprs:
                    if not isinstance(e, ast.Name):
                        continue
                    if e.id in seen:
                        if sf.suppressed(e.lineno,
                                         "shared-init-buffer"):
                            continue
                        findings.append(Finding(
                            pass_name="donation-safety",
                            code="shared-init-buffer", file=rel,
                            line=e.lineno, anchor=f"{fname}:{e.id}",
                            message=(
                                f"`{fname}` returns `{e.id}` for two "
                                f"fields — {reason}")))
                    seen[e.id] = e.lineno


def _check_preflight(project: Project,
                     findings: List[Finding]) -> None:
    for (rel, fname), (attempt, reason) in sorted(
            PREFLIGHT_CONTRACT.items()):
        sf = project.files.get(rel)
        if sf is None:
            continue
        for node in sf.nodes:
            if not (isinstance(node, ast.FunctionDef)
                    and qualname(node, sf.parents) == fname):
                continue
            preflights = [
                c for c in ast.walk(node)
                if isinstance(c, ast.Call)
                and isinstance(c.func, ast.Attribute)
                and c.func.attr == "preflight"]
            for pf in preflights:
                suite_stmt = _enclosing_stmt(pf, sf.parents)
                parent = sf.parents.get(suite_stmt)
                suite = getattr(parent, "body", [])
                if suite_stmt not in suite:
                    continue
                for sibling in suite[:suite.index(suite_stmt)]:
                    bad = [
                        c for c in ast.walk(sibling)
                        if isinstance(c, ast.Call)
                        and isinstance(c.func, ast.Name)
                        and c.func.id == attempt]
                    for c in bad[:1]:
                        if sf.suppressed(c.lineno,
                                         "preflight-after-dispatch"):
                            continue
                        findings.append(Finding(
                            pass_name="donation-safety",
                            code="preflight-after-dispatch", file=rel,
                            line=c.lineno,
                            anchor=f"{fname}:{attempt}",
                            message=(
                                f"`{attempt}(...)` dispatches before "
                                f"the injected-fault preflight in "
                                f"`{fname}` — {reason}")))


@register("donation-safety")
def run(project: Project) -> List[Finding]:
    inv = collect_programs(project)
    findings: List[Finding] = []
    # call-site discipline
    for rel in sorted(project.files):
        sf = project.files[rel]
        for node in sf.nodes:
            if not isinstance(node, ast.Call):
                continue
            prog = _program_for_call(inv, rel, node)
            if prog is None:
                continue
            prog.call_sites += 1
            fn = enclosing_function(node, sf.parents)
            stmt = _enclosing_stmt(node, sf.parents)
            if fn is None or stmt is None:
                continue
            _check_call_site(sf, rel, fn, stmt, node, prog, findings)
    # snapshot capture discipline over the registered planes
    for rel in sorted(DONATION_PRONE_PLANES):
        sf = project.files.get(rel)
        if sf is None:
            continue
        for node in sf.nodes:
            if not isinstance(node, ast.ClassDef):
                continue
            planes = DONATION_PRONE_PLANES[rel].get(node.name)
            if not planes:
                continue
            for item in node.body:
                if isinstance(item, ast.FunctionDef) \
                        and item.name.startswith("snapshot_begin"):
                    _check_snapshot_captures(sf, rel, node.name, item,
                                             planes, findings)
    _check_distinct_inits(project, findings)
    _check_preflight(project, findings)
    findings.sort(key=lambda f: (f.file, f.line, f.code))
    return findings


# ---------------------------------------------------------------------------
# transfer-budget
# ---------------------------------------------------------------------------


def _device_get_calls(sf: SourceFile, under) -> List[ast.Call]:
    jax_names = _jax_aliases(sf)
    out = []
    for node in ast.walk(under):
        if isinstance(node, ast.Call):
            name = dotted(node.func)
            if name and name.split(".")[-1] == "device_get" \
                    and (len(name.split(".")) == 1
                         or name.split(".")[0] in jax_names):
                out.append(node)
    return out


@register("transfer-budget")
def run_transfer(project: Project) -> List[Finding]:
    findings: List[Finding] = []
    for rel in sorted(project.files):
        sf = project.files[rel]
        for node in sf.nodes:
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            qn = qualname(node, sf.parents)
            if (rel, qn) in CHOKE_POINTS:
                continue
            for call in _device_get_calls(sf, node):
                if enclosing_function(call, sf.parents) is not node:
                    continue  # belongs to a nested def, checked there
                loop = _enclosing_loop(
                    _enclosing_stmt(call, sf.parents), node, sf.parents)
                if loop is None:
                    continue
                if sf.suppressed(call.lineno, "per-row-transfer"):
                    continue
                findings.append(Finding(
                    pass_name="transfer-budget", code="per-row-transfer",
                    file=rel, line=call.lineno, anchor=qn,
                    message=(
                        f"`jax.device_get` inside a loop in `{qn}` — a "
                        f"per-iteration device→host transfer. Batch the "
                        f"fetch (the PR 14 _flush_collect contract) or "
                        f"register the loop as a choke point in "
                        f"lint/deviceflow.py CHOKE_POINTS with a "
                        f"written justification")))
    findings.sort(key=lambda f: (f.file, f.line, f.code))
    return findings


# ---------------------------------------------------------------------------
# The generated registry table (docs/static-analysis.md drift-checks it)
# ---------------------------------------------------------------------------


def donation_table(project: Project) -> str:
    """Markdown inventory of the donating-program registry and the
    transfer choke points; regenerate with
    ``python -m veneur_tpu.lint --donation-table``."""
    inv = collect_programs(project)
    # count call sites (collect_programs alone does not walk calls)
    for rel in sorted(project.files):
        sf = project.files[rel]
        for node in sf.nodes:
            if isinstance(node, ast.Call):
                prog = _program_for_call(inv, rel, node)
                if prog is not None:
                    prog.call_sites += 1
    lines = [
        "| donating program | file | donated args | form | call sites |",
        "|---|---|---|---|---|",
    ]
    for p in sorted(inv.programs, key=lambda p: (p.relpath, p.name)):
        donated = ", ".join(p.params) if p.params else \
            ", ".join(f"#{i}" for i in p.donated)
        lines.append(f"| `{p.name}` | {p.relpath} | {donated} "
                     f"| {p.kind} | {p.call_sites} |")
    lines.append(f"| **total** | {len(inv.programs)} programs | — | — "
                 f"| — |")
    lines.append("")
    lines.append("| transfer choke point | file | justification |")
    lines.append("|---|---|---|")
    for (rel, qn), reason in sorted(CHOKE_POINTS.items()):
        lines.append(f"| `{qn}` | {rel} | {reason} |")
    return "\n".join(lines)
