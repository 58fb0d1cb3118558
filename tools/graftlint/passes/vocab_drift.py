"""vocab-drift: the observability vocabularies cross-checked statically.

r9–r16 grew five hand-maintained vocabularies that runtime code raises
on — ``faults.SITES`` (injection sites), ``journal.EVENTS`` (flight-
recorder kinds), ``profiler.LANES`` (timeline lanes), the trace-spine
stage constants (``tracing.FRAME_STAGES``), and — declared in r17 —
``metrics.FAMILIES`` (Prometheus families). The runtime check only
trips when the producing line EXECUTES; a drifted string in a rarely-hit
branch (a typo'd journal kind in an error path, a stage stamped under a
name the span reducer ignores) ships silently. This pass is the
wire-fingerprint idea applied to the observability vocabularies: every
string used as a site/kind/lane/stage/family in the package must appear
in its declared vocabulary, AND every declared entry must be used —
drift fails lint in either direction:

- ``journal.record("<kind>", …)`` / ``JOURNAL.record`` — kind must be a
  string literal in ``journal.EVENTS``;
- ``profiler.record("<lane>", …)`` / ``PROFILER.record`` /
  ``profiler.span("<lane>", …)`` — lane must be
  a string literal in ``profiler.LANES`` (``config.DERIVED_LANES`` are
  synthesized by read surfaces and exempt from the dead-entry check);
- ``tracing.stamp(traces, <stage>, …)`` — a literal stage must be in
  the ``FRAME_STAGES`` vocabulary; a ``STAGE_*`` constant must resolve
  to one;
- ``reg.counter/gauge/histogram("<family>", …)`` — family must be
  declared in ``metrics.FAMILIES`` with a MATCHING kind;
- every ``faults.SITES`` site must decorate at least one production
  boundary (unknown/non-literal sites are the fault-site pass's job;
  this pass owns the DEAD direction).

Like wire-drift and fault-site, there is no pragma: the acceptance
mechanism for a new name IS declaring it in its vocabulary (and for a
dead one, deleting it). Vocabularies are parsed from module source —
the pass never imports package code.
"""

from __future__ import annotations

import ast
import os
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.graftlint import config
from tools.graftlint.core import Finding, ModuleSource, scope_files
from tools.graftlint.passes.fault_site import _parse_vocabulary


def _parse_dict_vocab(
    path: str, var_name: str
) -> Tuple[Dict[str, int], str]:
    """String keys (with their source lines) of a module-level dict
    literal assignment ``VAR: … = {…}``. Returns ({key: lineno},
    relpath-ish label)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    out: Dict[str, int] = {}
    for node in tree.body:
        targets: List[ast.AST] = []
        value: Optional[ast.AST] = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        names = {t.id for t in targets if isinstance(t, ast.Name)}
        if var_name in names and isinstance(value, ast.Dict):
            for k in value.keys:
                if isinstance(k, ast.Constant) and isinstance(
                    k.value, str
                ):
                    out[k.value] = k.lineno
    return out, var_name


def _parse_stage_vocab(path: str) -> Tuple[Dict[str, str], Dict[str, int]]:
    """(STAGE_* constant name -> stage string, stage string -> lineno
    for FRAME_STAGES members)."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    consts: Dict[str, str] = {}
    const_lines: Dict[str, int] = {}
    frame_stage_names: List[str] = []
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if not isinstance(t, ast.Name):
                continue
            if t.id.startswith("STAGE_") and isinstance(
                node.value, ast.Constant
            ):
                consts[t.id] = str(node.value.value)
                const_lines[t.id] = node.lineno
            elif t.id == "FRAME_STAGES" and isinstance(
                node.value, (ast.Tuple, ast.List)
            ):
                for e in node.value.elts:
                    if isinstance(e, ast.Name):
                        frame_stage_names.append(e.id)
                    elif isinstance(e, ast.Constant):
                        frame_stage_names.append(str(e.value))
    stages: Dict[str, int] = {}
    for name in frame_stage_names:
        if name in consts:
            stages[consts[name]] = const_lines[name]
        else:
            stages[name] = 1
    return consts, stages


def _parse_families(path: str) -> Tuple[Dict[str, str], Dict[str, int]]:
    """(family -> kind, family -> lineno) from metrics.FAMILIES."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), filename=path)
    kinds: Dict[str, str] = {}
    lines: Dict[str, int] = {}
    for node in tree.body:
        targets: List[ast.AST] = []
        value: Optional[ast.AST] = None
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = [node.target], node.value
        names = {t.id for t in targets if isinstance(t, ast.Name)}
        if "FAMILIES" in names and isinstance(value, ast.Dict):
            for k, v in zip(value.keys, value.values):
                if isinstance(k, ast.Constant) and isinstance(
                    v, ast.Constant
                ):
                    kinds[str(k.value)] = str(v.value)
                    lines[str(k.value)] = k.lineno
    return kinds, lines


def _term(node: ast.AST) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


class _Vocab:
    """One root's parsed vocabularies + cross-file usage accumulators."""

    def __init__(self, root: str) -> None:
        def resolve(rel: str) -> str:
            path = os.path.join(root, rel)
            if not os.path.exists(path):
                # Fixture roots validate against the repo's real
                # vocabularies (the fault-site pass convention).
                path = os.path.join(config.REPO_ROOT, rel)
            return path

        self.sites, _ = _parse_vocabulary(resolve(config.FAULT_VOCAB_MODULE))
        self.events, _ = _parse_dict_vocab(
            resolve(config.JOURNAL_VOCAB_MODULE), "EVENTS"
        )
        self.lanes, _ = _parse_dict_vocab(
            resolve(config.PROFILER_VOCAB_MODULE), "LANES"
        )
        self.stage_consts, self.stages = _parse_stage_vocab(
            resolve(config.TRACING_VOCAB_MODULE)
        )
        self.families, self.family_lines = _parse_families(
            resolve(config.METRICS_VOCAB_MODULE)
        )
        self.used_sites: Set[str] = set()
        self.used_events: Set[str] = set()
        self.used_lanes: Set[str] = set()
        self.used_stages: Set[str] = set()
        self.used_families: Set[str] = set()


class VocabDriftPass:
    id = "vocab-drift"

    def __init__(self) -> None:
        self._root: Optional[str] = None
        self._vocab: Dict[str, _Vocab] = {}

    def scope(self, root: str) -> List[str]:
        self._root = root
        self._vocab.pop(root, None)  # fresh usage accumulators per run
        return scope_files(root, config.VOCAB_SCOPE)

    def vocabulary(self) -> _Vocab:
        root = self._root or config.REPO_ROOT
        if root not in self._vocab:
            self._vocab[root] = _Vocab(root)
        return self._vocab[root]

    # -- usage detection -------------------------------------------------------

    def run(self, src: ModuleSource) -> Iterator[Tuple[Finding, ast.AST]]:
        v = self.vocabulary()
        is_journal_mod = src.path == config.JOURNAL_VOCAB_MODULE
        is_profiler_mod = src.path == config.PROFILER_VOCAB_MODULE
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            fname = _term(f)
            recv = _term(f.value) if isinstance(f, ast.Attribute) else ""
            # inject_fault sites: usage only (fault-site flags unknowns).
            if fname == "inject_fault":
                if (
                    len(node.args) == 1
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    v.used_sites.add(node.args[0].value)
                continue
            # journal.record("<kind>", …) / profiler.record("<lane>", …)
            # / profiler.span("<lane>", …)
            if fname in ("record", "span"):
                table = None
                used = None
                what = where = ""
                if fname == "record" and (
                    recv in ("journal", "JOURNAL") or (
                        isinstance(f, ast.Name) and is_journal_mod
                    ) or (recv == "self" and is_journal_mod)
                ):
                    table, used = v.events, v.used_events
                    what, where = "journal event kind", "telemetry/journal.py EVENTS"
                elif recv in ("profiler", "PROFILER") or (
                    isinstance(f, ast.Name) and is_profiler_mod
                ) or (recv == "self" and is_profiler_mod):
                    table, used = v.lanes, v.used_lanes
                    what, where = "profiler lane", "telemetry/profiler.py LANES"
                if table is None or not node.args:
                    continue
                a0 = node.args[0]
                # A two-literal conditional kind is static enough
                # (`"admission.admit" if d.admitted else
                # "admission.deny"`): both arms check and count.
                if (
                    isinstance(a0, ast.IfExp)
                    and isinstance(a0.body, ast.Constant)
                    and isinstance(a0.body.value, str)
                    and isinstance(a0.orelse, ast.Constant)
                    and isinstance(a0.orelse.value, str)
                ):
                    for arm in (a0.body, a0.orelse):
                        used.add(arm.value)
                        if arm.value not in table:
                            yield (
                                src.finding(
                                    self.id,
                                    node,
                                    f"undeclared {what} "
                                    f"{arm.value!r} — declare it in "
                                    f"{where}",
                                ),
                                node,
                            )
                    continue
                if not (
                    isinstance(a0, ast.Constant)
                    and isinstance(a0.value, str)
                ):
                    # The vocabulary module's own delegating shim
                    # (record(lane, …) forwarding to the ring) is the
                    # one sanctioned non-literal producer.
                    if not (is_journal_mod or is_profiler_mod):
                        yield (
                            src.finding(
                                self.id,
                                node,
                                f"{what} must be a single string literal "
                                "— the vocabulary is checked statically",
                            ),
                            node,
                        )
                    continue
                used.add(a0.value)
                if a0.value not in table:
                    yield (
                        src.finding(
                            self.id,
                            node,
                            f"undeclared {what} {a0.value!r} — declare "
                            f"it in {where} (unknown names raise at "
                            "runtime, but only when the branch runs)",
                        ),
                        node,
                    )
                continue
            # tracing.stamp(traces, <stage>, …)
            if fname == "stamp" and len(node.args) >= 2:
                a1 = node.args[1]
                if isinstance(a1, ast.Constant) and isinstance(
                    a1.value, str
                ):
                    v.used_stages.add(a1.value)
                    if a1.value not in v.stages:
                        yield (
                            src.finding(
                                self.id,
                                node,
                                f"stage {a1.value!r} is not in the "
                                "trace-spine vocabulary "
                                "(tracing.FRAME_STAGES) — the span "
                                "reducer drops unknown stages silently",
                            ),
                            node,
                        )
                else:
                    cname = _term(a1)
                    if cname.startswith("STAGE_"):
                        stage = v.stage_consts.get(cname)
                        if stage is None:
                            yield (
                                src.finding(
                                    self.id,
                                    node,
                                    f"unknown trace-stage constant "
                                    f"{cname} — tracing.py declares the "
                                    "stage vocabulary",
                                ),
                                node,
                            )
                        else:
                            v.used_stages.add(stage)
                continue
            # Registry family registrations.
            if fname in ("counter", "gauge", "histogram") and recv.lower() in (
                "reg",
                "registry",
            ):
                if not node.args or not (
                    isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                ):
                    yield (
                        src.finding(
                            self.id,
                            node,
                            "metric family name must be a string "
                            "literal — metrics.FAMILIES is the "
                            "exposition contract, checked statically",
                        ),
                        node,
                    )
                    continue
                fam = node.args[0].value
                v.used_families.add(fam)
                if fam not in v.families:
                    yield (
                        src.finding(
                            self.id,
                            node,
                            f"undeclared Prometheus family {fam!r} — "
                            "declare it in telemetry/metrics.py "
                            "FAMILIES with its kind",
                        ),
                        node,
                    )
                elif v.families[fam] != fname:
                    yield (
                        src.finding(
                            self.id,
                            node,
                            f"family {fam!r} registered as {fname} but "
                            f"declared {v.families[fam]!r} in "
                            "metrics.FAMILIES — one family, one kind",
                        ),
                        node,
                    )

    # -- dead-entry direction --------------------------------------------------

    def finalize(self) -> List[Finding]:
        """Declared-but-unused vocabulary entries, reported at their
        declaration lines — only meaningful after the WHOLE scope has
        been scanned (the runner skips finalize under a paths filter)."""
        v = self.vocabulary()
        out: List[Finding] = []

        def dead(
            entries, used: Set[str], path: str, what: str, line_of
        ) -> None:
            for name in sorted(entries):
                if name in used:
                    continue
                out.append(
                    Finding(
                        rule=self.id,
                        path=path,
                        line=line_of(name),
                        col=1,
                        message=(
                            f"dead {what} {name!r}: declared but never "
                            "used by any production module — delete it "
                            "or wire the producer (dead vocabulary "
                            "rows misdocument the observability "
                            "surface)"
                        ),
                    )
                )

        dead(
            v.sites,
            v.used_sites,
            config.FAULT_VOCAB_MODULE,
            "fault site",
            lambda n: 1,
        )
        dead(
            v.events,
            v.used_events,
            config.JOURNAL_VOCAB_MODULE,
            "journal event kind",
            lambda n: v.events[n],
        )
        dead(
            {
                lane: ln
                for lane, ln in v.lanes.items()
                if lane not in config.DERIVED_LANES
            },
            v.used_lanes,
            config.PROFILER_VOCAB_MODULE,
            "profiler lane",
            lambda n: v.lanes[n],
        )
        dead(
            v.stages,
            v.used_stages,
            config.TRACING_VOCAB_MODULE,
            "trace-spine stage",
            lambda n: v.stages[n],
        )
        dead(
            v.families,
            v.used_families,
            config.METRICS_VOCAB_MODULE,
            "Prometheus family",
            lambda n: v.family_lines[n],
        )
        return out
