"""R5: telemetry drift — code vs docs/TELEMETRY.md, both directions.

Generalizes PR 8's span-name literal-scan test into an engine rule and
extends it to the whole observable surface:

- **spans**: every literal ``tracer.span("...")`` /
  ``tracer.record("...")`` name must appear in TELEMETRY.md's
  "## Instrumented spans" fenced table, and every documented span must
  still be emitted. ``bg.*`` loop spans and ``http.*`` route-handler
  spans are dynamic-by-design and covered as a prefix; any other
  f-string site must be registered in ``DYNAMIC`` with its expansions.
- **Prometheus series**: every ``nomad_tpu_*`` series literal in the
  code must appear in the "## Prometheus series" fenced list, and vice
  versa (a scraper alerting on a renamed series is an outage, not a
  diff).
- **bench keys**: every ``trace_*`` / ``contention_*`` / ``fleet_*``
  / ``chaos_*`` keyword bench.py emits into BENCH_*.json must appear in the
  "## Bench emission keys" fenced list, and vice versa (trend lines
  silently going dark is how perf regressions hide).

The docs sections are the contract; prose may mention whatever it
likes — only the fenced blocks are parsed.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tools.graftcheck.engine import Context, Finding, SourceFile, dotted_name

RULE = "R5"

DOC_REL = "docs/TELEMETRY.md"
BENCH_REL = "bench.py"

#: span families named from what runs (a background loop, a route
#: handler): covered as a prefix, never enumerated
DYNAMIC_PREFIXES = ("bg.", "http.")

#: registered dynamic span-name sites (template with {} placeholders
#: -> concrete expansions). A new f-string span site must be added
#: here with its value set, or use a literal.
DYNAMIC: Dict[str, Tuple[str, ...]] = {
    "kernel.{}": ("kernel.compile", "kernel.dispatch"),
}

_SPAN_NAME = re.compile(r"[a-z][a-z0-9_]*\.[a-z0-9_.{}]+")
#: a series name needs >= 2 words after the prefix (every real series
#: does: subsystem + metric) — this keeps cache-file path strings like
#: "nomad_tpu_warmup.json" / "nomad_tpu_xla" out of the contract
_PROM_NAME = re.compile(r"\bnomad_tpu_[a-z0-9]+(?:_[a-z0-9]+)+\b")
#: fleet_* joined in ISSUE 11 (the serving-plane fleet cell's trend
#: lines are contract like every other bench emission); chaos_* in
#: ISSUE 12 (the chaos cell's convergence verdict + per-schedule
#: stats); restart_* in ISSUE 13 (kill→restart recovery + torn-tail
#: fuzz verdicts); mesh_* in ISSUE 14 (the 100k-node sharded mesh
#: cell's scale/parity/collective-share lines); timeline_* in
#: ISSUE 15 (the failover timeline's phase-attribution lines riding
#: CHAOS_TIMELINE.json); store_* in ISSUE 16 (the MVCC store cell's
#: snapshot/write-txn latency and read-lock-share lines); worker_* in
#: ISSUE 17 (the multi-process scheduler worker cell's A/B speedup,
#: lease-reissue, and IPC round-trip lines); raft_* in ISSUE 18 (the
#: raft cell's pipelined-vs-synchronous commit-window attribution and
#: lease-read split); readplane_* in ISSUE 20 (the follower-read smoke's three mode-leg
#: verdicts — the fleet cell's read lines ride the fleet_* prefix)
_BENCH_KEY = re.compile(
    r"^(?:trace|contention|fleet|chaos|restart|mesh|timeline|store"
    r"|worker|raft|readplane)_[a-z0-9_]+$")
#: bench kwargs that are not emission keys (worker_batch_size is the
#: ServerConfig in-process dequeue window, not a trend line)
_BENCH_KEY_EXCLUDE = {"trace_id", "timeline_path", "worker_batch_size"}


def _fenced_block(doc: str, section: str) -> Optional[str]:
    """First fenced code block under ``## section``; None if absent."""
    marker = f"## {section}"
    if marker not in doc:
        return None
    tail = doc.split(marker, 1)[1]
    parts = tail.split("```")
    return parts[1] if len(parts) >= 2 else None


def _doc_tokens(block: str, pattern: re.Pattern) -> Set[str]:
    out: Set[str] = set()
    for line in block.splitlines():
        tok = line.strip().split(" ", 1)[0]
        if tok and pattern.fullmatch(tok):
            out.add(tok)
    return out


class TelemetryDriftRule:
    rule_id = RULE

    def check(self, ctx: Context) -> Iterable[Finding]:
        doc = ctx.read(DOC_REL)
        if doc is None:
            yield Finding(RULE, DOC_REL, 1, "", "doc-missing",
                          f"{DOC_REL} not found: the telemetry contract "
                          f"has no home")
            return
        yield from self._check_spans(ctx, doc)
        yield from self._check_prometheus(ctx, doc)
        yield from self._check_bench_keys(ctx, doc)

    # -- spans ------------------------------------------------------------

    def _emitted_spans(self, ctx: Context):
        """{name: (rel, line)} for literal sites; findings for
        unregistered dynamic sites."""
        emitted: Dict[str, Tuple[str, int]] = {}
        bad: List[Finding] = []
        for src in ctx.files:
            for node in ast.walk(src.tree):
                if not isinstance(node, ast.Call) or not node.args:
                    continue
                d = dotted_name(node.func)
                if d.rsplit(".", 1)[-1] not in ("span", "record") \
                        or "tracer" not in d:
                    continue
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(arg.value,
                                                                str):
                    name = arg.value
                    if not name.startswith(DYNAMIC_PREFIXES):
                        emitted.setdefault(name, (src.rel, node.lineno))
                elif isinstance(arg, ast.JoinedStr):
                    template = "".join(
                        v.value if isinstance(v, ast.Constant) else "{}"
                        for v in arg.values)
                    if template.startswith(DYNAMIC_PREFIXES):
                        continue
                    if template not in DYNAMIC:
                        bad.append(Finding(
                            RULE, src.rel, node.lineno,
                            src.scope_of(node),
                            f"span-dynamic:{template}",
                            f"dynamic span name {template!r} is not "
                            f"registered in graftcheck R5 DYNAMIC — "
                            f"register its expansions or use a "
                            f"literal"))
                        continue
                    for concrete in DYNAMIC[template]:
                        emitted.setdefault(concrete,
                                           (src.rel, node.lineno))
        return emitted, bad

    def _check_spans(self, ctx: Context, doc: str) -> Iterable[Finding]:
        emitted, bad = self._emitted_spans(ctx)
        yield from bad
        block = _fenced_block(doc, "Instrumented spans")
        if block is None:
            yield Finding(RULE, DOC_REL, 1, "", "spans-section-missing",
                          "TELEMETRY.md has no '## Instrumented spans' "
                          "fenced table")
            return
        documented = {
            tok for tok in _doc_tokens(block, _SPAN_NAME)
            if "{" not in tok
        }
        for name in sorted(set(emitted) - documented):
            rel, line = emitted[name]
            yield Finding(
                RULE, rel, line, "", f"span-undocumented:{name}",
                f"span {name!r} is emitted but missing from "
                f"{DOC_REL}'s span table")
        for name in sorted(documented - set(emitted)):
            yield Finding(
                RULE, DOC_REL, 1, "", f"span-stale:{name}",
                f"span {name!r} is documented in {DOC_REL} but no "
                f"longer emitted")

    # -- prometheus series ------------------------------------------------

    def _emitted_series(self, ctx: Context) -> Dict[str, Tuple[str, int]]:
        """nomad_tpu_* literals from string constants, docstrings
        excluded (prose must not mint series)."""
        out: Dict[str, Tuple[str, int]] = {}
        for src in ctx.files:
            docstring_nodes = set()
            for node in ast.walk(src.tree):
                if isinstance(node, (ast.Module, ast.ClassDef,
                                     ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    body = getattr(node, "body", [])
                    if body and isinstance(body[0], ast.Expr) \
                            and isinstance(body[0].value, ast.Constant):
                        docstring_nodes.add(body[0].value)
            for node in ast.walk(src.tree):
                if isinstance(node, ast.Constant) \
                        and isinstance(node.value, str) \
                        and node not in docstring_nodes:
                    for m in _PROM_NAME.finditer(node.value):
                        out.setdefault(m.group(0),
                                       (src.rel, node.lineno))
        return out

    def _check_prometheus(self, ctx: Context, doc: str) -> Iterable[Finding]:
        emitted = self._emitted_series(ctx)
        block = _fenced_block(doc, "Prometheus series")
        if block is None:
            yield Finding(RULE, DOC_REL, 1, "", "prom-section-missing",
                          "TELEMETRY.md has no '## Prometheus series' "
                          "fenced list")
            return
        documented = _doc_tokens(
            block, re.compile(r"nomad_tpu_[a-z0-9]+(?:_[a-z0-9]+)+"))
        for name in sorted(set(emitted) - documented):
            rel, line = emitted[name]
            yield Finding(
                RULE, rel, line, "", f"prom-undocumented:{name}",
                f"Prometheus series {name!r} is emitted but missing "
                f"from {DOC_REL}'s series list")
        for name in sorted(documented - set(emitted)):
            yield Finding(
                RULE, DOC_REL, 1, "", f"prom-stale:{name}",
                f"Prometheus series {name!r} is documented in "
                f"{DOC_REL} but no longer emitted")

    # -- bench emission keys ----------------------------------------------

    def _emitted_bench_keys(self, ctx: Context) -> Dict[str, int]:
        text = ctx.read(BENCH_REL)
        if text is None:
            return {}
        out: Dict[str, int] = {}
        for node in ast.walk(ast.parse(text)):
            if not isinstance(node, ast.Call):
                continue
            for kw in node.keywords:
                if kw.arg and _BENCH_KEY.fullmatch(kw.arg) \
                        and kw.arg not in _BENCH_KEY_EXCLUDE:
                    out.setdefault(kw.arg, node.lineno)
        return out

    def _check_bench_keys(self, ctx: Context, doc: str) -> Iterable[Finding]:
        emitted = self._emitted_bench_keys(ctx)
        if not emitted:
            return          # bench.py not part of this scan
        block = _fenced_block(doc, "Bench emission keys")
        if block is None:
            yield Finding(RULE, DOC_REL, 1, "", "bench-section-missing",
                          "TELEMETRY.md has no '## Bench emission keys' "
                          "fenced list")
            return
        documented = _doc_tokens(block, _BENCH_KEY)
        for name in sorted(set(emitted) - documented):
            yield Finding(
                RULE, BENCH_REL, emitted[name], "",
                f"bench-undocumented:{name}",
                f"bench key {name!r} is emitted but missing from "
                f"{DOC_REL}'s bench-key list")
        for name in sorted(documented - set(emitted)):
            yield Finding(
                RULE, DOC_REL, 1, "", f"bench-stale:{name}",
                f"bench key {name!r} is documented in {DOC_REL} but "
                f"no longer emitted by bench.py")
