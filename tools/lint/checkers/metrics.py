"""metric-docs + metric-tags: the metric plane's two static rails.

metric-docs is the PR 3 drift check (tools/check_metrics_docs.py,
now a thin shim over this module): every metric the code emits must be
catalogued in docs/observability.md and every catalogued name must have
an emitter. Project-level — it reads the whole source tree and the doc.

metric-tags is the cardinality rule: tag KEYS must come from the
documented vocabulary below (a new key is a conscious schema decision,
not a typo), and tag VALUES must never be raw request content — a query
string or peer URL as a tag value mints an unbounded series per distinct
request and OOMs the in-memory registry (the classic cardinality bomb).
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Iterable, Optional

from tools.lint.core import REPO_ROOT, Checker, SourceFile, Violation

SRC_DIR = REPO_ROOT / "pilosa_tpu"
DOC = REPO_ROOT / "docs" / "observability.md"

# -- metric-docs scan (shared with the tools/check_metrics_docs.py shim) ---

#: Metric families emitted with computed (f-string) names: the checker
#: cannot read them statically, so each must keep a doc mention of the
#: spelled-out family (asserted below so the exemption itself can't rot).
DYNAMIC_FAMILIES = {
    # executor.py: stats.count(f"query_{call.name}_total")
    "query_<Call>_total",
}

#: A doc token must end in one of these to be treated as a metric name
#: (after stripping histogram/exporter suffixes, so a plain JSON field
#: like `device_count` does not match).
METRIC_SUFFIXES = (
    "_total", "_seconds", "_bytes", "_pending", "_done",
    "_inflight", "_up", "_fds", "_threads", "_nodes", "_fields",
    "_shards", "_evictions", "_rederives", "_state",
    "_occupancy", "_queries", "_ops", "_entries",
    "_programs", "_live", "_heat", "_depth", "_opened", "_words",
)

_CALL_RE = re.compile(
    r"""\.(?:count|gauge|timing|histogram|timer|remove_gauge)\(\s*
        ["']([a-z][a-z0-9_.]*)["']""",
    re.VERBOSE,
)

_TOKEN_RE = re.compile(r"`([^`\n]+)`")

_EXPORT_SUFFIX_RE = re.compile(r"_(?:bucket|count|sum|p50|p95|p99|p999)$")

#: Series synthesized as literal exposition lines (no StatsClient call):
#: the /metrics/cluster scrape-health pair. Each must still appear as a
#: literal in the source, which source_metrics verifies.
SYNTHESIZED = ("cluster_scrape_up", "cluster_scrape_seconds")


def source_metrics(src_dir: Optional[Path] = None) -> set[str]:
    names: set[str] = set()
    all_text = []
    for path in sorted((src_dir or SRC_DIR).rglob("*.py")):
        text = path.read_text()
        all_text.append(text)
        for m in _CALL_RE.finditer(text):
            names.add(m.group(1).replace(".", "_").replace("-", "_"))
    blob = "\n".join(all_text)
    for name in SYNTHESIZED:
        if name in blob:
            names.add(name)
    return names


def doc_tokens(doc_text: Optional[str] = None) -> tuple[set[str], set[str]]:
    """(exact metric-shaped tokens, wildcard prefixes) from the doc."""
    exact: set[str] = set()
    wildcards: set[str] = set()
    for tok in _TOKEN_RE.findall(
        doc_text if doc_text is not None else DOC.read_text()
    ):
        tok = tok.strip()
        tok = re.sub(r"\{[^}]*\}$", "", tok)  # strip {tags}
        if tok.startswith("pilosa_"):
            tok = tok[len("pilosa_"):]
        if re.fullmatch(r"[a-z][a-z0-9_]*_\*", tok):
            wildcards.add(tok[:-2])
            continue
        if not re.fullmatch(r"[a-z][a-z0-9_]*", tok):
            continue
        base = _EXPORT_SUFFIX_RE.sub("", tok)
        if base.endswith(METRIC_SUFFIXES):
            exact.add(base)
    return exact, wildcards


def metrics_docs_drift(
    src: Optional[set[str]] = None, doc_text: Optional[str] = None
) -> list[str]:
    """Human-readable drift findings (empty = clean). Injectable inputs
    so the rule itself is testable without mutating the repo."""
    src = src if src is not None else source_metrics()
    doc_exact, doc_wild = doc_tokens(doc_text)
    text = doc_text if doc_text is not None else DOC.read_text()
    out = []
    for n in sorted(src):
        if n not in doc_exact and not any(n.startswith(w) for w in doc_wild):
            out.append(f"emitted but not documented: {n}")
    for t in sorted(doc_exact):
        if t not in src:
            out.append(f"documented but not emitted: {t}")
    for fam in sorted(DYNAMIC_FAMILIES):
        if fam not in text:
            out.append(f"dynamic family missing its doc mention: {fam}")
    return out


class MetricDocsChecker(Checker):
    rule = "metric-docs"
    doc = ("every emitted metric documented in docs/observability.md, "
           "every documented metric emitted (PR 3's drift check)")
    scope = ("pilosa_tpu",)
    project_level = True

    def finalize(self, files) -> Iterable[Violation]:
        for finding in metrics_docs_drift():
            yield Violation(
                rule=self.rule, path="docs/observability.md", line=1,
                message=finding,
                hint="add the catalogue entry or remove the dead name "
                     "(python tools/check_metrics_docs.py for the "
                     "two-way report)",
            )


# -- metric-tags: tag-key vocabulary + value-cardinality rule --------------

#: The documented tag-key vocabulary (docs/development.md "Metric
#: discipline"). Keys are bounded enumerations by construction:
ALLOWED_TAG_KEYS = {
    "route",   # HTTP route handler name (route table is finite)
    "method",  # HTTP verb / client op name
    "call",    # PQL call name (parser vocabulary)
    "phase",   # query lifecycle phase (qprofile.PHASES)
    "kind",    # leg/launch kind (batcher LEG_KINDS + program kinds)
    "index",   # index name (operator-created, bounded by schema)
    "field",   # field name (operator-created, bounded by schema)
    "peer",    # peer host:port (bounded by cluster size)
    "node",    # node id (bounded by cluster size)
    "tier",    # container representation tier (dense/array/run)
    "class",   # error class (4xx/5xx/transport/decode)
    "state",   # cluster state enum + connection lifecycle state
               # (server/connplane.py STATES — 8 literals)
    "role",    # thread role (utils/threads.py vocabulary: one literal
               # per spawn site + main/unknown — bounded by
               # construction, NEVER a thread name or peer address)
    "to",      # state-transition target enum
    "won",     # hedge winner (hedge/primary)
    "direction",  # directed-repair resolution (remote_wins/local_wins)
    "reason",  # bounded failure-reason enum (device fallback, import shed)
    "outcome", # recovery outcome enum (replayed/truncated/corrupt)
    "le",      # histogram bucket bound (static BUCKET_BOUNDS)
    "site",    # instrumented-lock site name (utils/locks call sites)
    "program", # device-program ledger kind (program kinds are finite)
    "step",    # a step of a drain (utils/qprofile.py DRAIN_STEPS plus the
               # phases a drain can pass through) or of Fragment.close()
               # (five literals + attr_stores): literals at the call
               # sites, never request content
    "generation",  # garbage-collector generation (0, 1, 2)
    "shape",   # canonical-PQL shape fingerprint (pql/ast.py shape_key:
               # structure only — call vocabulary x schema field names;
               # literals never survive into the key)
    "path",    # which of two code paths did the work (two literals at
               # the call sites: columnar/objects); never a file or URL
               # path
}

#: Variable names that smell like raw request content. A tag VALUE
#: rendered from one of these is an unbounded-cardinality series.
FORBIDDEN_VALUE_NAMES = {
    "query", "pql", "sql", "url", "uri", "path", "body", "text",
    "raw", "msg", "message", "detail", "payload", "line",
}


class TagCardinalityChecker(Checker):
    rule = "metric-tags"
    doc = ("with_tags keys must come from the documented vocabulary; "
           "values must never be raw query strings / URLs / bodies")
    # Unscoped: the default tree is pilosa_tpu/ already; explicit paths
    # (fixtures, --changed) must still be checkable.
    scope = ("",)

    def check_file(self, f: SourceFile) -> Iterable[Violation]:
        for node in ast.walk(f.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "with_tags"
            ):
                continue
            for arg in node.args:
                yield from self._check_tag(f, node, arg)

    def _check_tag(self, f, call, arg) -> Iterable[Violation]:
        key = None
        value_names: list[str] = []
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            key = arg.value.split(":", 1)[0]
        elif isinstance(arg, ast.JoinedStr) and arg.values:
            head = arg.values[0]
            if isinstance(head, ast.Constant) and isinstance(head.value, str):
                key = head.value.split(":", 1)[0]
            for part in arg.values:
                if isinstance(part, ast.FormattedValue) and isinstance(
                    part.value, ast.Name
                ):
                    value_names.append(part.value.id)
        else:
            return  # *tags forwarding / non-literal: out of static reach
        if key is None or not re.fullmatch(r"[a-z][a-z0-9_]*", key or ""):
            if f.waive(self.rule, arg.lineno, arg.end_lineno):
                return
            yield Violation(
                rule=self.rule, path=f.rel, line=arg.lineno,
                message="tag without a literal `key:` prefix",
                hint='tags are "key:value" with a key from the '
                     "documented vocabulary",
            )
            return
        if key not in ALLOWED_TAG_KEYS:
            if not f.waive(self.rule, arg.lineno, arg.end_lineno):
                yield Violation(
                    rule=self.rule, path=f.rel, line=arg.lineno,
                    message=f"unknown tag key {key!r}",
                    hint="new tag keys are a schema decision: add to "
                         "ALLOWED_TAG_KEYS (tools/lint/checkers/"
                         "metrics.py) with a boundedness rationale and "
                         "document it in docs/development.md",
                )
            return
        for vn in value_names:
            if vn.lower() in FORBIDDEN_VALUE_NAMES:
                if f.waive(self.rule, arg.lineno, arg.end_lineno):
                    continue
                yield Violation(
                    rule=self.rule, path=f.rel, line=arg.lineno,
                    message=f"tag value interpolates {vn!r} — raw "
                            "request content is unbounded cardinality",
                    hint="tag a bounded enum (route/op/class) instead; "
                         "the raw value belongs in logs/traces",
                )
