"""Static audit of every Prometheus family the codebase renders.

The /metrics surface has grown across five PRs (engine counters, swap
state, zoo families, ingress phases, SLO burn rates) and its contracts
are easy to regress one call site at a time: a counter without the
``_total`` suffix breaks downstream PromQL idioms, a family without
HELP text fails strict scrapers, and one unbounded ``model=...`` label
re-opens the cardinality hole the zoo's hard cap closed. The runtime
grammar validator (tests/test_observability.py) only checks what a
given test run happens to render; this checker audits the SOURCE — the
kernel-checker discipline (tools/check_fusion_kernels.py) applied to
the metrics plane.

What it checks, per renderer call site (``r.counter`` / ``r.gauge`` /
``r.histogram`` / ``r.info`` / ``r.sample`` in the audited modules):

1. **HELP present** — the help-text argument is a non-empty string
   literal (the renderer emits ``# HELP``/``# TYPE`` from it; an empty
   or dynamic help is a docs hole at scrape time).
2. **Naming conventions** — counters end ``_total``; histogram
   families end in a unit suffix (``_ms``/``_s``/``_rows``/
   ``_bytes``); gauges/infos must NOT end in ``_total`` or the
   reserved histogram suffixes (``_bucket``/``_sum``/``_count``).
3. **Dynamic names declared** — an f-string family name (e.g.
   ``f"serving_{name}"``) must appear in ``DYNAMIC_OK`` with its full
   expected expansion list, and every expansion passes rule 2: the
   audit must never shrug at a name it cannot see.
4. **Cardinality caps declared** — any family labelled with an
   unbounded-identity key (``model``/``version``/``tenant``) must be
   listed in ``CAPPED_FAMILIES``, whose entries are families documented
   to render under a hard cap (zoo ``label_cardinality_cap``, SLO
   ``label_cap``). A new per-model family is a one-line diff here —
   made consciously, with the cap story written down.
5. **Raw samples continue a family** — ``r.sample`` (header-less) must
   reuse a family name already declared by a headered call in the same
   module.

Run from the repo root::

    python tools/check_metrics.py

Exit 1 + a listing on any violation. Tier-1 runs this from
tests/test_slo.py alongside the kernel checkers, plus
checker-catches-violation tests feeding known-bad snippets through
``audit_source``.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Any, Dict, List, Optional, Set, Tuple

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

# the modules that render Prometheus families
AUDIT_FILES = (
    "mmlspark_tpu/core/prometheus.py",
    "mmlspark_tpu/serving/server.py",
    "mmlspark_tpu/serving/fleet.py",
)

RENDER_METHODS = {"counter", "gauge", "histogram", "info", "sample"}
# receivers that LOOK like renderer calls but aren't (logger.info)
_EXCLUDED_RECEIVERS = {"log", "logger", "logging", "self", "cls"}

HISTOGRAM_SUFFIXES = ("_ms", "_s", "_rows", "_bytes")
RESERVED_SUFFIXES = ("_total", "_bucket", "_sum", "_count")

# label keys that identify an unbounded population: any family carrying
# one must declare its cardinality story in CAPPED_FAMILIES
UNBOUNDED_LABEL_KEYS = {"model", "version", "tenant", "feature"}

# families allowed to carry unbounded-identity labels, because their
# renderers are hard-capped at the source:
CAPPED_FAMILIES = {
    # zoo: resident-first rows capped at label_cardinality_cap;
    # latency overflow folds into model="_other" (docs/model_zoo.md)
    "serving_model_info",
    "serving_model_latency_ms",
    # SLO engine: per-model streams capped at SLOMonitor.label_cap,
    # overflow folds into "_other"; active alerts inherit the same
    # capped identity space (docs/observability.md)
    "serving_slo_model_burn_rate",
    "serving_slo_alert_active",
    # drift exposition: per-feature scores capped at DRIFT_FEATURE_CAP
    # (top-K by score), overflow folds into feature="_other"
    # (core/prometheus.py drift_families)
    "serving_drift_score",
    # placement plane: per-model replica gauges capped at
    # REPLICA_LABEL_CAP, overflow summed into model="_other"
    # (core/prometheus.py placement_families)
    "serving_placement_replicas",
    # variant plane: per-model rung/floor gauges + the info row capped
    # at VARIANT_LABEL_CAP declared ladders (core/prometheus.py
    # variant_families; docs/adaptive_serving.md)
    "serving_variant_rung",
    "serving_variant_floor",
    "serving_variant_info",
}

# dynamic (f-string) family names, with their FULL expected expansions —
# every expansion is suffix-checked like a literal. Key: the template
# with "{}" placeholders, as extracted from the JoinedStr.
DYNAMIC_OK: Dict[str, Tuple[str, ...]] = {
    # engine/fleet per-stage histograms + the warmup family
    "serving_{}": ("serving_queue_wait_ms", "serving_collect_wait_ms",
                   "serving_token_wait_ms", "serving_decode_ms",
                   "serving_dispatch_wait_ms", "serving_pipeline_ms",
                   "serving_respond_ms", "serving_batch_rows",
                   "serving_worker_idle_ms", "serving_model_warmup_ms"),
    # pipeline_families: the model's own histogram hooks (TPUModel
    # pad/device/readback split)
    "serving_model_{}": ("serving_model_pad_ms",
                         "serving_model_device_ms",
                         "serving_model_readback_ms"),
    # device memory gauges (utils/profiling.device_memory_stats keys)
    "device_memory_{}": ("device_memory_bytes_in_use",
                         "device_memory_bytes_limit",
                         "device_memory_peak_bytes_in_use"),
}


class Violation:
    def __init__(self, filename: str, line: int, message: str):
        self.filename = filename
        self.line = line
        self.message = message

    def __repr__(self) -> str:
        return f"{self.filename}:{self.line}: {self.message}"


def _template_of(node: ast.AST) -> Optional[str]:
    """A Constant string verbatim; a JoinedStr as a "{}" template;
    None for anything the audit cannot see through."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.JoinedStr):
        parts: List[str] = []
        for value in node.values:
            if isinstance(value, ast.Constant):
                parts.append(str(value.value))
            else:
                parts.append("{}")
        return "".join(parts)
    return None


def _label_keys(node: Optional[ast.AST]) -> Set[str]:
    """String keys of a labels argument: dict literals (including
    ``{**base, "k": v}`` — the spread contributes nothing statically)
    and dict() calls with keyword args."""
    keys: Set[str] = set()
    if node is None:
        return keys
    if isinstance(node, ast.Dict):
        for k in node.keys:
            if isinstance(k, ast.Constant) and isinstance(k.value, str):
                keys.add(k.value)
    elif isinstance(node, ast.Call) and \
            isinstance(node.func, ast.Name) and node.func.id == "dict":
        for kw in node.keywords:
            if kw.arg is not None:
                keys.add(kw.arg)
    return keys


def _check_name(method: str, name: str, filename: str, line: int,
                out: List[Violation]) -> None:
    if method == "counter" and not name.endswith("_total"):
        out.append(Violation(
            filename, line,
            f"counter {name!r} must end in '_total'"))
    if method == "histogram" and \
            not name.endswith(HISTOGRAM_SUFFIXES):
        out.append(Violation(
            filename, line,
            f"histogram {name!r} must end in a unit suffix "
            f"{HISTOGRAM_SUFFIXES}"))
    if method in ("gauge", "info") and \
            name.endswith(RESERVED_SUFFIXES):
        out.append(Violation(
            filename, line,
            f"{method} {name!r} ends in a reserved suffix "
            f"{RESERVED_SUFFIXES} (counters own '_total'; histograms "
            f"own '_bucket'/'_sum'/'_count')"))


def audit_source(src: str, filename: str = "<string>"
                 ) -> List[Violation]:
    """Audit one module's source. Returns the violation list."""
    out: List[Violation] = []
    tree = ast.parse(src, filename=filename)
    declared: Set[str] = set()     # families with HELP in this module

    # source order, not ast.walk's BFS order: the sample-continues-a-
    # declared-family rule depends on seeing declarations first
    calls = sorted(
        (n for n in ast.walk(tree) if isinstance(n, ast.Call)),
        key=lambda n: (n.lineno, n.col_offset))
    for node in calls:
        func = node.func
        if not isinstance(func, ast.Attribute) or \
                func.attr not in RENDER_METHODS:
            continue
        if not isinstance(func.value, ast.Name) or \
                func.value.id in _EXCLUDED_RECEIVERS:
            continue
        method = func.attr
        line = node.lineno
        if not node.args:
            out.append(Violation(filename, line,
                                 f"{method} call with no name argument"))
            continue
        template = _template_of(node.args[0])
        if template is None:
            out.append(Violation(
                filename, line,
                f"{method} family name is not a (f-)string literal — "
                f"the audit cannot verify it; render through a literal "
                f"or an f-string declared in DYNAMIC_OK"))
            continue
        if "{}" in template:
            expansions = DYNAMIC_OK.get(template)
            if expansions is None:
                out.append(Violation(
                    filename, line,
                    f"dynamic family name {template!r} is not declared "
                    f"in DYNAMIC_OK (tools/check_metrics.py) — list its "
                    f"full expected expansions"))
                names: Tuple[str, ...] = ()
            else:
                names = expansions
        else:
            names = (template,)
        for name in names:
            _check_name(method, name, filename, line, out)
        # HELP text: 2nd positional (or help_text kw) must be a
        # non-empty string literal — except r.sample, which continues
        # an already-declared family (and must not mint one itself)
        if method == "sample":
            for name in names:
                if name not in declared:
                    out.append(Violation(
                        filename, line,
                        f"raw sample {name!r} does not continue a "
                        f"family declared (with HELP) in this module"))
            continue
        declared.update(names)
        help_node: Optional[ast.AST] = None
        if len(node.args) >= 2:
            help_node = node.args[1]
        else:
            for kw in node.keywords:
                if kw.arg == "help_text":
                    help_node = kw.value
        help_text = _template_of(help_node) if help_node is not None \
            else None
        if not help_text or not help_text.strip():
            out.append(Violation(
                filename, line,
                f"{method} family {names or template!r} has no literal "
                f"non-empty HELP text"))
        # cardinality: unbounded-identity labels require a declared cap
        labels_node: Optional[ast.AST] = None
        pos = {"counter": 3, "gauge": 3, "histogram": 3, "info": 2}
        if len(node.args) > pos[method]:
            labels_node = node.args[pos[method]]
        else:
            for kw in node.keywords:
                if kw.arg == "labels":
                    labels_node = kw.value
        hot = _label_keys(labels_node) & UNBOUNDED_LABEL_KEYS
        if hot:
            for name in names:
                if name not in CAPPED_FAMILIES:
                    out.append(Violation(
                        filename, line,
                        f"family {name!r} carries unbounded-identity "
                        f"label(s) {sorted(hot)} but is not declared in "
                        f"CAPPED_FAMILIES — document its hard "
                        f"cardinality cap first"))
    return out


def audit_file(path: str) -> List[Violation]:
    with open(path, "r", encoding="utf-8") as f:
        src = f.read()
    return audit_source(src, filename=os.path.relpath(path, _REPO))


def main() -> int:
    violations: List[Violation] = []
    audited = 0
    for rel in AUDIT_FILES:
        path = os.path.join(_REPO, rel)
        violations += audit_file(path)
        audited += 1
    if violations:
        print(f"{len(violations)} metrics-exposition violation(s) "
              f"across {audited} audited modules:")
        for v in violations:
            print("  -", v)
        return 1
    print(f"OK: {audited} modules audited — every family has HELP, "
          f"passes naming conventions, and every unbounded label is "
          f"cap-declared")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
