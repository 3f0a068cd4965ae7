"""AST-based repo lint: repo-specific hazard rules with per-rule codes
and an inline waiver syntax (DESIGN.md §11).

Rules (all severity "error"; unwaived findings gate CI):

  RL001 time-time-monotonic   `time.time()` call — wall-clock steps under
                              NTP; interval/staleness logic must use
                              `time.monotonic()`. Waive the few legit
                              wall-clock sites (checkpoint manifests,
                              bench record stamps).
  RL002 optional-truthiness   truthiness test on an Optional[float]
                              request field (`arrival`, `deadline_s`, ...)
                              — 0.0 is falsy but is a REAL value (the
                              PR-6 arrival=0.0 bug class); use `is None`.
  RL003 kv-dtype-compare      raw string compare against kv_dtype —
                              route through kvquant.validate_kv_dtype /
                              kvquant.is_int8 so typos fail loudly.
  RL004 tracer-host-pull      jax.device_get / np.asarray in the serve
                              tick or train step hot path — each is a
                              device sync; the hot loop budgets exactly
                              one.
  RL005 bench-no-block        a benchmark function timing with >=2
                              perf_counter/monotonic calls and no
                              block_until_ready — measures dispatch, not
                              compute.
  RL006 unclamped-index-map   in a kernel module using scalar prefetch, a
                              BlockSpec index_map reads a prefetch ref
                              without clamping (jnp.minimum/clip) — an
                              out-of-range block index faults or reads
                              garbage on real hardware.
  RL007 obs-site-name         a string-literal site/metric name passed to
                              an obs call (span/instant/trace_event/
                              counter/gauge/histogram/series) or to
                              jax.named_scope that is not a lowercase
                              dotted identifier under a registered prefix
                              (repro.obs.sites) — a typo'd site silently
                              forks the timeline.

Waiver syntax — same line or the line above the finding:

    x = time.time()  # lint: waive RL001 manifest wants wall-clock

Waived findings still appear in reports (waived=True) but never fail CI.
"""
from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys
from typing import Dict, List, Optional, Set, Tuple

from repro.analysis.report import Finding
from repro.obs.sites import SITE_PREFIXES, SITE_RE

WAIVER_RE = re.compile(r"#\s*lint:\s*waive\s+([A-Z]{2}\d{3})\b\s*(.*)")

OPTIONAL_FIELDS = {"arrival", "deadline_s", "first_tok_mono", "done_mono",
                   "ttft_s"}
KV_DTYPE_LITERALS = {"model", "int8"}
KV_VALIDATORS = {"validate_kv_dtype", "is_int8"}
# hot functions per module basename for RL004: the serve tick and the
# train step loop — the paths where an extra sync is a throughput bug
HOT_FUNCS = {"engine.py": {"_tick"}, "trainer.py": {"train"}}
TIMER_ATTRS = {"perf_counter", "monotonic"}
CLAMP_NAMES = {"minimum", "clip"}
# obs recording entry points for RL007: any string-literal first arg is a
# site/metric name and must validate against repro.obs.sites; a named
# scope is the device-side site the benchmark reads from the trace
OBS_CALLS = {"span", "instant", "trace_event", "counter", "gauge",
             "histogram", "series", "named_scope"}


def _func_name(call: ast.Call) -> Optional[str]:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


def _is_module_call(call: ast.Call, modules: Set[str], attr: str) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == attr
            and isinstance(f.value, ast.Name) and f.value.id in modules)


def collect_waivers(src: str) -> Dict[int, Tuple[str, str]]:
    """line -> (code, reason). A waiver covers its own line and the next
    (so a comment line directly above the offending statement works)."""
    out: Dict[int, Tuple[str, str]] = {}
    for i, line in enumerate(src.splitlines(), start=1):
        m = WAIVER_RE.search(line)
        if m:
            out[i] = (m.group(1), m.group(2).strip())
    return out


# ---------------------------------------------------------------------------
# rules — each returns [(code, lineno, message)]

RuleHit = Tuple[str, int, str]


def rule_time_time(tree: ast.AST) -> List[RuleHit]:
    hits = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_module_call(node, {"time"},
                                                          "time"):
            hits.append(("RL001", node.lineno,
                         "time.time() is wall-clock (NTP can step it); "
                         "use time.monotonic() for intervals/staleness"))
    return hits


class _TruthinessVisitor(ast.NodeVisitor):
    def __init__(self):
        self.hits: List[RuleHit] = []

    def _check(self, node: ast.AST) -> None:
        name = None
        if isinstance(node, ast.Attribute):
            name = node.attr
        elif isinstance(node, ast.Name):
            name = node.id
        if name in OPTIONAL_FIELDS:
            self.hits.append((
                "RL002", node.lineno,
                f"truthiness test on Optional[float] field '{name}': 0.0 "
                "is falsy but is a real value — test `is None` / "
                "`is not None`"))

    def visit_If(self, node):
        self._check(node.test)
        self.generic_visit(node)

    def visit_While(self, node):
        self._check(node.test)
        self.generic_visit(node)

    def visit_IfExp(self, node):
        self._check(node.test)
        self.generic_visit(node)

    def visit_Assert(self, node):
        self._check(node.test)
        self.generic_visit(node)

    def visit_BoolOp(self, node):
        for v in node.values:
            self._check(v)
        self.generic_visit(node)

    def visit_UnaryOp(self, node):
        if isinstance(node.op, ast.Not):
            self._check(node.operand)
        self.generic_visit(node)

    def _comp(self, node):
        for gen in node.generators:
            for cond in gen.ifs:
                self._check(cond)
        self.generic_visit(node)

    visit_ListComp = visit_SetComp = visit_DictComp = _comp
    visit_GeneratorExp = _comp


def rule_optional_truthiness(tree: ast.AST) -> List[RuleHit]:
    v = _TruthinessVisitor()
    v.visit(tree)
    return v.hits


def _mentions_kv_dtype(node: ast.AST) -> bool:
    for n in ast.walk(node):
        if isinstance(n, ast.Name) and n.id == "kv_dtype":
            return True
        if isinstance(n, ast.Attribute) and n.attr == "kv_dtype":
            return True
    return False


def _routes_through_validator(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Call) and _func_name(n) in KV_VALIDATORS
               for n in ast.walk(node))


def rule_kv_dtype_compare(tree: ast.AST) -> List[RuleHit]:
    hits = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Compare):
            continue
        sides = [node.left] + list(node.comparators)
        has_literal = any(isinstance(s, ast.Constant)
                          and s.value in KV_DTYPE_LITERALS for s in sides)
        kv_sides = [s for s in sides if _mentions_kv_dtype(s)]
        if (has_literal and kv_sides
                and not any(_routes_through_validator(s) for s in kv_sides)):
            hits.append(("RL003", node.lineno,
                         "raw string compare against kv_dtype; use "
                         "kvquant.validate_kv_dtype / kvquant.is_int8 so "
                         "an invalid dtype fails loudly"))
    return hits


def rule_tracer_host_pull(tree: ast.AST, basename: str) -> List[RuleHit]:
    hot = HOT_FUNCS.get(basename)
    if not hot:
        return []
    hits = []
    for node in ast.walk(tree):
        if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and node.name in hot):
            continue
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            if (_is_module_call(sub, {"jax"}, "device_get")
                    or _is_module_call(sub, {"np", "numpy"}, "asarray")):
                hits.append((
                    "RL004", sub.lineno,
                    f"host pull ({ast.unparse(sub.func)}) in hot path "
                    f"'{node.name}': each is a device sync — the loop "
                    "budgets exactly one (waive it)"))
    return hits


def rule_bench_no_block(tree: ast.AST) -> List[RuleHit]:
    hits = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        timers = sum(1 for sub in ast.walk(node)
                     if isinstance(sub, ast.Call)
                     and isinstance(sub.func, ast.Attribute)
                     and sub.func.attr in TIMER_ATTRS
                     and isinstance(sub.func.value, ast.Name)
                     and sub.func.value.id == "time")
        blocks = any(isinstance(sub, ast.Attribute)
                     and sub.attr == "block_until_ready"
                     for sub in ast.walk(node))
        if timers >= 2 and not blocks:
            hits.append((
                "RL005", node.lineno,
                f"benchmark fn '{node.name}' times ({timers} timer calls) "
                "without block_until_ready — async dispatch makes the "
                "interval measure launch overhead, not compute"))
    return hits


def _contains_clamp(node: ast.AST, local_fns: Dict[str, ast.AST],
                    seen: Optional[Set[str]] = None) -> bool:
    """Clamp = jnp.minimum/.clip (or bare minimum/clip) in the body, or a
    call to a local function whose body clamps (index_maps may delegate,
    e.g. scale_block reusing kv_block's clamped page lookup)."""
    seen = seen if seen is not None else set()
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in CLAMP_NAMES:
            return True
        if isinstance(n, ast.Name) and n.id in CLAMP_NAMES:
            return True
        if isinstance(n, ast.Call):
            callee = _func_name(n)
            if callee in local_fns and callee not in seen:
                seen.add(callee)
                if _contains_clamp(local_fns[callee], local_fns, seen):
                    return True
    return False


def rule_unclamped_index_map(tree: ast.AST) -> List[RuleHit]:
    """Kernel modules using PrefetchScalarGridSpec(num_scalar_prefetch=k):
    an index_map's trailing k params are the scalar-prefetch refs; reading
    one without a clamp means a data-dependent block index can run off the
    end of the operand. Uses the module's max k (conservative)."""
    max_k = 0
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            for kw in node.keywords:
                if (kw.arg == "num_scalar_prefetch"
                        and isinstance(kw.value, ast.Constant)
                        and isinstance(kw.value.value, int)):
                    max_k = max(max_k, kw.value.value)
    if max_k == 0:
        return []
    local_fns: Dict[str, ast.AST] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            local_fns[node.name] = node
        elif (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Lambda)):
            local_fns[node.targets[0].id] = node.value

    hits = []
    checked: Set[int] = set()

    def check_index_map(fn: ast.AST) -> None:
        if id(fn) in checked:
            return
        checked.add(id(fn))
        args = fn.args.args
        prefetch = {a.arg for a in args[-max_k:]} if len(args) > max_k \
            else set()
        body = fn.body if isinstance(fn, ast.Lambda) else fn
        reads = any(isinstance(n, ast.Name) and n.id in prefetch
                    and isinstance(n.ctx, ast.Load)
                    for n in ast.walk(body))
        if reads and not _contains_clamp(body, local_fns):
            name = getattr(fn, "name", "<lambda>")
            hits.append((
                "RL006", fn.lineno,
                f"index_map '{name}' reads a scalar-prefetch ref without "
                "clamping (jnp.minimum/clip); a data-dependent block index "
                "must be clamped to the operand extent"))

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _func_name(node) == "BlockSpec"):
            continue
        candidates = [kw.value for kw in node.keywords
                      if kw.arg == "index_map"]
        candidates += list(node.args)
        for cand in candidates:
            if isinstance(cand, ast.Lambda):
                check_index_map(cand)
            elif isinstance(cand, ast.Name) and cand.id in local_fns:
                check_index_map(local_fns[cand.id])
    return hits


def rule_obs_site_names(tree: ast.AST) -> List[RuleHit]:
    """RL007: string-literal site names at obs call sites must be lowercase
    dotted identifiers under a registered prefix. Dynamic names (f-strings,
    variables) are runtime-checked by check_site instead."""
    hits = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and _func_name(node) in OBS_CALLS and node.args):
            continue
        a = node.args[0]
        if not (isinstance(a, ast.Constant) and isinstance(a.value, str)):
            continue
        site = a.value
        if not SITE_RE.match(site):
            hits.append((
                "RL007", node.lineno,
                f"obs site {site!r} is not a lowercase dotted identifier "
                "(expected e.g. 'lms.swap_in')"))
        elif site.split(".", 1)[0] not in SITE_PREFIXES:
            hits.append((
                "RL007", node.lineno,
                f"obs site {site!r} uses unregistered prefix "
                f"{site.split('.', 1)[0]!r}; registered: "
                f"{', '.join(sorted(SITE_PREFIXES))} (repro.obs.sites)"))
    return hits


# ---------------------------------------------------------------------------
# file / tree drivers

def lint_source(src: str, path: str, repo_root: str = "") -> List[Finding]:
    rel = os.path.relpath(path, repo_root) if repo_root else path
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [Finding("RL000", f"syntax error: {e}", f"{rel}:{e.lineno}")]
    basename = os.path.basename(path)
    hits: List[RuleHit] = []
    hits += rule_time_time(tree)
    hits += rule_optional_truthiness(tree)
    hits += rule_kv_dtype_compare(tree)
    hits += rule_tracer_host_pull(tree, basename)
    hits += rule_obs_site_names(tree)
    if f"{os.sep}benchmarks{os.sep}" in path or \
            os.path.basename(os.path.dirname(path)) == "benchmarks":
        hits += rule_bench_no_block(tree)
    if f"{os.sep}kernels{os.sep}" in path:
        hits += rule_unclamped_index_map(tree)

    waivers = collect_waivers(src)
    findings = []
    for code, lineno, msg in sorted(hits, key=lambda h: (h[1], h[0])):
        waived, reason = False, ""
        for wline in (lineno, lineno - 1):
            w = waivers.get(wline)
            if w and w[0] == code:
                waived, reason = True, w[1]
                break
        findings.append(Finding(code, msg, f"{rel}:{lineno}",
                                waived=waived, waiver_reason=reason))
    return findings


def lint_file(path: str, repo_root: str = "") -> List[Finding]:
    with open(path, "r") as f:
        return lint_source(f.read(), path, repo_root)


def lint_paths(paths, repo_root: str = "") -> List[Finding]:
    findings: List[Finding] = []
    for p in paths:
        if os.path.isfile(p):
            findings.extend(lint_file(p, repo_root))
            continue
        for dirpath, _, names in sorted(os.walk(p)):
            for name in sorted(names):
                if name.endswith(".py"):
                    findings.extend(lint_file(os.path.join(dirpath, name),
                                              repo_root))
    return findings


def default_paths() -> Tuple[str, List[str]]:
    """(repo_root, [lint roots]) resolved from this file's location."""
    root = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "..", ".."))
    roots = [os.path.join(root, "src", "repro")]
    bench = os.path.join(root, "benchmarks")
    if os.path.isdir(bench):
        roots.append(bench)
    return root, roots


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", help="files/dirs (default: "
                    "src/repro + benchmarks)")
    ap.add_argument("--json", default="", help="write findings as JSON")
    args = ap.parse_args(argv)
    root, roots = default_paths()
    findings = lint_paths(args.paths or roots, root)
    gating = [f for f in findings if f.gating]
    for f in findings:
        tag = "waived" if f.waived else f.severity.upper()
        print(f"[{tag}] {f.code} {f.where}: {f.message}")
    print(f"lint: {len(gating)} gating finding(s), "
          f"{len(findings) - len(gating)} waived")
    if args.json:
        with open(args.json, "w") as f:
            json.dump([x.to_dict() for x in findings], f, indent=1)
    return 1 if gating else 0


if __name__ == "__main__":
    sys.exit(main())
