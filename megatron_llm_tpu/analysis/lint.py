"""graft-check pass 1: an AST linter for JAX trace discipline.

Pure-`ast`, no jax import — the rules encode how THIS repo is allowed
to touch the tracer:

- GR001 tracer-host-sync      .item() / float() / int() / bool() /
                              np.asarray / np.array on non-constant
                              values inside traced code — each forces a
                              concretization that either fails under
                              jit or silently pins a host round-trip.
- GR002 jit-in-loop           jax.jit / pjit constructed inside a
                              for/while body or a comprehension: a
                              fresh wrapper per iteration defeats jit's
                              call cache and retraces every time.
- GR003 unhashable-static     static_argnums / static_argnames given a
                              list/set/dict display: unhashable the
                              moment the wrapper is reused as a cache
                              key (functools.partial application, LRU
                              keys) — tuples or bare ints only.
- GR004 host-entropy          time.* / random.* / np.random.* inside
                              traced code: evaluated ONCE at trace
                              time, then frozen into the executable —
                              the classic "my timestamp never changes"
                              / "my noise is identical every step" bug.
- GR005 unordered-pytree      iterating a set (display or set(...)
                              call) to build containers inside traced
                              code: set order is hash-seed dependent,
                              so the pytree structure — and the
                              executable — can differ between
                              processes that must agree (multi-host
                              lockstep dispatch).
- GR006 hot-loop-host-sync    device_get / block_until_ready /
                              np.asarray / float() / int() inside the
                              engine serve loop's per-round path and
                              the trainer's step path (HOT_PATHS):
                              every one is a device stall per round;
                              deliberate ones carry a baseline
                              justification.
- GR007 unregistered-jit      bare jax.jit in megatron_llm_tpu/ with no
                              compile-contract registration marker: an
                              entry point the AOT audit cannot see.
                              Mark registered sites with a
                              `# graft-contract: <name>` comment.

Accepted findings live in `lint_baseline.json` next to this file, one
justification per finding key. Keys are line-number-free
(`rule:path:qualname:detail#ordinal`) so refactors that only move code
do not churn the baseline.
"""

from __future__ import annotations

import ast
import json
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Set, Tuple

__all__ = [
    "Finding",
    "RULES",
    "HOT_PATHS",
    "lint_source",
    "lint_paths",
    "default_paths",
    "load_baseline",
    "apply_baseline",
]

RULES: Dict[str, str] = {
    "GR001": "tracer-host-sync",
    "GR002": "jit-in-loop",
    "GR003": "unhashable-static",
    "GR004": "host-entropy-in-trace",
    "GR005": "unordered-pytree-iteration",
    "GR006": "hot-loop-host-sync",
    "GR007": "unregistered-jit-entry",
}

# GR006 scope: the functions whose per-call latency IS the product
# (one scheduler round / one optimizer step). Qualnames per repo-relative
# path; extend when a new hot loop is built.
HOT_PATHS: Dict[str, Set[str]] = {
    "megatron_llm_tpu/inference/engine.py": {
        "DecodeEngine.step",
        "DecodeEngine._step_inner",
        # ISSUE 26: the one emission point of a round (audit trail,
        # latency window, histogram, counters, flight recorder)
        "DecodeEngine._emit_round",
        "DecodeEngine._decode_round",
        "DecodeEngine._mixed_round",
        "DecodeEngine._spec_round",
        # every operand copy up and every fetch back of a round
        "DecodeEngine._upload",
        "DecodeEngine._fetch",
        "DecodeEngine._book_token",
        "DecodeEngine._admit",
        # ISSUE 15 device-cost accounting: per-retire cost record,
        # per-round modeled-vs-measured note, per-round sentinel feed —
        # pricing a round must never cost a transfer (the mint-time
        # registry record exists so it doesn't). Fixtures
        # gr006_cost_{good,bad}.py pin the pattern.
        "DecodeEngine._retire",
        "DecodeEngine._request_cost",
        "DecodeEngine._note_dispatch",
        "DecodeEngine._sentinel_observe",
    },
    "megatron_llm_tpu/training/trainer.py": {
        "Trainer.train_step",
        "Trainer._train_step",
        "Trainer.train",
    },
    # telemetry emit sites (ISSUE 13): called once or more per engine
    # round / train step — per-round span/event/histogram bookkeeping
    # must stay pure host arithmetic, never a device sync. The fixtures
    # gr006_span_{good,bad}.py pin the pattern.
    "megatron_llm_tpu/telemetry/trace.py": {
        "SpanTracer.span",
        "SpanTracer.step_span",
        "SpanTracer._live",
        "SpanTracer.instant",
        "SpanTracer.complete",
        "SpanTracer.set_context",
        "SpanTracer._push",
        "SpanTracer._ts",
        "SpanTracer._tid",
        "_Span.__enter__",
        "_Span.__exit__",
        "_Span.note",
    },
    "megatron_llm_tpu/telemetry/recorder.py": {
        "FlightRecorder.record",
        "FlightRecorder.note_counters",
    },
    "megatron_llm_tpu/telemetry/prometheus.py": {
        "Histogram.observe",
    },
    # ISSUE 15 goodput/cost/sentinel emit sites: per-step ledger adds,
    # per-round registry lookups + roofline math, per-step/round
    # sentinel verdicts — all pure host arithmetic by contract (the
    # mint-time capture is the ONLY place the registry touches jax,
    # and it is not on these paths)
    "megatron_llm_tpu/telemetry/goodput.py": {
        "GoodputLedger.note",
        "GoodputLedger.wall_s",
    },
    "megatron_llm_tpu/telemetry/costs.py": {
        "CostRegistry.record",
        "CostRecord.modeled_seconds",
    },
    "megatron_llm_tpu/telemetry/sentinel.py": {
        "PerfSentinel.observe",
        "RobustWindow.push",
        "RobustWindow.threshold",
    },
}

# Transform entry points whose function arguments run under trace.
_TRACE_WRAPPERS = {
    "jit", "pjit", "vmap", "pmap", "grad", "value_and_grad", "scan",
    "while_loop", "fori_loop", "cond", "switch", "checkpoint", "remat",
    "shard_map",
}

_CONTRACT_MARK = "graft-contract:"


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative
    line: int
    col: int
    qualname: str
    detail: str
    message: str
    ordinal: int = 0

    @property
    def key(self) -> str:
        return (f"{self.rule}:{self.path}:{self.qualname}:"
                f"{self.detail}#{self.ordinal}")

    def to_dict(self) -> dict:
        return {
            "rule": self.rule, "name": RULES[self.rule], "path": self.path,
            "line": self.line, "col": self.col, "qualname": self.qualname,
            "detail": self.detail, "message": self.message, "key": self.key,
        }


def _attr_chain(node: ast.AST) -> Optional[str]:
    """'jax.lax.scan' for Attribute(Name) chains; None otherwise."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_callable(node: ast.AST) -> bool:
    """The node names jax.jit/pjit itself (not a transform like vmap)."""
    chain = _attr_chain(node)
    return chain in {"jit", "pjit", "jax.jit", "jax.pjit"}


def _is_trace_wrapper_call(call: ast.Call) -> bool:
    chain = _attr_chain(call.func)
    if chain is None:
        return False
    leaf = chain.rsplit(".", 1)[-1]
    if leaf not in _TRACE_WRAPPERS:
        return False
    # tree.map-style utilities share no leaf with _TRACE_WRAPPERS, so a
    # leaf match (qualified or bare) is enough for this repo's idiom.
    return True


def _partial_of_jit(call: ast.Call) -> bool:
    """functools.partial(jax.jit, ...) — the decorator idiom."""
    chain = _attr_chain(call.func)
    if chain not in {"partial", "functools.partial"}:
        return False
    return bool(call.args) and _is_jit_callable(call.args[0])


class _ModuleIndex:
    """First pass: which FunctionDef / Lambda NODES are traced.

    A `jax.jit(step)`-style reference marks the def it actually
    resolves to: the def whose enclosing scope (function, lambda, class
    or module) is an ancestor of the referencing call. Scope-aware on
    purpose — `DecodeEngine.step` (a host-side scheduler method) must
    not become "traced" because some builder jits a LOCAL `step`."""

    _SCOPES = (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda,
               ast.ClassDef, ast.Module)

    def __init__(self, tree: ast.Module):
        self.traced_ids: Set[int] = set()
        parent: Dict[int, ast.AST] = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                parent[id(child)] = node

        def scope_of(node: ast.AST) -> ast.AST:
            n = parent.get(id(node))
            while n is not None and not isinstance(n, self._SCOPES):
                n = parent.get(id(n))
            return n if n is not None else tree

        def scope_chain(node: ast.AST) -> List[ast.AST]:
            chain, n = [], scope_of(node)
            while n is not None:
                chain.append(n)
                n = scope_of(n) if not isinstance(n, ast.Module) else None
            return chain

        defs: Dict[str, List[Tuple[ast.AST, ast.AST]]] = {}
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(
                    (node, scope_of(node)))

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if not (_is_trace_wrapper_call(node) or _partial_of_jit(node)):
                continue
            args = node.args[1:] if _partial_of_jit(node) else node.args
            chain = None
            for a in args:
                if isinstance(a, ast.Lambda):
                    self.traced_ids.add(id(a))
                elif isinstance(a, ast.Name):
                    if chain is None:
                        chain = scope_chain(node)
                    chain_ids = {id(s) for s in chain}
                    for d, d_scope in defs.get(a.id, []):
                        if id(d_scope) in chain_ids:
                            self.traced_ids.add(id(d))


def _decorator_traced(fn: ast.AST) -> bool:
    for dec in getattr(fn, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        if _is_jit_callable(target):
            return True
        if isinstance(dec, ast.Call) and (_partial_of_jit(dec)
                                          or _is_trace_wrapper_call(dec)):
            return True
        chain = _attr_chain(target)
        if chain and chain.rsplit(".", 1)[-1] in _TRACE_WRAPPERS:
            return True
    return False


def _contract_decorated(fn: ast.AST) -> bool:
    """`@compile_contract(...)`-decorated builders register their jit
    site with the registry — GR007's whole point is satisfied."""
    for dec in getattr(fn, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        chain = _attr_chain(target)
        if chain and chain.rsplit(".", 1)[-1] == "compile_contract":
            return True
    return False


class _Linter(ast.NodeVisitor):
    def __init__(self, path: str, source: str, *, package_scope: bool):
        self.path = path
        self.lines = source.splitlines()
        self.package_scope = package_scope  # GR007 applies
        self.findings: List[Finding] = []
        self._counts: Dict[Tuple[str, str, str], int] = {}
        self._scope: List[str] = []  # qualname parts
        self._traced_depth = 0
        self._loop_depth = 0
        self._hot = HOT_PATHS.get(path, set())
        self._hot_depth = 0
        self._contract_depth = 0
        self._decorator_calls: Set[int] = set()
        self._index: Optional[_ModuleIndex] = None

    # -- emit --------------------------------------------------------------

    def _qual(self) -> str:
        return ".".join(self._scope) or "<module>"

    def _emit(self, rule: str, node: ast.AST, detail: str, message: str):
        ckey = (rule, self._qual(), detail)
        n = self._counts.get(ckey, 0)
        self._counts[ckey] = n + 1
        self.findings.append(Finding(
            rule=rule, path=self.path, line=getattr(node, "lineno", 0),
            col=getattr(node, "col_offset", 0), qualname=self._qual(),
            detail=detail, message=message, ordinal=n))

    def _marked(self, node: ast.AST) -> bool:
        """A `# graft-contract: <name>` comment on the node's line or one
        of the three lines above registers the jit site for GR007."""
        line = getattr(node, "lineno", 0)
        lo = max(0, line - 4)
        return any(_CONTRACT_MARK in ln
                   for ln in self.lines[lo:line])

    # -- scope tracking ----------------------------------------------------

    def run(self, tree: ast.Module):
        self._index = _ModuleIndex(tree)
        self.visit(tree)
        return self.findings

    def _visit_scope(self, node, name: str, traced: bool, hot: bool,
                     contract: bool = False):
        self._scope.append(name)
        self._traced_depth += 1 if traced else 0
        self._hot_depth += 1 if hot else 0
        self._contract_depth += 1 if contract else 0
        self.generic_visit(node)
        self._contract_depth -= 1 if contract else 0
        self._hot_depth -= 1 if hot else 0
        self._traced_depth -= 1 if traced else 0
        self._scope.pop()

    def visit_FunctionDef(self, node):
        traced = (_decorator_traced(node)
                  or id(node) in self._index.traced_ids)
        qual = ".".join(self._scope + [node.name])
        # GR007 on jit DECORATORS: `@jax.jit` / `@partial(jax.jit, ...)`
        # on a package function is an entry point too
        if self.package_scope and not _contract_decorated(node) \
                and not self._contract_depth:
            for dec in node.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                is_jit = _is_jit_callable(target) or (
                    isinstance(dec, ast.Call) and _partial_of_jit(dec))
                if isinstance(dec, ast.Call) and is_jit:
                    # one finding per decorator site, not a second one
                    # when visit_Call reaches the same node
                    self._decorator_calls.add(id(dec))
                if is_jit and not self._marked(dec) \
                        and not self._marked(node):
                    self._scope.append(node.name)
                    self._emit(
                        "GR007", dec, "bare-jit-decorator",
                        "jitted entry point outside the compile-contract "
                        "registry: register a contract and mark the site "
                        "with `# graft-contract: <name>`, or baseline "
                        "with justification")
                    self._scope.pop()
        self._visit_scope(node, node.name, traced, qual in self._hot,
                          contract=_contract_decorated(node))

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_ClassDef(self, node):
        self._visit_scope(node, node.name, False, False)

    def visit_Lambda(self, node):
        traced = id(node) in self._index.traced_ids
        self._visit_scope(node, "<lambda>", traced, False)

    def visit_For(self, node):
        self._check_iter_order(node.iter)
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    def visit_While(self, node):
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    def visit_comprehension_like(self, node):
        for gen in node.generators:
            self._check_iter_order(gen.iter)
        self._loop_depth += 1
        self.generic_visit(node)
        self._loop_depth -= 1

    visit_ListComp = visit_comprehension_like
    visit_SetComp = visit_comprehension_like
    visit_DictComp = visit_comprehension_like
    visit_GeneratorExp = visit_comprehension_like

    # -- rules -------------------------------------------------------------

    def _check_iter_order(self, it: ast.AST):
        """GR005: iterating a set to build structure inside traced code."""
        if not self._traced_depth:
            return
        is_set = isinstance(it, ast.Set) or (
            isinstance(it, ast.Call) and _attr_chain(it.func) == "set")
        if is_set:
            self._emit(
                "GR005", it, "set-iteration",
                "iteration order of a set is hash-seed dependent inside "
                "traced code: the pytree/executable structure it builds "
                "can differ across processes that must dispatch in "
                "lockstep — sort it or use a tuple/dict")

    def visit_Call(self, node: ast.Call):
        chain = _attr_chain(node.func)
        in_traced = self._traced_depth > 0
        in_hot = self._hot_depth > 0

        # GR002: jit constructed inside a loop/comprehension
        if (_is_jit_callable(node.func) or _partial_of_jit(node)) \
                and self._loop_depth:
            self._emit(
                "GR002", node, "jit-in-loop",
                "jax.jit constructed inside a loop: every iteration "
                "mints a fresh wrapper with an empty call cache, so "
                "every call retraces — hoist the jit (or cache it, "
                "LRU-bounded like api._pp_decode_fn)")

        # GR003: list/set/dict-typed static_argnums|static_argnames
        if _is_jit_callable(node.func) or _partial_of_jit(node):
            for kw in node.keywords:
                if kw.arg in ("static_argnums", "static_argnames") and \
                        isinstance(kw.value,
                                   (ast.List, ast.Set, ast.Dict,
                                    ast.ListComp, ast.SetComp)):
                    self._emit(
                        "GR003", kw.value, kw.arg,
                        f"{kw.arg} given a list/set/dict display: "
                        "unhashable the moment the wrapper is reused as "
                        "a cache key — use a tuple or bare int")

        # GR007: bare jit in package code with no contract marker
        if self.package_scope \
                and (_is_jit_callable(node.func) or _partial_of_jit(node)) \
                and not self._contract_depth \
                and id(node) not in self._decorator_calls \
                and not self._marked(node):
            self._emit(
                "GR007", node, "bare-jit",
                "jax.jit entry point outside the compile-contract "
                "registry: the AOT audit cannot see it. Register a "
                "contract (analysis/contracts.py) and mark the site "
                "with `# graft-contract: <name>`, or baseline with "
                "justification")

        if in_traced:
            # GR001: concretizing calls on traced values
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "item" and not node.args:
                self._emit(
                    "GR001", node, ".item()",
                    ".item() inside traced code concretizes the tracer: "
                    "TracerArrayConversionError under jit, silent host "
                    "sync outside — keep it as a device scalar")
            if isinstance(node.func, ast.Name) \
                    and node.func.id in ("float", "int", "bool") \
                    and node.args \
                    and not isinstance(node.args[0], ast.Constant):
                self._emit(
                    "GR001", node, f"{node.func.id}()",
                    f"{node.func.id}() on a non-constant inside traced "
                    "code concretizes the tracer — use jnp casts "
                    "(astype) to change dtype, or move the conversion "
                    "outside the jitted function")
            if chain in ("np.asarray", "np.array", "numpy.asarray",
                         "numpy.array"):
                self._emit(
                    "GR001", node, chain,
                    "numpy materialization inside traced code forces a "
                    "concrete value (trace-time constant at best, "
                    "TracerArrayConversionError at worst) — use jnp")

            # GR004: host entropy frozen at trace time
            if chain and (chain.startswith("time.")
                          or chain.startswith("random.")
                          or chain.startswith("np.random.")
                          or chain.startswith("numpy.random.")):
                self._emit(
                    "GR004", node, chain,
                    f"{chain} inside traced code runs ONCE at trace "
                    "time and is frozen into the executable — pass "
                    "times/randomness in as arguments (jax.random for "
                    "on-device RNG)")

        if in_hot:
            # GR006: host syncs in the per-round/per-step hot path
            if chain in ("jax.device_get", "np.asarray", "np.array",
                         "numpy.asarray", "numpy.array"):
                self._emit(
                    "GR006", node, chain or "device_get",
                    f"{chain} in a hot loop is a device->host transfer "
                    "per round — batch it, gate it on need, or move it "
                    "off the round path")
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "block_until_ready":
                self._emit(
                    "GR006", node, "block_until_ready",
                    "block_until_ready in a hot loop serializes host "
                    "and device — the dispatch pipeline exists to "
                    "overlap them")
            if isinstance(node.func, ast.Name) \
                    and node.func.id in ("float", "int") \
                    and node.args \
                    and not isinstance(node.args[0], ast.Constant):
                self._emit(
                    "GR006", node, f"{node.func.id}()",
                    f"{node.func.id}() in a hot loop blocks on the "
                    "device value if its arg is a jax array — fetch "
                    "once per round as numpy, then index on host")
        self.generic_visit(node)


def lint_source(source: str, path: str, *, package_scope: bool = False
                ) -> List[Finding]:
    tree = ast.parse(source, filename=path)
    return _Linter(path, source, package_scope=package_scope).run(tree)


def default_paths(root: str) -> List[str]:
    """The lint surface: the package, the task/tool scripts, and the
    top-level entry scripts. Tests and fixtures are excluded — they
    deliberately exercise anti-patterns — and so is the analysis
    package itself: the auditor's one-shot reference jits ARE its
    measurement apparatus, not serving/training entry points."""
    out: List[str] = []
    for sub in ("megatron_llm_tpu", "tasks", "tools"):
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, sub)):
            dirnames[:] = [d for d in dirnames
                           if d not in ("__pycache__", "analysis")]
            for f in sorted(filenames):
                if f.endswith(".py"):
                    out.append(os.path.join(dirpath, f))
    for f in ("verify_correctness.py", "finetune.py",
              "pretrain_bert.py", "pretrain_t5.py", "pretrain_ict.py"):
        p = os.path.join(root, f)
        if os.path.exists(p):
            out.append(p)
    return out


def lint_paths(paths: List[str], root: str) -> List[Finding]:
    findings: List[Finding] = []
    for p in paths:
        rel = os.path.relpath(p, root).replace(os.sep, "/")
        with open(p, "r", encoding="utf-8") as fh:
            src = fh.read()
        findings.extend(lint_source(
            src, rel, package_scope=rel.startswith("megatron_llm_tpu/")))
    return findings


# -- baseline ---------------------------------------------------------------


def load_baseline(path: str) -> Dict[str, str]:
    """key -> justification. Missing file = empty baseline."""
    if not os.path.exists(path):
        return {}
    with open(path, "r", encoding="utf-8") as fh:
        data = json.load(fh)
    entries = data["entries"] if isinstance(data, dict) else data
    out = {}
    for e in entries:
        if not e.get("justification", "").strip():
            raise ValueError(
                f"baseline entry {e.get('key')!r} has no justification — "
                "every accepted finding must say WHY it is accepted")
        out[e["key"]] = e["justification"]
    return out


def apply_baseline(findings: List[Finding], baseline: Dict[str, str]
                   ) -> Tuple[List[Finding], List[Finding], List[str]]:
    """-> (new, accepted, stale-baseline-keys)."""
    seen = set()
    new, accepted = [], []
    for f in findings:
        if f.key in baseline:
            accepted.append(f)
            seen.add(f.key)
        else:
            new.append(f)
    stale = sorted(set(baseline) - seen)
    return new, accepted, stale
