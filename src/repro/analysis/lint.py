"""AST-based NUMA-contract linter.

Each contract the repo's correctness/perf story depends on gets exactly one
implementation: a named :class:`Rule` in the registry below. The tier-1
tests invoke the same registry (``tests/test_analysis_lint.py``), so a
contract cannot drift between "what CI greps for" and "what the tests
assert" — the grep scans this package replaced used to live copy-pasted in
three different test files.

Run over the tree::

    PYTHONPATH=src python -m repro.analysis            # advisory rules warn
    PYTHONPATH=src python -m repro.analysis --strict   # advisory rules fail

Adding a rule: write a function taking the list of parsed
:class:`Module` objects and returning :class:`Violation` s, then decorate
it with :func:`rule`. Rules must be pure AST/source checks — no imports of
the scanned code, so the linter runs even when the tree is broken enough
that importing it would crash.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import pathlib
import sys
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = [
    "Module",
    "Rule",
    "RULES",
    "Violation",
    "collect_modules",
    "lint_source",
    "main",
    "repo_root",
    "rule",
    "run_rules",
]

#: Directories (relative to the repo root) the linter scans.
SCAN_DIRS: Tuple[str, ...] = ("src", "benchmarks", "examples", "tests")


@dataclasses.dataclass(frozen=True)
class Violation:
    """One contract breach at a specific source location."""

    rule: str
    path: str  # repo-relative posix path
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclasses.dataclass(frozen=True)
class Module:
    """A parsed source file handed to every rule."""

    path: str  # repo-relative posix path
    source: str
    tree: ast.AST


@dataclasses.dataclass(frozen=True)
class Rule:
    name: str
    description: str
    check: Callable[[Sequence[Module]], List[Violation]]
    #: Advisory rules report but only fail the run under ``--strict``.
    advisory: bool = False


RULES: Dict[str, Rule] = {}


def rule(name: str, description: str, advisory: bool = False):
    """Register ``fn`` as the single implementation of a contract."""

    def deco(fn: Callable[[Sequence[Module]], List[Violation]]):
        if name in RULES:  # pragma: no cover - registry misuse
            raise ValueError(f"duplicate rule {name!r}")
        RULES[name] = Rule(name, description, fn, advisory)
        return fn

    return deco


# --- shared AST helpers -------------------------------------------------------


def _identifiers(node: ast.AST) -> Iterable[Tuple[str, int]]:
    """Yield every (identifier, lineno) referenced in ``node``.

    Covers bare names, attribute accesses, keyword-argument names, and
    function parameters — but *not* string literals or comments, which is
    the point of moving off the text scans: a docstring that mentions a
    forbidden symbol is fine; code that names it is not.
    """
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name):
            yield sub.id, sub.lineno
        elif isinstance(sub, ast.Attribute):
            yield sub.attr, sub.lineno
        elif isinstance(sub, ast.keyword) and sub.arg is not None:
            yield sub.arg, sub.value.lineno
        elif isinstance(sub, ast.arg):
            yield sub.arg, sub.lineno
        elif isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield sub.name, sub.lineno
        elif isinstance(sub, ast.ImportFrom):
            for alias in sub.names:
                yield alias.name, sub.lineno
        elif isinstance(sub, ast.Import):
            for alias in sub.names:
                yield alias.name.split(".")[0], sub.lineno


def _call_name(call: ast.Call) -> Optional[str]:
    """The trailing identifier of a call target (``a.b.f(...)`` -> ``f``)."""
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


def _in_dir(mod: Module, rel_dir: str) -> bool:
    return mod.path.startswith(rel_dir.rstrip("/") + "/")


# --- rules --------------------------------------------------------------------


_VERSIONED_JAX = ("CompilerParams", "TPUCompilerParams", "AxisType")


@rule(
    "compat-only-versioned-jax",
    "version-dependent JAX symbols (CompilerParams / TPUCompilerParams / "
    "AxisType) may only be named by src/repro/compat.py, so the next JAX "
    "bump stays a one-file change",
)
def check_versioned_jax(modules: Sequence[Module]) -> List[Violation]:
    out: List[Violation] = []
    for mod in modules:
        if mod.path == "src/repro/compat.py":
            continue
        for ident, line in _identifiers(mod.tree):
            if ident in _VERSIONED_JAX:
                out.append(Violation(
                    "compat-only-versioned-jax", mod.path, line,
                    f"{ident} referenced outside compat.py — route through "
                    "repro.compat (tpu_compiler_params / make_mesh)",
                ))
    return out


#: Per-file identifier bans at the former dispatch sites. These files
#: consume AttentionPlans; none of them may thread ``q_offset`` /
#: ``mapping_name`` by hand, look up ``PAPER_MAPPINGS``, or hand-roll a
#: ``MappingConfig`` past the plan layer. kernels/ops.py dispatches plans
#: but the scoring bodies must live in plan.py.
_PLAN_SITE_BANS: Dict[str, Tuple[str, ...]] = {
    "src/repro/models/attention.py": (
        "q_offset", "mapping_name", "PAPER_MAPPINGS", "resolve_mapping",
        "MappingConfig",
    ),
    "src/repro/models/transformer.py": (
        "q_offset", "mapping_name", "PAPER_MAPPINGS", "resolve_mapping",
        "MappingConfig",
    ),
    "src/repro/serving/engine.py": (
        "q_offset", "mapping_name", "PAPER_MAPPINGS", "resolve_mapping",
        "MappingConfig",
    ),
    "src/repro/serving/backends.py": (
        "q_offset", "mapping_name", "PAPER_MAPPINGS", "resolve_mapping",
        "MappingConfig",
    ),
    "src/repro/serving/scheduler.py": (
        "q_offset", "mapping_name", "PAPER_MAPPINGS", "resolve_mapping",
        "MappingConfig",
    ),
    "src/repro/kernels/ops.py": (
        "_resolve_mapping_cached", "_resolve_kv_layout_cached",
        "PAPER_MAPPINGS", "use_interpret",
    ),
}


@rule(
    "plan-dispatch-only",
    "dispatch sites consume AttentionPlans only: no out-of-band "
    "mapping_name/q_offset threading or PAPER_MAPPINGS lookups past the "
    "plan layer",
)
def check_plan_dispatch(modules: Sequence[Module]) -> List[Violation]:
    out: List[Violation] = []
    for mod in modules:
        banned = _PLAN_SITE_BANS.get(mod.path)
        if not banned:
            continue
        for ident, line in _identifiers(mod.tree):
            if ident in banned:
                out.append(Violation(
                    "plan-dispatch-only", mod.path, line,
                    f"{ident} at a plan-dispatch site — schedule policy "
                    "belongs in kernels/plan.py; thread an AttentionPlan "
                    "instead",
                ))
    return out


_LEGACY_ENGINES = ("ServingEngine", "PagedServingEngine")
_LEGACY_ALLOWED = ("src/repro/serving/", "tests/test_serving.py")


@rule(
    "no-legacy-engine-construction",
    "the deprecated ServingEngine/PagedServingEngine shims may only be "
    "constructed inside src/repro/serving/ (and the shim tests); everything "
    "else goes through LLMEngine",
)
def check_legacy_engines(modules: Sequence[Module]) -> List[Violation]:
    out: List[Violation] = []
    for mod in modules:
        if any(mod.path == a or mod.path.startswith(a)
               for a in _LEGACY_ALLOWED):
            continue
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) and \
                    _call_name(node) in _LEGACY_ENGINES:
                out.append(Violation(
                    "no-legacy-engine-construction", mod.path, node.lineno,
                    f"{_call_name(node)}(...) constructed outside serving/ "
                    "— use repro.serving.LLMEngine",
                ))
    return out


_DECODE_KERNELS = (
    "src/repro/kernels/decode_attention.py",
    "src/repro/kernels/paged_decode_attention.py",
)


@rule(
    "decode-relevance-shared",
    "the dense and paged decode kernels (one-pass and split-K paths alike) "
    "must gate units through decode_common.chunk_relevant and merge partials "
    "with decode_common.combine_split_states, not re-derive either locally",
)
def check_decode_relevance(modules: Sequence[Module]) -> List[Violation]:
    out: List[Violation] = []
    for mod in modules:
        if mod.path not in _DECODE_KERNELS:
            continue
        counts = {"chunk_relevant": 0, "combine_split_states": 0}
        for ident, _line in _identifiers(mod.tree):
            if ident in counts:
                counts[ident] += 1
        # One reference per kernel body: two where the one-pass and split
        # paths have bodies of their own, one where they share a walk.
        bodies = max(1, sum(
            1 for node in mod.tree.body
            if isinstance(node, ast.FunctionDef)
            and node.name.endswith("_kernel")
        ))
        if counts["chunk_relevant"] < bodies:
            out.append(Violation(
                "decode-relevance-shared", mod.path, 1,
                "the one-pass and split kernel bodies must gate units via "
                f"decode_common.chunk_relevant (fewer than {bodies} "
                "references)",
            ))
        if counts["combine_split_states"] < 1:
            out.append(Violation(
                "decode-relevance-shared", mod.path, 1,
                "split partials must merge via "
                "decode_common.combine_split_states",
            ))
        # Local re-derivation of the window edge (`length - window`): any
        # subtraction whose operands name `window` is relevance arithmetic
        # that belongs in decode_common.
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
                names = {i for i, _ in _identifiers(node)}
                if "window" in names:
                    out.append(Violation(
                        "decode-relevance-shared", mod.path, node.lineno,
                        "window-edge arithmetic re-derived locally — "
                        "relevance math lives in decode_common",
                    ))
    return out


@rule(
    "pallas-call-via-compat",
    "every pallas_call lives under src/repro/kernels/ and passes "
    "compiler_params=compat.tpu_compiler_params(...) so Mosaic scheduling "
    "hints survive JAX version bumps",
)
def check_pallas_call_compat(modules: Sequence[Module]) -> List[Violation]:
    out: List[Violation] = []
    for mod in modules:
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call) and
                    _call_name(node) == "pallas_call"):
                continue
            if not _in_dir(mod, "src/repro/kernels"):
                out.append(Violation(
                    "pallas-call-via-compat", mod.path, node.lineno,
                    "pallas_call outside src/repro/kernels/ — kernels are "
                    "the only layer that may talk to Pallas directly",
                ))
                continue
            cp = next((kw.value for kw in node.keywords
                       if kw.arg == "compiler_params"), None)
            ok = (isinstance(cp, ast.Call) and
                  _call_name(cp) == "tpu_compiler_params")
            if not ok:
                out.append(Violation(
                    "pallas-call-via-compat", mod.path, node.lineno,
                    "pallas_call without compiler_params="
                    "compat.tpu_compiler_params(...) — dimension semantics "
                    "must flow through the compat shim",
                ))
    return out


#: Decode-hot-loop functions in serving/: one step() must stay free of
#: host round-trips. ``LLMEngine._sync_scan`` is deliberately *not*
#: listed — with the fused ``lax.scan`` decode (ROADMAP item 3) it is the
#: sanctioned sync point, entered once per ``steps_per_sync`` tokens.
_HOT_LOOP_FNS = ("decode", "prepare_row", "_decode_tick", "fused_decode")
_HOST_SYNC_ATTRS = ("item", "block_until_ready")
_NUMPY_ALIASES = ("np", "numpy")


@rule(
    "no-host-sync-in-decode-hot-loop",
    "no .item() / np.asarray / block_until_ready inside serving/ decode "
    "hot-loop functions (decode, prepare_row, _decode_tick, fused_decode) "
    "— host syncs there serialize the NUMA-local pipeline; the only "
    "sanctioned sync point is LLMEngine._sync_scan, once per fused scan",
)
def check_host_sync(modules: Sequence[Module]) -> List[Violation]:
    out: List[Violation] = []
    for mod in modules:
        if not _in_dir(mod, "src/repro/serving"):
            continue
        for node in ast.walk(mod.tree):
            if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name in _HOT_LOOP_FNS):
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Call):
                    continue
                fn = sub.func
                if isinstance(fn, ast.Attribute) and \
                        fn.attr in _HOST_SYNC_ATTRS:
                    out.append(Violation(
                        "no-host-sync-in-decode-hot-loop", mod.path,
                        sub.lineno,
                        f".{fn.attr}() inside {node.name}() — host sync in "
                        "the decode hot loop",
                    ))
                elif (isinstance(fn, ast.Attribute) and
                      fn.attr == "asarray" and
                      isinstance(fn.value, ast.Name) and
                      fn.value.id in _NUMPY_ALIASES):
                    out.append(Violation(
                        "no-host-sync-in-decode-hot-loop", mod.path,
                        sub.lineno,
                        f"{fn.value.id}.asarray inside {node.name}() — "
                        "device->host copy in the decode hot loop",
                    ))
    return out


#: Serving functions on the per-tick path (PR 7): telemetry there may
#: only *use* pre-bound instruments, never register/look them up.
#: ``__init__`` is where binding happens; these are where it must not.
_OBS_HOT_FNS = ("step", "_decode_tick", "_sync_scan", "_flush",
                "_emit_lifecycle", "decode", "prepare_row",
                "fused_decode")
_OBS_REGISTRATION_CALLS = ("counter", "gauge", "histogram", "labels")


@rule(
    "obs-no-hot-loop-allocs",
    "serving per-tick functions (step/_decode_tick/_advance/_flush/"
    "_emit_lifecycle/decode/prepare_row) may not register or look up "
    "metric instruments (.counter/.gauge/.histogram/.labels) — bind them "
    "once at construction and call .inc()/.observe()/.set() on the bound "
    "object",
)
def check_obs_hot_loop_allocs(modules: Sequence[Module]) -> List[Violation]:
    out: List[Violation] = []
    for mod in modules:
        if not _in_dir(mod, "src/repro/serving"):
            continue
        for node in ast.walk(mod.tree):
            if not (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and node.name in _OBS_HOT_FNS):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Call) and \
                        isinstance(sub.func, ast.Attribute) and \
                        sub.func.attr in _OBS_REGISTRATION_CALLS:
                    out.append(Violation(
                        "obs-no-hot-loop-allocs", mod.path, sub.lineno,
                        f".{sub.func.attr}(...) inside {node.name}() — "
                        "instrument registration/lookup in the decode hot "
                        "loop; pre-bind at construction",
                    ))
    return out


#: Cross-device collective primitives. Under the mesh-sharded serving
#: path (PR 9) the only sanctioned cross-device traffic is the split-K
#: combine and the sampler's logits reduction — everywhere else the
#: sharded decode must stay device-pure (GSPMD inserts what the
#: NamedShardings require; hand-written collectives in kernel bodies,
#: the scheduler, or the page pool would add fabric crossings the perf
#: model does not price).
_COLLECTIVE_IDENTS = ("psum", "psum_scatter", "all_gather", "ppermute",
                      "all_to_all", "pmean")
_COLLECTIVE_SCOPES = ("src/repro/kernels", "src/repro/serving",
                      "src/repro/cache")
_COLLECTIVE_ALLOWED = ("src/repro/kernels/decode_common.py",
                       "src/repro/kernels/ops.py",
                       "src/repro/serving/sampling.py")


@rule(
    "collectives-only-in-combine",
    "cross-device collectives (psum/all_gather/ppermute/...) may only "
    "appear in the sanctioned combine, dispatch and sampling modules "
    "(kernels/decode_common.py, kernels/ops.py's head-parallel gather, "
    "serving/sampling.py) — never in kernel "
    "bodies, the scheduler, or the page pool, which must stay "
    "device-pure under the head-sharded mesh",
)
def check_collectives(modules: Sequence[Module]) -> List[Violation]:
    out: List[Violation] = []
    for mod in modules:
        if mod.path in _COLLECTIVE_ALLOWED:
            continue
        if not any(_in_dir(mod, d) for d in _COLLECTIVE_SCOPES):
            continue
        for ident, line in _identifiers(mod.tree):
            if ident in _COLLECTIVE_IDENTS:
                out.append(Violation(
                    "collectives-only-in-combine", mod.path, line,
                    f"{ident} outside the sanctioned combine/sampling "
                    "modules — cross-device traffic belongs in "
                    "decode_common's split combine or the sampler's "
                    "logits reduction",
                ))
    return out


#: Quantized-pool scale metadata (PR 10): ``k_scales`` / ``v_scales``
#: ride the page table — scalar-prefetch SMEM metadata indexed by page id
#: inside kernel bodies (src/repro/kernels/) and packed/scattered by the
#: quantization library (src/repro/cache/). Everywhere else they are
#: opaque cache-dict entries: serving code that *indexes* a bare
#: ``k_scales`` array or does arithmetic on one is re-deriving
#: dequantization outside the kernel, which silently diverges from what
#: the SMEM path actually computes.
_SCALE_NAMES = ("k_scales", "v_scales")
_SCALE_ALLOWED_DIRS = ("src/repro/kernels", "src/repro/cache")


@rule(
    "kv-scales-ride-page-table",
    "bare k_scales/v_scales arrays may only be indexed or used in "
    "arithmetic inside src/repro/kernels/ and src/repro/cache/ — "
    "everywhere else scale metadata is an opaque page-table payload "
    "(dict entries pass through; dequant math lives with the kernels)",
)
def check_kv_scales_opaque(modules: Sequence[Module]) -> List[Violation]:
    out: List[Violation] = []
    for mod in modules:
        if any(_in_dir(mod, d) for d in _SCALE_ALLOWED_DIRS):
            continue

        def bad(node: ast.AST) -> Optional[str]:
            if isinstance(node, ast.Name) and node.id in _SCALE_NAMES:
                return node.id
            return None

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Subscript):
                name = bad(node.value)
                if name:
                    out.append(Violation(
                        "kv-scales-ride-page-table", mod.path, node.lineno,
                        f"{name}[...] outside kernels/cache — scale "
                        "metadata is opaque page-table payload here",
                    ))
            elif isinstance(node, ast.BinOp):
                name = bad(node.left) or bad(node.right)
                if name:
                    out.append(Violation(
                        "kv-scales-ride-page-table", mod.path, node.lineno,
                        f"arithmetic on {name} outside kernels/cache — "
                        "dequantization lives with the kernel SMEM path",
                    ))
    return out


# --- driver -------------------------------------------------------------------


def repo_root() -> pathlib.Path:
    """The repo checkout that owns the installed ``repro`` package."""
    import repro

    # src/repro/__init__.py -> src/repro -> src -> repo root
    return pathlib.Path(repro.__file__).resolve().parents[2]


def collect_modules(root: pathlib.Path) -> List[Module]:
    mods: List[Module] = []
    for sub in SCAN_DIRS:
        base = root / sub
        if not base.is_dir():
            continue
        for path in sorted(base.rglob("*.py")):
            rel = path.relative_to(root).as_posix()
            source = path.read_text()
            try:
                tree = ast.parse(source, filename=rel)
            except SyntaxError as e:  # pragma: no cover - broken tree
                raise SystemExit(f"{rel}: syntax error while linting: {e}")
            mods.append(Module(rel, source, tree))
    return mods


def run_rules(
    root: Optional[pathlib.Path] = None,
    rules: Optional[Sequence[str]] = None,
) -> List[Violation]:
    """Lint the tree at ``root`` (default: the live repo) and return
    every violation from the selected rules."""
    modules = collect_modules(root or repo_root())
    return _apply(modules, rules)


def lint_source(
    source: str,
    virtual_path: str,
    rules: Optional[Sequence[str]] = None,
) -> List[Violation]:
    """Lint a source snippet as if it lived at ``virtual_path``.

    Used by the rule-coverage tests to prove each rule still fires on a
    known-bad fixture without planting bad files in the tree.
    """
    tree = ast.parse(source, filename=virtual_path)
    return _apply([Module(virtual_path, source, tree)], rules)


def _apply(
    modules: Sequence[Module],
    rules: Optional[Sequence[str]],
) -> List[Violation]:
    selected = list(RULES) if rules is None else list(rules)
    unknown = [r for r in selected if r not in RULES]
    if unknown:
        raise KeyError(f"unknown rule(s): {unknown}; have {sorted(RULES)}")
    out: List[Violation] = []
    for name in selected:
        out.extend(RULES[name].check(modules))
    return sorted(out, key=lambda v: (v.path, v.line, v.rule))


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="NUMA-contract linter (AST rule registry)",
    )
    parser.add_argument("--strict", action="store_true",
                        help="advisory rules fail the run too")
    parser.add_argument("--root", type=pathlib.Path, default=None,
                        help="repo root to scan (default: the live repo)")
    parser.add_argument("--rule", action="append", default=None,
                        metavar="NAME", help="run only this rule "
                        "(repeatable); default: all")
    parser.add_argument("--list", action="store_true",
                        help="list registered rules and exit")
    args = parser.parse_args(argv)

    if args.list:
        for r in RULES.values():
            tag = " (advisory)" if r.advisory else ""
            print(f"{r.name}{tag}\n    {r.description}")
        return 0

    violations = run_rules(args.root, args.rule)
    fatal = 0
    for v in violations:
        advisory = RULES[v.rule].advisory and not args.strict
        stream = sys.stdout if advisory else sys.stderr
        prefix = "warning" if advisory else "error"
        print(f"{prefix}: {v}", file=stream)
        fatal += 0 if advisory else 1
    checked = len(RULES) if args.rule is None else len(args.rule)
    if not violations:
        print(f"repro.analysis: {checked} rule(s) clean")
    return 1 if fatal else 0


if __name__ == "__main__":  # pragma: no cover
    raise SystemExit(main())
