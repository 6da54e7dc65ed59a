"""Round-program-discipline rules: engines declare stages, the builder
owns the round machinery (ISSUE 11).

The declarative round-program builder (engines/program.py) exists so
cohort sharding, the folded client loop, donation, defenses, and codec
knobs are written ONCE. Two lexical rules keep it that way:

- ``round-program-fused-body`` — no engine module may hand-roll a
  multi-round scan: a ``lax.scan`` call lexically inside a
  ``*round*``/``*fused*``-named method of a ``FederatedEngine`` subclass
  (outside engines/program.py itself) is the copy-the-machinery-back
  regression this rule exists to stop. Round programs come from
  engines/program.py, one round a dispatch; engines declare
  :class:`RoundStages`.
- ``round-program-reason`` — fallback reasons come from the single
  source of truth: a ``*_fallback_key`` override must return ``None`` or
  a string literal that is a key of ``engines/program.py``'s ``REASONS``
  table (parsed from source, dependency-free). Ad-hoc reason strings
  resurrect the grep-only fallback reporting the structured
  ``nidt_fallback_total`` counter replaced.
"""

from __future__ import annotations

import ast
import functools
import os
from typing import Iterator

from neuroimagedisttraining_tpu.analysis.core import (
    Finding,
    ModuleInfo,
    Rule,
    dotted_name,
    normalize,
    register,
)
from neuroimagedisttraining_tpu.analysis.engine_contract import (
    ROOT_CLASS,
    _classes_of,
    _parse_file,
    _sibling_classes,
    EngineContractRule,
)

_PACKAGED_PROGRAM = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "engines", "program.py")

#: path suffixes allowed to contain round-body scans / reason
#: literals — suffix-matched, not basename-matched, so a future
#: pkg/<other>/program.py with a hand-rolled round body is NOT exempt
_BUILDER_FILES = ("engines/program.py",)


def _is_builder_file(path: str) -> bool:
    norm = path.replace(os.sep, "/")
    return any(norm == b or norm.endswith("/" + b)
               for b in _BUILDER_FILES)

_SCAN_CALLS = ("jax.lax.scan", "lax.scan")
_KEY_METHODS = ("cohort_fallback_key", "fold_refusal_key")


@functools.lru_cache(maxsize=None)
def _reason_keys(path: str = _PACKAGED_PROGRAM) -> frozenset[str]:
    """The REASONS table's keys, parsed from engines/program.py source
    (the linter stays dependency-free — no runtime import of jax; the
    result is constant per process, so one parse serves every linted
    module)."""
    try:
        with open(path, encoding="utf-8") as fh:
            tree = ast.parse(fh.read(), filename=path)
    except (OSError, SyntaxError):
        return frozenset()
    for node in ast.walk(tree):
        if isinstance(node, ast.AnnAssign) and isinstance(
                node.target, ast.Name) and node.target.id == "REASONS" \
                and isinstance(node.value, ast.Dict):
            return frozenset(
                k.value for k in node.value.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str))
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "REASONS"
                for t in node.targets) and isinstance(node.value, ast.Dict):
            return frozenset(
                k.value for k in node.value.keys
                if isinstance(k, ast.Constant) and isinstance(k.value, str))
    return frozenset()


def _scan_calls_in(fn: ast.AST, aliases: dict) -> Iterator[ast.Call]:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = normalize(dotted_name(node.func), aliases)
            if name in _SCAN_CALLS:
                yield node


@register
class RoundProgramRule(Rule):
    rule_ids = ("round-program-fused-body", "round-program-reason")
    description = ("engines declare round stages through the builder "
                   "(engines/program.py): no hand-rolled lax.scan "
                   "round bodies in engine classes, and *_fallback_key "
                   "overrides return keys from the REASONS table")

    def check(self, mod: ModuleInfo) -> Iterator[Finding]:
        if _is_builder_file(mod.path):
            return
        table = _sibling_classes(mod.path)
        table.update(_classes_of(mod.tree))
        if ROOT_CLASS not in table:
            from neuroimagedisttraining_tpu.analysis.engine_contract import (
                _PACKAGED_BASE,
            )
            table.update(_parse_file(_PACKAGED_BASE))
        engine_classes = set()
        for node in mod.tree.body:
            if not isinstance(node, ast.ClassDef):
                continue
            info = _classes_of(mod.tree).get(node.name)
            if info is None:
                continue
            chain = EngineContractRule._engine_ancestry(info, table)
            if chain is not None or node.name == ROOT_CLASS:
                engine_classes.add(node.name)
        if not engine_classes:
            return
        keys = _reason_keys()
        for node in mod.tree.body:
            if not isinstance(node, ast.ClassDef) \
                    or node.name not in engine_classes:
                continue
            for stmt in node.body:
                if not isinstance(stmt, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                yield from self._check_method(mod, node, stmt, keys)

    def _check_method(self, mod: ModuleInfo, cls: ast.ClassDef,
                      fn: ast.FunctionDef, keys: frozenset[str]
                      ) -> Iterator[Finding]:
        name = fn.name.lower()
        if "round" in name or "fused" in name:
            for call in _scan_calls_in(fn, mod.aliases):
                yield Finding(
                    mod.path, call.lineno, "round-program-fused-body",
                    f"{cls.name}.{fn.name} hand-rolls a lax.scan "
                    "round body; engines declare RoundStages and round "
                    "programs come from the builder "
                    "(engines/program.py), one round a dispatch — "
                    "hand-rolled copies drift from the "
                    "donation/sharding/fold contracts")
        if fn.name in _KEY_METHODS and keys:
            for node in ast.walk(fn):
                if isinstance(node, ast.Return) \
                        and isinstance(node.value, ast.Constant) \
                        and isinstance(node.value.value, str) \
                        and node.value.value not in keys:
                    yield Finding(
                        mod.path, node.lineno, "round-program-reason",
                        f"{cls.name}.{fn.name} returns "
                        f"{node.value.value!r}, which is not a key of "
                        "engines/program.py REASONS — fallback reasons "
                        "have ONE source of truth (the structured "
                        "nidt_fallback_total counter labels by key)")
