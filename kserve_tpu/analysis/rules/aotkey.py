"""Rule ``aot-cache-key-drift``: engine-config reads inside compiled-
program construction that the AOT cache-key digest does not cover.

The persistent AOT executable cache (engine/aot_cache.py,
docs/coldstart.md) keys executables by a digest of
``AOT_KEY_ENGINE_FIELDS`` — the EngineConfig fields that determine the
compiled artifact.  If ``build_compiled`` starts reading a NEW config
field (a new dtype knob, a kernel-selection flag) without that field
joining the digest list, two deployments differing only in that field
silently SHARE executables: the stale-executable hazard, which on a real
fleet surfaces as wrong numerics or shape crashes on warm starts only —
the worst kind of heisenbug.  This rule pins the two in lockstep: every
``<engine-config>.field`` read (attribute or ``getattr``) inside a
compiled-program builder — a function named ``build_compiled`` or
``program_defs`` (the extracted definition table both dispatch modes and
the hlo_oracle build from), or ``DispatchShapes.of`` (engine/shapes.py:
the slice alignment the programs are traced with is derived there) — must
appear in ``AOT_KEY_ENGINE_FIELDS``.

The allowlist is resolved from the linted source itself when it defines
``AOT_KEY_ENGINE_FIELDS`` (test fixtures), else from the sibling
``aot_cache.py`` next to the linted file (the real tree layout).  The
model config and mesh are digested WHOLE by aot_cache_key, so only the
engine-config parameter needs field-level tracking.
"""

from __future__ import annotations

import ast
import os
from typing import Iterator, Optional, Set

from ..core import FileContext, Finding, Rule, register

#: names the engine-config parameter (and its aliases) goes by in
#: compiled-program builders
_CONFIG_PARAM_NAMES = {"engine_config", "cfg"}

#: the functions whose engine-config reads this rule audits.  program_defs
#: is the extracted definition table (engine/compiled.py) — moving reads
#: there must NOT escape the audit.
_BUILDER_NAMES = {"build_compiled", "program_defs"}

#: (class, method) pairs audited the same way: program_defs hands the
#: config whole to DispatchShapes.of, whose reads would otherwise leave
#: the audit's sight
_BUILDER_METHODS = {("DispatchShapes", "of")}

_LIST_NAME = "AOT_KEY_ENGINE_FIELDS"


def _fields_from_tree(tree: ast.Module) -> Optional[Set[str]]:
    """The AOT_KEY_ENGINE_FIELDS literal tuple/list in a module, if any."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == _LIST_NAME
            for t in node.targets
        ):
            continue
        if isinstance(node.value, (ast.Tuple, ast.List)):
            fields = set()
            for elt in node.value.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    fields.add(elt.value)
            return fields
    return None


def _sibling_fields(path: str) -> Optional[Set[str]]:
    """AOT_KEY_ENGINE_FIELDS from aot_cache.py next to the linted file."""
    sibling = os.path.join(os.path.dirname(path), "aot_cache.py")
    try:
        with open(sibling, encoding="utf-8") as f:
            tree = ast.parse(f.read(), filename=sibling)
    except (OSError, SyntaxError):
        return None
    return _fields_from_tree(tree)


def _config_aliases(fn: ast.FunctionDef) -> Set[str]:
    """The engine-config parameter name plus simple `x = cfg` aliases."""
    names = {
        a.arg for a in fn.args.args if a.arg in _CONFIG_PARAM_NAMES
    }
    if not names:
        return names
    for node in ast.walk(fn):
        if (
            isinstance(node, ast.Assign)
            and isinstance(node.value, ast.Name)
            and node.value.id in names
        ):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
    return names


@register
class AOTCacheKeyDrift(Rule):
    id = "aot-cache-key-drift"
    description = (
        "engine-config field read inside build_compiled/program_defs but "
        "missing from AOT_KEY_ENGINE_FIELDS: configs differing in that "
        "field would silently share stale AOT-cached executables"
    )

    def check(self, ctx: FileContext) -> Iterator[Finding]:
        builders = [
            node for node in ast.walk(ctx.tree)
            if isinstance(node, ast.FunctionDef)
            and node.name in _BUILDER_NAMES
        ] + [
            fn for cls in ast.walk(ctx.tree)
            if isinstance(cls, ast.ClassDef)
            for fn in cls.body
            if isinstance(fn, ast.FunctionDef)
            and (cls.name, fn.name) in _BUILDER_METHODS
        ]
        if not builders:
            return
        fields = _fields_from_tree(ctx.tree)
        if fields is None:
            fields = _sibling_fields(ctx.path)
        if fields is None:
            for fn in builders:
                yield self.finding(
                    ctx, fn,
                    f"{fn.name} found but no AOT_KEY_ENGINE_FIELDS "
                    "literal is resolvable (in this file or a sibling "
                    "aot_cache.py): the cache-key digest cannot be "
                    "audited against the fields this builder reads",
                )
            return
        for fn in builders:
            aliases = _config_aliases(fn)
            if not aliases:
                continue
            for node in ast.walk(fn):
                # cfg.field (attribute read, incl. cfg.field(...) calls)
                if (
                    isinstance(node, ast.Attribute)
                    and isinstance(node.value, ast.Name)
                    and node.value.id in aliases
                    and node.attr not in fields
                ):
                    yield self.finding(
                        ctx, node,
                        f"{node.value.id}.{node.attr} read during "
                        "compiled-program construction is not in "
                        "AOT_KEY_ENGINE_FIELDS — configs differing in "
                        f"{node.attr!r} would share stale AOT executables",
                    )
                # getattr(cfg, "field", ...) spelling
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id == "getattr"
                    and len(node.args) >= 2
                    and isinstance(node.args[0], ast.Name)
                    and node.args[0].id in aliases
                    and isinstance(node.args[1], ast.Constant)
                    and isinstance(node.args[1].value, str)
                    and node.args[1].value not in fields
                ):
                    yield self.finding(
                        ctx, node,
                        f"getattr({node.args[0].id}, "
                        f"{node.args[1].value!r}) during compiled-program "
                        "construction is not in AOT_KEY_ENGINE_FIELDS — "
                        "configs differing in that field would share "
                        "stale AOT executables",
                    )
