"""PTL001 — moving-api routing.

Version-moving jax APIs must route through
``paddle_tpu/framework/jax_compat.py`` (standing ROADMAP constraint:
one installed jax is supported, and the next upgrade is repaired in
that one file).  The old ``tools/shard_map_guard.sh`` grep enforced three
surface spellings and missed every aliased import; this rule resolves
imports, aliases and attribute chains, so ``from jax.experimental
import shard_map as sm`` and ``import jax; jax.sharding.NamedSharding``
are both caught.

Flagged once at the binding import (uses through a flagged binding are
not re-reported) plus at every un-imported attribute-chain use.
"""
from __future__ import annotations

import ast

from .core import Finding, Rule, register
from .resolve import matches

# origin -> the jax_compat routing that replaces it
MOVING_API = {
    "jax.experimental.shard_map": "shard_map",
    "jax.shard_map": "shard_map",
    "jax.sharding.Mesh": "make_mesh",
    "jax.sharding.NamedSharding": "named_sharding",
    "jax.sharding.PartitionSpec": "partition_spec / partition_spec_class",
    "jax.lax.psum_scatter": "psum_scatter",
    "jax.lax.axis_size": "axis_size",
    "jax.lax.pcast": "pcast_varying",
    "jax.lax.with_sharding_constraint": "with_sharding_constraint",
    "jax.experimental.pjit.with_sharding_constraint":
        "with_sharding_constraint",
    "jax.numpy.float8_e4m3fn": "fp8_dtype",
    "jax.experimental.pallas.tpu.CompilerParams": "tpu_compiler_params",
    "jax.experimental.pallas.tpu.TPUCompilerParams": "tpu_compiler_params",
    # AOT export / compiled-executable serialization (ISSUE 14): jax
    # has re-homed export (experimental -> top-level) and the
    # serialize_executable surface is experimental — route through
    # jax_compat so the next move is a one-line fix
    "jax.export": "jax_export_module",
    "jax.experimental.export": "jax_export_module",
    "jax.experimental.serialize_executable":
        "aot_serialize_compiled / aot_deserialize_compiled",
}

# the one module allowed to pin the moving spellings
ALLOWED_PATH_SUFFIXES = ("framework/jax_compat.py",)


def _allowed(relpath):
    return any(relpath.endswith(s) for s in ALLOWED_PATH_SUFFIXES)


@register
class MovingApiRule(Rule):
    id = "PTL001"
    name = "moving-api"
    describe = ("direct version-moving jax API outside "
                "framework/jax_compat.py (alias-aware)")

    def visit_module(self, mod, add):
        if _allowed(mod.relpath):
            return
        targets = tuple(MOVING_API)
        seen = set()       # nested Attribute chains share a col: dedupe

        def report(node, origin, hit):
            key = (node.lineno, node.col_offset, hit)
            if key in seen:
                return
            seen.add(key)
            add(Finding(
                self.id, mod.relpath, node.lineno, node.col_offset,
                f"direct {origin} — route through framework/"
                f"jax_compat.py::{MOVING_API[hit]}",
                symbol=hit, scope=mod.scope_at(node.lineno)))

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    hit = matches(a.name, targets)
                    if hit:
                        report(node, a.name, hit)
            elif isinstance(node, ast.ImportFrom):
                base = ("." * node.level) + (node.module or "")
                for a in node.names:
                    origin = (f"{base}.{a.name}" if a.name != "*"
                              else base)
                    hit = matches(origin, targets)
                    if hit:
                        report(node, origin, hit)
            elif isinstance(node, ast.Attribute):
                origin = mod.imports.qualify(node)
                hit = matches(origin, targets)
                if not hit:
                    continue
                # skip chains rooted in a binding that is ITSELF the
                # moving name — its import line already reported
                root = mod.imports.root_origin(node)
                if matches(root, targets):
                    continue
                # only the full chain reports, not its sub-attributes
                report(node, origin, hit)
