"""api-compat: JAX symbols absent from the installed version, or on a
deprecation denylist.

Exactly the failure class that took out the seed: call sites written
for one JAX raised AttributeError at query time on another — 33 tier-1
failures from one symbol. The rule resolves every statically-visible
`jax.*` dotted chain (and every `import`/`from ... import` of a jax
module) against the INSTALLED jax via importlib/getattr, so version
skew is caught at lint time, not discovered one bench regression at a
time. The code is written for the one installed JAX: the fix for a
finding is the spelling that JAX resolves, not a version branch.
"""
from __future__ import annotations

import ast
import importlib
import warnings
from typing import Dict, Iterator, Optional

from pinot_tpu.analysis import astutil
from pinot_tpu.analysis.core import Finding, Rule, register

#: dotted path → why it must not be used (fires even when resolvable)
DENYLIST: Dict[str, str] = {
    "jax.tree_map": "removed in JAX 0.6 — use jax.tree.map",
    "jax.tree_multimap": "long removed — use jax.tree.map",
    "jax.tree_util.tree_multimap": "removed — use jax.tree_util.tree_map",
    "jax.experimental.host_callback":
        "removed — use jax.pure_callback / jax.debug.callback",
    "jax.experimental.maps": "xmap is removed — use jax.shard_map",
    "jax.experimental.pjit.pjit": "legacy alias — jax.jit takes shardings",
    "jax.abstract_arrays": "removed module",
    "jax.linear_util": "removed module",
    "jax.config.config": "removed — use jax.config.update",
}

_ROOTS = ("jax",)


class _Resolver:
    """getattr/import_module walk over the installed jax, memoized."""

    def __init__(self):
        self._cache: Dict[str, bool] = {}

    def resolvable(self, dotted: str) -> bool:
        hit = self._cache.get(dotted)
        if hit is not None:
            return hit
        parts = dotted.split(".")
        ok = True
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            try:
                obj = importlib.import_module(parts[0])
                for i, name in enumerate(parts[1:], start=1):
                    try:
                        obj = getattr(obj, name)
                    except AttributeError:
                        # lazily-imported submodule (jax.experimental.*)
                        obj = importlib.import_module(
                            ".".join(parts[: i + 1]))
            except ImportError:
                ok = False
        self._cache[dotted] = ok
        return ok


_RESOLVER = _Resolver()


@register
class ApiCompatRule(Rule):
    id = "api-compat"
    description = ("jax symbols absent from the installed version or on "
                   "the deprecation denylist")

    def check(self, ctx) -> Iterator[Finding]:
        sites = []   # (line, dotted, node)
        for node in ast.walk(ctx.tree):
            for dotted in self._site_dotteds(node, ctx):
                if dotted.split(".")[0] in _ROOTS:
                    sites.append((getattr(node, "lineno", 0), dotted,
                                  node))
        # keep only maximal chains: `jax.foo` riding inside `jax.foo.bar`
        # on the same line is the same usage site, not a second one
        by_line: Dict[int, list] = {}
        for line, dotted, _node in sites:
            by_line.setdefault(line, []).append(dotted)
        seen = set()
        for line, dotted, node in sites:
            if (line, dotted) in seen:
                continue
            seen.add((line, dotted))
            if any(other.startswith(dotted + ".")
                   for other in by_line[line] if other != dotted):
                continue
            deny = self._denied(dotted)
            if deny is not None:
                yield ctx.finding(self.id, node,
                                  f"`{deny}` is denylisted: "
                                  f"{DENYLIST[deny]}")
            elif not _RESOLVER.resolvable(dotted):
                import jax
                yield ctx.finding(
                    self.id, node,
                    f"`{dotted}` does not exist in the installed jax "
                    f"{jax.__version__} — gate it behind "
                    "pinot_tpu.compat")

    @staticmethod
    def _site_dotteds(node: ast.AST, ctx) -> list:
        if isinstance(node, ast.Attribute):
            d = astutil.resolve(node, ctx.aliases)
            return [d] if d else []
        if isinstance(node, ast.Import):
            return [a.name for a in node.names]
        if isinstance(node, ast.ImportFrom) and node.module and \
                not node.level:
            return [f"{node.module}.{a.name}" for a in node.names
                    if a.name != "*"]
        return []

    @staticmethod
    def _denied(dotted: str) -> Optional[str]:
        # a chain is denied if it IS a denylist entry or extends one
        # (jax.experimental.host_callback.call → the module entry)
        probe = dotted
        while probe:
            if probe in DENYLIST:
                return probe
            probe, _, _ = probe.rpartition(".")
        return None
