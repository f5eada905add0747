"""compat-shim: the moving jax API surface is shimmed in exactly one place.

``common/jax_compat.py`` owns every jax spelling that has moved between
versions (shard_map's home and its check_vma/check_rep rename,
``lax.axis_size``, ``jax.distributed.initialize``'s kwargs), so a jax
migration lands in one file.  r6 found the last raw ``shard_map`` call site
by hand; this pass makes the rule mechanical: outside the shim module, the
following are findings —

- ``from jax.experimental.shard_map import ...`` / ``import
  jax.experimental.shard_map``
- ``jax.shard_map`` attribute use
- ``jax.distributed.initialize(...)`` call sites (route through
  ``jax_compat.distributed_initialize``)
- ``lax.axis_size`` / ``jax.lax.axis_size`` attribute use (route through
  ``jax_compat.axis_size``)
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from elasticdl_tpu.analysis.core import Finding, LintPass, SourceFile, attr_chain

#: The one module allowed to spell the raw APIs.
SHIM_MODULE_SUFFIX = "common/jax_compat.py"

_FORBIDDEN_ATTR_CHAINS = {
    "jax.shard_map": "use elasticdl_tpu.common.jax_compat.shard_map",
    "jax.distributed.initialize": (
        "use elasticdl_tpu.common.jax_compat.distributed_initialize"
    ),
    "lax.axis_size": "use elasticdl_tpu.common.jax_compat.axis_size",
    "jax.lax.axis_size": "use elasticdl_tpu.common.jax_compat.axis_size",
}


class CompatShimPass(LintPass):
    name = "compat-shim"
    description = (
        "raw shard_map / jax.distributed.initialize / lax.axis_size only "
        "inside common/jax_compat.py"
    )

    def run(self, src: SourceFile) -> Iterable[Finding]:
        if src.path.replace("\\", "/").endswith(SHIM_MODULE_SUFFIX):
            return ()
        findings: List[Finding] = []
        for node in ast.walk(src.tree):
            if isinstance(node, ast.ImportFrom):
                mod = node.module or ""
                if mod.startswith("jax.experimental.shard_map"):
                    findings.append(Finding(
                        self.name, src.path, node.lineno,
                        "raw shard_map import bypasses the version shim — "
                        "use elasticdl_tpu.common.jax_compat.shard_map",
                    ))
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.startswith("jax.experimental.shard_map"):
                        findings.append(Finding(
                            self.name, src.path, node.lineno,
                            "raw shard_map import bypasses the version shim "
                            "— use elasticdl_tpu.common.jax_compat.shard_map",
                        ))
            elif isinstance(node, ast.Attribute):
                chain = attr_chain(node)
                fix = _FORBIDDEN_ATTR_CHAINS.get(chain)
                if fix is not None:
                    findings.append(Finding(
                        self.name, src.path, node.lineno,
                        f"raw {chain} bypasses the version shim — {fix}",
                    ))
        return findings
