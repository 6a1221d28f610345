"""host-sync: no hidden device->host synchronization on per-tick paths.

The round-5 perf win came from hunting exactly these: a stray
``np.asarray`` / ``.item()`` / ``block_until_ready`` inside the per-tick
device path stalls the dispatch pipeline for a full D2H round-trip
(PCIe + a sync per fetch).  Intentional drain points -- the ONE place per tick where
results are harvested -- are annotated ``# gwlint: allow[host-sync]`` on
the ``def`` line; host-side oracle modules are grandfathered in
``gwlint.suppressions``.

Scope: the per-tick device modules only (engine/aoi*.py, ops/).
"""

from __future__ import annotations

import ast

from .core import Context, Finding, call_name

RULE = "host-sync"

SCOPE = ("engine/aoi.py", "engine/aoi_mesh.py", "engine/aoi_rowshard.py",
         "ops/")

# attribute calls that force a device sync
_SYNC_ATTRS = {"block_until_ready", "item"}
# dotted call prefixes that force a sync / D2H copy
_SYNC_CALLS = {
    "jax.device_get": "jax.device_get forces a D2H copy",
    "jax.block_until_ready": "jax.block_until_ready stalls dispatch",
    "np.asarray": "np.asarray on a device value is a blocking D2H fetch",
    "numpy.asarray": "numpy.asarray on a device value is a blocking D2H fetch",
}


def check(ctx: Context):
    for sf in ctx.files_matching(*SCOPE):
        for node in sf.nodes:
            if not isinstance(node, ast.Call):
                continue
            name = call_name(node)
            msg = None
            if name in _SYNC_CALLS:
                msg = _SYNC_CALLS[name]
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _SYNC_ATTRS:
                verb = ("forces a device sync"
                        if node.func.attr == "block_until_ready"
                        else "is a scalar D2H fetch")
                msg = f".{node.func.attr}() {verb}"
            elif name in ("float", "int") and len(node.args) == 1 \
                    and not node.keywords \
                    and not isinstance(node.args[0], ast.Constant):
                msg = (f"{name}() on a possibly-device value is a scalar "
                       "D2H fetch")
            if msg is None:
                continue
            yield Finding(
                RULE, sf.rel, node.lineno, node.col_offset,
                msg + " inside a per-tick module; move it off the hot path "
                      "or mark the drain point with "
                      "'# gwlint: allow[host-sync] -- <why>'")
