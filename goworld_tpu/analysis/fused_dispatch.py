"""fused-dispatch: nothing host-syncs inside the fused one-launch step.

The fused pipeline (ops/aoi_fused, docs/perf.md "Fused dispatch") buys
its one-enqueue-per-tick shape by keeping the whole steady tick -- delta
scatter -> neighbor kernel -> diff -> triple extraction / page
allocation -- inside one jitted program plus one async D2H fetch.  A
single host-sync call reachable from the fused attempt (a stray
``np.asarray`` on a device value, an ``.item()`` "just to check", a
``block_until_ready``) silently re-serializes the tick: the program
still runs, parity still holds, and the dispatch is back to paying a
blocking round-trip -- exactly the overhead the fused mode exists to
delete.  Worse than the flush-phase failure mode, it also hides in the
A/B: the fused row keeps winning on dispatch COUNT while losing the
wall-clock it was built to reclaim.

Entry points walked (the shared ProjectIndex call graph -- index.py --
one sync classification shared with host-sync and flush-phase):

* every module function of ops/aoi_fused.py (the fused programs and
  their lazy impl builders);
* every ``*_fused*`` method of the bucket tiers (eligibility check,
  packet build, seam checks, and the enqueue around the program call).

Boundaries are explicit: ``# gwlint: allow[fused-dispatch] -- <why>`` on
the call or callee ``def`` line stops the traversal (demotion recovery
is host-side by design and lives on the unfused path anyway).

Scope: the bucket modules (engine/aoi.py, engine/aoi_mesh.py,
engine/aoi_rowshard.py) and ops/aoi_fused.py.
"""

from __future__ import annotations

import ast

from .core import Context
from .index import walk_no_sync

RULE = "fused-dispatch"

SCOPE = ("engine/aoi.py", "engine/aoi_mesh.py", "engine/aoi_rowshard.py",
         "ops/aoi_fused.py")

_REASON = ("the fused step is one enqueue + one async fetch (docs/perf.md "
           "'Fused dispatch'); a host sync here re-serializes the tick the "
           "fusion exists to overlap")


_HINT = "move it out of the fused step"


def check(ctx: Context):
    index = ctx.index
    for sf in ctx.files_matching(*SCOPE):
        if sf.rel.endswith("ops/aoi_fused.py"):
            # every fused program (module function) is an entry point
            for name, (fn, fsf) in index.mod_funcs.get(sf.rel, {}).items():
                yield from walk_no_sync(index, RULE, _REASON, _HINT,
                                        "", name, fn, fsf)
            continue
        for cls in sf.tree.body:
            if not isinstance(cls, ast.ClassDef):
                continue
            ci = index.classes_by_rel.get(sf.rel, {}).get(cls.name)
            if ci is None:
                continue
            for name, (m, msf) in ci.methods.items():
                if msf is sf and "_fused" in name:
                    yield from walk_no_sync(index, RULE, _REASON, _HINT,
                                            cls.name, name, m, msf)
