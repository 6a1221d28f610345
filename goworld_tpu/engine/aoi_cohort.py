"""The cohort bucket tier: many spaces, one device program per tick.

A ``_TPUBucket``'s packed state already carries a leading slot axis
(``[S, C, W]``) and its dispatch already ticks every staged slot in one
fused launch -- so the slot axis IS the space-stacking axis (ROADMAP
#2, ops/aoi_cohort.py).  What the cohort tier adds on top of the plain
bucket is the membership contract:

* spaces of *different* (small) capacities share the bucket: the engine
  rounds each up to the bucket's pow2 ladder shape (ops/aoi_cohort
  ``cohort_shape``) and the padded tail stays inactive, which the
  predicate ignores bit-exactly;
* the bucket is the blast radius of the ``aoi.cohort`` fault seam,
  probed at dispatch BEFORE any staging mutates device or shadow state
  -- any fired kind flags the bucket for demotion and the engine
  rebuilds every member space onto its own solo bucket the same flush,
  re-staging this tick's inputs so the republish is same-tick and
  bit-exact (``AOIEngine._demote_cohort``);
* the triples extraction cap (inherited) is bucket-wide, so a crowded
  member space uses the room its quiet neighbours leave; a tick past
  the cap takes the counted full-grid recovery and grows the cap.

Everything else -- delta staging, fused dispatch, recovery ladder,
export/import/evacuate -- is inherited unchanged from ``_TPUBucket``;
the chip-loss failover hooks the fault-seam-coverage rule demands come
with the inheritance.
"""

from __future__ import annotations

from .. import faults
from .aoi import _TPUBucket, _device_fault


class _CohortTPUBucket(_TPUBucket):
    """Shared ladder-shaped device bucket stacking many small spaces."""

    def __init__(self, capacity: int, **kw):
        super().__init__(capacity, **kw)
        self.cohort = True
        # set by dispatch when the aoi.cohort seam fires; consumed by
        # AOIEngine (flush demotes the bucket before its harvest slot)
        self._cohort_demote = False
        self.stats["cohort_dispatches"] = 0
        self.stats["cohort_demotions"] = 0

    def dispatch(self) -> None:
        """Probe the ``aoi.cohort`` seam, then run the inherited
        dispatch.  The probe comes FIRST -- like ``aoi.device`` in
        ``_dispatch_device`` -- so a firing seam leaves ``_staged`` and
        the host shadows untouched: the engine can re-stage this tick's
        inputs onto the demotion targets and republish same-tick."""
        if not self._cohort_demote:
            try:
                spec = faults.check("aoi.cohort")
            except Exception as e:
                if not (_device_fault(e)
                        or isinstance(e, ConnectionResetError)):
                    raise
                spec = e
            if spec is not None:
                # ANY fired kind demotes (the aoi.ingest/aoi.interest
                # discipline): a cohort whose shared program is suspect
                # must not tick ANY member on it
                self._cohort_demote = True
                self.stats["cohort_demotions"] += 1
        if self._cohort_demote:
            # park nothing: the engine tears this bucket down before
            # harvest; an inflight (pipelined) tick is drained by the
            # per-slot snapshot export during demotion
            return
        if self._staged:
            self.stats["cohort_dispatches"] += 1
        super().dispatch()
