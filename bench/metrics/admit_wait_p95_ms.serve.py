"""95th percentile of the wait from a request's due time to its admission
into a scheduler slot (the scheduler's ``admit`` trace event)."""

import stats


def read(ctx):
    slot_at = {ev["rid"]: ev["mono"] for ev in ctx["admits"] if "slot" in ev}
    rid_of = {ev["fid"]: ev["rid"] for ev in ctx["admits"]
              if "fid" in ev and "rid" in ev}
    end = ctx["end"]
    waits = []
    for r in ctx["recs"]:
        if r.due >= end or r.fid is None:
            continue
        at = slot_at.get(rid_of.get(r.fid))
        waits.append((min(at, end) if at is not None else end) - r.due)
    return 1e3 * stats.percentile(waits, 95) if waits else None
