"""Share of the traced window's merge-cache probes (the program's
``plan.lookup`` spans) that found a plan, in memory or in the plan store,
and so skipped partitioning.  A program without the span reads nothing.
Reads ``plan.merge_hit.<cell family>``."""


def read(w):
    hits = [ev["args"]["hit"] for ev in w.rec.spans
            if ev.get("ph") == "X" and ev["name"] == "plan.lookup"]
    if not hits:
        return None
    return 100.0 * sum(h != "miss" for h in hits) / len(hits)
