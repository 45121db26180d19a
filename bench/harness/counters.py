"""What a window did, from the service's own counters."""
from __future__ import annotations

import sys


def counters(cache) -> dict:
    """The service's own counters that say what a window did."""
    s = cache.stats_snapshot()
    return {"rows": s.traffic["lookup_rows"],
            "hits": s.traffic["hot_hits"] + s.traffic["warm_hits"],
            "admitted": s.admission["admitted"],
            "demoted": s.tiers["demotions"],
            "rebuilds": s.rebuild["rebuilds"]}


def report(before: dict, after: dict, setup: dict) -> None:
    """One line on standard error: the set-up phases' seconds and the
    window's rows, hit share, admissions, demotions and rebuilds."""
    d = {k: after[k] - before[k] for k in after}
    share = d["hits"] / d["rows"] if d["rows"] else 0.0
    print("bench: set-up " + ", ".join(f"{k} {v:.1f} s" for k, v in
                                       setup.items())
          + f"; window {d['rows']} rows, hit share {share:.3f}, "
          f"{d['admitted']} admitted, {d['demoted']} demoted, "
          f"{d['rebuilds']} rebuilds", file=sys.stderr)
