"""hit_recall: the share of the exact brute-force hits that the cascade
served, on probes planned after the window."""
from __future__ import annotations

import numpy as np


def recall(cache, embs, tenants, hit, vid, threshold, band=1e-4):
    """-> (served, exact): the number of exact brute-force hits (the
    best same-tenant row over every live row of both tiers, f32), and
    how many of them the cascade served with a row as good: one whose
    exact score lies within ``band`` of the best, so that a duplicate
    of the best row counts.  Probes whose best row
    lies within ``band`` of the threshold are left out: rounding may
    flip them."""
    from reference.cosine import brute_force, unit
    keys, ten, ids = [], [], []
    for tier in (cache.hot, cache.warm):
        v = np.asarray(tier.valid)
        keys.append(np.asarray(tier.keys)[v])
        ten.append(np.asarray(tier.tenants)[v])
        ids.append(np.asarray(tier.value_ids)[v])
    keys, ten, ids = (np.concatenate(x) for x in (keys, ten, ids))
    best = brute_force(embs, np.asarray(tenants), keys, ten)
    b_hit = best >= threshold + band
    row = {int(v): i for i, v in enumerate(ids)}
    q = unit(embs).astype(np.float32)
    k = unit(keys).astype(np.float32)
    good = [bool(h) and int(v) in row
            and float(q[i] @ k[row[int(v)]]) >= best[i] - band
            for i, (h, v) in enumerate(zip(hit, vid)) if b_hit[i]]
    return int(sum(good)), len(good)
