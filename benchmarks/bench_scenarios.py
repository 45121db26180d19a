"""Trace-driven scenario macro-bench for the tiered cache service
(DESIGN.md §14.1).

Replays the seeded ``benchmarks/scenarios.py`` traces through a real
``CacheService`` built from a ``CacheConfig``, under a **logical
clock** (``StalenessConfig.clock`` reads the trace's arrival times),
and scores each scenario on:

  * SLO-style latency — p50/p99 of per-batch ``plan()`` wall time,
    µs per row, with the first batch of every distinct batch *size*
    excluded (that batch pays the jit trace; production pays it once
    at warmup, not per request);
  * false-hit budget — served hits whose response belongs to another
    answer group (including every adversarial ``must_miss`` row),
    per scenario and per tenant;
  * staleness — ANY hit served after the row's answer group passed
    its TTL deadline is a stale serve; hard-asserted **zero**.

The ``drift`` trace runs twice for the §14.3 conformal contrast:
once with the fixed per-tenant *learned* threshold (calibrated on
phase-1 pairs — it must LEAK once the negative band drifts above it)
and once with conformal hit calibration on (the recency-window floor
must pull the false-hit rate back under the scenario budget).  Both
outcomes are hard asserts: the bench fails if the learned arm stops
leaking (the scenario lost its teeth) or the conformal arm leaks.

Every replay audits each served hit against trace ground truth and
feeds the verdict to ``FeedbackLoop.observe_hit_audit`` — the §14.3
channel that de-censors the score stream above the threshold.

Results append to ``results/BENCH_scenarios.json`` (override path
with ``BENCH_SCENARIOS_JSON``; set it empty to skip writing).
``results/make_tables.py scenarios`` renders the table;
``scripts/check_bench_trajectory.py`` gates regressions per scenario.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from scenarios import SCENARIOS, ScenarioTrace, build  # noqa: E402

from repro.cache_service import (  # noqa: E402
    CacheConfig, CacheRequest, CacheService, LearningConfig,
    StalenessConfig, TieringConfig,
)
from repro.cache_service.feedback import FeedbackConfig  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

# hard-assert ledger: every claim this bench certifies lands in
# "checked"; anything environment-skipped lands in "skipped" with the
# reason.  check_bench_trajectory.py cross-checks the owed names.
_ASSERTS = {"checked": [], "skipped": []}

OWED_ASSERTS = (
    "scenario_zero_stale_serves",
    "scenario_false_hit_budgets",
    "drift_learned_threshold_leaks",
    "drift_conformal_holds_budget",
    "adversarial_must_miss_budget",
    "ttl_expiry_enforced",
    "ttl_prewindow_hits",
)


def _assert_checked(name, cond, msg=""):
    assert cond, f"[{name}] {msg}"
    if name not in _ASSERTS["checked"]:
        _ASSERTS["checked"].append(name)


# per-scenario tier sizing: ttl_churn deliberately squeezes the hot
# tier so live-but-doomed entries demote through warm (and capture
# into cold) while their deadline runs — expiry must hold in every
# tier, not just where the row was born.
def _tiering(name: str) -> TieringConfig:
    if name == "ttl_churn":
        return TieringConfig(hot_capacity=32, warm_capacity=512,
                             n_clusters=8, bucket=64, n_probe=8,
                             cold_capacity=1024)
    return TieringConfig(hot_capacity=2048, warm_capacity=4096,
                         n_clusters=8, bucket=256, n_probe=8)


def _service(trace: ScenarioTrace, clock, *, conformal: bool):
    cfg = CacheConfig(
        dim=trace.dim,
        threshold=trace.threshold,
        tiering=_tiering(trace.name),
        learning=LearningConfig(
            conformal=conformal,
            # a small split so the floor activates off calibration-scale
            # traffic; the window/alpha defaults are the serving ones
            feedback=FeedbackConfig(conformal_min=16)),
        staleness=StalenessConfig(clock=lambda: clock["t"]),
    )
    svc = CacheService(cfg)
    for tenant, (scores, labels) in trace.calibration.items():
        budget = float(trace.meta.get("max_false_hit_rate", 0.02))
        svc.calibrate_tenant(tenant, scores, labels,
                             max_false_hit_rate=budget)
    return svc


def replay(trace: ScenarioTrace, *, conformal: bool, audit: bool = True):
    """Run one trace through a fresh service; returns the scored row."""
    clock = {"t": 0.0}
    svc = _service(trace, clock, conformal=conformal)
    answer = {}                      # gid committed at least once
    deadline = {}                    # gid -> latest live TTL deadline
    n_q = hits = false_hits = stale = 0
    per_tenant = {}                  # tenant -> [queries, false_hits]
    timed, compile_sizes = [], set()
    expired_masked = ttl_stamped = expired_reaped = 0
    prewin_hits = prewin_total = 0   # ttl_churn inside-deadline repeats

    for step in trace.steps:
        clock["t"] = float(step.t)
        B = len(step.tenants)
        req = CacheRequest.build(step.embs, step.tenants, ttl=step.ttl)
        t0 = time.perf_counter()
        plan = svc.plan(req, coalesce=False)
        np.asarray(plan.hit)         # force any async dispatch home
        dt = time.perf_counter() - t0
        if B in compile_sizes:
            timed.append(dt / B * 1e6)
        else:
            compile_sizes.add(B)     # first sight of this shape: jit
        expired_masked += plan.expired_masked

        responses = [None] * B
        for i in range(B):
            gid = int(step.group[i])
            tn = int(step.tenants[i])
            own = f"ans-g{gid}"
            n_q += 1
            pt = per_tenant.setdefault(tn, [0, 0])
            pt[0] += 1
            if plan.hit[i]:
                hits += 1
                served = plan.responses[i]
                is_dup = served == own
                if is_dup:
                    if deadline.get(gid, np.inf) < step.t:
                        stale += 1
                else:
                    false_hits += 1
                    pt[1] += 1
                if audit and conformal:
                    svc.feedback.observe_hit_audit(
                        tn, float(plan.scores[i]), is_dup)
                # pre-deadline repeats in ttl_churn must keep hitting
                if (trace.name == "ttl_churn" and step.ttl is None
                        and not step.must_miss[i]):
                    prewin_hits += 1
            else:
                responses[i] = own
            if (trace.name == "ttl_churn" and step.ttl is None
                    and not step.must_miss[i]):
                prewin_total += 1

        receipt = svc.commit(plan, responses)
        ttl_stamped += receipt.ttl_stamped
        admitted = np.asarray(plan.admit, bool) & ~np.asarray(
            plan.hit, bool)
        ttl_col = (np.asarray(step.ttl, np.float32)
                   if step.ttl is not None else None)
        for i in np.flatnonzero(admitted):
            gid = int(step.group[i])
            answer[gid] = True
            if ttl_col is None or not np.isfinite(ttl_col[i]):
                deadline[gid] = np.inf
            else:
                deadline[gid] = max(deadline.get(gid, -np.inf),
                                    float(step.t) + float(ttl_col[i]))
        report = svc.maintenance()
        expired_reaped += report.expired_reaped

    timed_a = np.asarray(timed) if timed else np.asarray([0.0])
    row = {
        "scenario": trace.name,
        "mode": "conformal" if conformal else "learned",
        "seed": trace.seed,
        "dim": trace.dim,
        "n_steps": len(trace.steps),
        "n_queries": n_q,
        "hits": hits,
        "hit_rate": hits / max(n_q, 1),
        "false_hits": false_hits,
        "false_hit_rate": false_hits / max(n_q, 1),
        "false_hit_budget": trace.false_hit_budget,
        "stale_serves": stale,
        "p50_us_per_row": float(np.percentile(timed_a, 50)),
        "p99_us_per_row": float(np.percentile(timed_a, 99)),
        "timed_batches": len(timed),
        "ttl_stamped": ttl_stamped,
        "expired_masked": expired_masked,
        "expired_reaped": expired_reaped,
        "per_tenant_false_hit_rate": {
            str(t): (fh / q if q else 0.0)
            for t, (q, fh) in sorted(per_tenant.items())},
        "per_tenant_queries": {str(t): q for t, (q, _)
                               in sorted(per_tenant.items())},
    }
    if trace.name == "ttl_churn":
        row["prewindow_hit_rate"] = prewin_hits / max(prewin_total, 1)
    if conformal:
        cs = svc.feedback.conformal_state()
        row["conformal_floors"] = {
            str(t): v["floor"] for t, v in cs["tenants"].items()
            if v["floor"] is not None}
        row["hit_audits"] = cs["hit_audits"]
        row["audited_false_hits"] = cs["audited_false_hits"]
    return row


def _check_budget(row, min_tenant_q):
    """Per-scenario AND per-tenant false-hit budget."""
    b = row["false_hit_budget"]
    assert row["false_hit_rate"] <= b, (
        f"{row['scenario']}: false-hit rate {row['false_hit_rate']:.4f} "
        f"over budget {b}")
    for t, r in row["per_tenant_false_hit_rate"].items():
        if row["per_tenant_queries"][t] >= min_tenant_q:
            assert r <= b, (f"{row['scenario']} tenant {t}: per-tenant "
                            f"false-hit rate {r:.4f} over budget {b}")


def bench_scenarios(names=None, seed=0, dim=64, smoke=False):
    """Yields one scored row per (scenario, mode) replay."""
    _ASSERTS["checked"].clear()
    _ASSERTS["skipped"].clear()
    names = list(names or SCENARIOS)
    min_tenant_q = 20 if smoke else 100
    rows = []
    for name in names:
        trace = build(name, seed=seed, dim=dim, smoke=smoke)
        if name == "drift":
            # the §14.3 contrast: same trace, fixed learned threshold
            # vs conformal floor.  The leak is part of the spec.
            fixed = replay(trace, conformal=False)
            _assert_checked(
                "drift_learned_threshold_leaks",
                fixed["false_hit_rate"] > trace.false_hit_budget,
                f"calibrated-but-fixed threshold no longer leaks under "
                f"drift ({fixed['false_hit_rate']:.4f} <= "
                f"{trace.false_hit_budget}); the scenario lost its "
                f"teeth — retune the distractor band")
            rows.append(fixed)
            yield fixed
            conf = replay(trace, conformal=True)
            _assert_checked(
                "drift_conformal_holds_budget",
                conf["false_hit_rate"] <= trace.false_hit_budget,
                f"conformal floor leaked {conf['false_hit_rate']:.4f} > "
                f"budget {trace.false_hit_budget}")
            _check_budget(conf, min_tenant_q)
            rows.append(conf)
            yield conf
            continue
        row = replay(trace, conformal=True)
        _check_budget(row, min_tenant_q)
        _ASSERTS["checked"].append("scenario_false_hit_budgets") \
            if "scenario_false_hit_budgets" not in _ASSERTS["checked"] \
            else None
        if name == "adversarial":
            _assert_checked(
                "adversarial_must_miss_budget",
                row["false_hit_rate"] <= trace.false_hit_budget,
                f"near-duplicate paraphrases leaked "
                f"{row['false_hit_rate']:.4f}")
        if name == "ttl_churn":
            _assert_checked(
                "ttl_expiry_enforced",
                row["stale_serves"] == 0 and row["ttl_stamped"] > 0
                and row["expired_masked"] > 0
                and row["expired_reaped"] > 0,
                f"TTL machinery not engaged: stamped="
                f"{row['ttl_stamped']} masked={row['expired_masked']} "
                f"reaped={row['expired_reaped']} "
                f"stale={row['stale_serves']}")
            _assert_checked(
                "ttl_prewindow_hits",
                row["prewindow_hit_rate"] >= 0.9,
                f"inside-deadline repeats only hit at "
                f"{row['prewindow_hit_rate']:.3f}")
        rows.append(row)
        yield row
    _assert_checked(
        "scenario_zero_stale_serves",
        all(r["stale_serves"] == 0 for r in rows),
        "stale serve(s) slipped through plan-time expiry masking: "
        + json.dumps({r["scenario"]: r["stale_serves"]
                      for r in rows if r["stale_serves"]}))
    _assert_checked(
        "scenario_false_hit_budgets",
        all(r["false_hit_rate"] <= r["false_hit_budget"]
            for r in rows if r["mode"] == "conformal"),
        "a conformal-mode scenario is over its false-hit budget")


def _json_path():
    env = os.environ.get("BENCH_SCENARIOS_JSON")
    if env is not None:
        return Path(env) if env else None
    return Path(__file__).resolve().parent.parent \
        / "results" / "BENCH_scenarios.json"


def main(argv=None):
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="short traces (CI-sized); same asserts")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--scenario", action="append", default=None,
                    choices=sorted(SCENARIOS),
                    help="run only these (repeatable); default: all")
    args = ap.parse_args(argv)
    if args.scenario:
        owed = {"scenario_zero_stale_serves",
                "scenario_false_hit_budgets"}
        if "drift" in args.scenario:
            owed |= {"drift_learned_threshold_leaks",
                     "drift_conformal_holds_budget"}
        if "adversarial" in args.scenario:
            owed.add("adversarial_must_miss_budget")
        if "ttl_churn" in args.scenario:
            owed |= {"ttl_expiry_enforced", "ttl_prewindow_hits"}
        for name in sorted(set(OWED_ASSERTS) - owed):
            _ASSERTS["skipped"].append(
                {"name": name, "reason":
                 "scenario subset via --scenario"})
    rows = []
    import jax
    for row in bench_scenarios(args.scenario, seed=args.seed,
                               dim=args.dim, smoke=args.smoke):
        rows.append(row)
        print(f"  {row['scenario']:>14s}/{row['mode']:<9s} "
              f"q={row['n_queries']:>5d} hit={row['hit_rate']:.3f} "
              f"false={row['false_hit_rate']:.4f}"
              f"(<={row['false_hit_budget']}) "
              f"stale={row['stale_serves']} "
              f"p99={row['p99_us_per_row']:.0f}us/row")
    # --scenario subsets skip cross-scenario asserts recorded above;
    # a full run must come out owing nothing
    if not args.scenario:
        missing = set(OWED_ASSERTS) - set(_ASSERTS["checked"])
        assert not missing, f"owed asserts never ran: {sorted(missing)}"
    payload = {
        "bench": "scenarios",
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "smoke": bool(args.smoke),
        "seed": args.seed,
        "dim": args.dim,
        "checked_asserts": list(_ASSERTS["checked"]),
        "skipped_asserts": list(_ASSERTS["skipped"]),
        "rows": rows,
    }
    path = _json_path()
    if path is not None:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(json.dumps(payload, indent=1, sort_keys=True)
                        + "\n")
        print(f"wrote {path} ({len(rows)} rows)")
    else:
        print("BENCH_SCENARIOS_JSON empty — not writing results")


if __name__ == "__main__":
    main()
