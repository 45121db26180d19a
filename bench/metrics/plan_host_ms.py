"""plan_host_ms: host time of the ``plan`` span per window request: its
wall time less the ``plan.sync`` spans inside it, the program's waits
for the cascade's verdicts to reach the host (one span per copy)."""


def read(ctx):
    plan, sync = ctx["spans"].get("plan"), ctx["spans"].get("plan.sync")
    if not plan or not sync:
        return None
    return (sum(plan) - sum(sync)) / len(plan) * 1e3
