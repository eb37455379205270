"""Device launches (``PlanCache`` counter) per request of the window."""


def read(ctx):
    return ctx.delta("plan_launches_total") / len(ctx.records)
