"""Host planning time of the warm-up's cold call, as the program's planner
reports it in the executed plan's stats (``plan_seconds``)."""


def read(ctx):
    s = ctx.plan_stats.get("plan_seconds")
    return None if s is None or s <= 0 else float(s)
