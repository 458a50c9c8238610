"""Driver (the chunked while loop): the share of executed lane-ticks
spent on lanes already frozen at quiescence, pooled over the window's
calls: 1 - sum of lane horizons / (batch x executed ticks), where a
call's executed ticks are its longest lane's horizon."""


def read(ctx):
    calls = ctx["calls"]
    if not calls:
        return None
    useful = sum(int(c.horizons.sum()) for c in calls)
    paid = sum(len(c.horizons) * int(c.horizons.max()) for c in calls)
    return 1.0 - useful / paid
