"""Peak device memory in use over the planner's Eq. 11 requirement: below
1 the plan left memory unused, above 1 it promised less than it took."""


def read(ctx):
    if not ctx.memory_peak_bytes:
        return None
    return ctx.memory_peak_bytes / ctx.promised_bytes
