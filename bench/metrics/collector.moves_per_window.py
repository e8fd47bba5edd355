"""KV blocks the collector migrated per collect, both directions: the
mean of moved_to_hot + moved_to_cold over the program's collect reports
(`Server.reports`) of the window's jobs."""


def read(r):
    reps = [x for j in r.jobs for x in j.reports]
    if not reps:
        return None
    return sum(x["moved_to_hot"] + x["moved_to_cold"] for x in reps) \
        / len(reps)
