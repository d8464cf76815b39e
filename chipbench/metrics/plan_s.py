"""plan_s: host seconds of `build_gnn`'s planning (advisor, tuner,
partition, backward schedule) in the run's set-up."""


def read(r):
    return r.host.get("plan_s")
