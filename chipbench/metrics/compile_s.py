"""compile_s: host seconds to lower and compile the train step, or to
load it from the persistent compilation cache."""


def read(r):
    return r.host.get("compile_s")
