"""padded_slots_per_edge: the forward plan's slots (tiles x groups per
tile x group size) over its real edges."""


def read(r):
    p = getattr(r, "plan", None)
    if not p or not p["edges"]:
        return None
    return p["tiles"] * p["gpt"] * p["gs"] / p["edges"]
