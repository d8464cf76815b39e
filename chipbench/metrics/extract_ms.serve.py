"""extract_ms.serve: mean host milliseconds of the serving engine's
`extract` span per batch fired in the window."""


def read(r):
    s = getattr(r, "serve", None)
    if not s or not s["batches"]:
        return None
    return s["extract_s"] * 1e3 / s["batches"]
