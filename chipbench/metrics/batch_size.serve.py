"""batch_size.serve: requests completed in the window per batch fired."""


def read(r):
    s = getattr(r, "serve", None)
    if not s or not s["batches"]:
        return None
    return s["completed"] / s["batches"]
