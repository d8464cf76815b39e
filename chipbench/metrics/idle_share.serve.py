"""idle_share.serve: the share of the traced window in which no
operation ran on the chip (1 - union of device op intervals / window)."""


def read(r):
    win = getattr(r, "window", None)
    if win is None or win["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - win["busy_s"] / win["window_s"])
