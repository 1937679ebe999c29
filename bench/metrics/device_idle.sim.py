"""Share of the window in which no operation ran on the chip."""


def read(rec):
    tr = rec["trace"]
    if tr is None or not tr["devices"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
