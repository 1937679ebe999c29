"""Peak device memory in use on the fullest chip, read as the window closes
(``memory_stats()["peak_bytes_in_use"]``), in GiB."""


def read(rec):
    b = rec["memory_peak_bytes"]
    return None if b is None else b / 2 ** 30
