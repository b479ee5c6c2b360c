"""Peak device memory of the fullest chip after the window, in GiB."""


def read(rc):
    return rc.memory_peak_bytes / 2 ** 30 if rc.memory_peak_bytes else None
