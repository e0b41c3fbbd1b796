"""Peak device memory of the run up to the window's end, in GB (1e9 B):
``peak_bytes_in_use`` of the fullest chip, read before the correctness
check allocates anything."""


def read(r):
    return r.peak_bytes / 1e9
