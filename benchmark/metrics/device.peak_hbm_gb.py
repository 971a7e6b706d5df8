"""Peak of device memory on the fullest chip, buffers plus what loaded programs reserve for their temporaries
(``peak_bytes_in_use`` + ``peak_bytes_reserved``), read when the window has closed and before the reference runs."""


def read(run):
    return None if run.peak_bytes is None else run.peak_bytes / 1e9
