"""peak_hbm_gib: ``peak_bytes_in_use`` of the fullest device after the
window, build included, in GiB."""


def read(run):
    return run.peak_bytes / 2 ** 30 if run.peak_bytes else None
