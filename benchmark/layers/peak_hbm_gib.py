"""Peak device memory of the fullest chip after the window, train cells."""


def read(run):
    if run["kind"] != "train" or not run["peak_bytes"]:
        return None
    return run["peak_bytes"] / 2 ** 30
