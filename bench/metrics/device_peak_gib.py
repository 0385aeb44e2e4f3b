"""``peak_bytes_in_use`` of the fullest chip after the window, in GiB."""


def read(run):
    peak = run.device.get("memory_peak_bytes")
    return None if not peak else peak / 2.0 ** 30
