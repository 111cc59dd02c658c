"""Peak device memory in GiB on the fullest chip:
``memory_stats()['peak_bytes_in_use']`` after the window."""


def read(run, metric):
    peak = run.device.get('memory_peak_bytes')
    return None if not peak else peak / 2.0 ** 30
