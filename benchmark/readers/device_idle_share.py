"""1 - union of device-op intervals over the traced window, fullest device."""


def read(run, spec):
    t = run["trace"]
    return 100.0 * (1.0 - t["busy_s_fullest"] / t["window_s"])
