"""The driver's `memory_peak_bytes` (fullest device: the backend's
`peak_bytes_in_use`, the arrays held, plus its `peak_bytes_reserved`, what the
runtime set aside for the running program's temporaries) over the table's HBM
bytes."""


def read(run, spec):
    peak = run["state"]["memory_peak_bytes"]
    return 100.0 * peak / run["peaks"]["hbm_bytes"] if peak else None
