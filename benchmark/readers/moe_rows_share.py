"""Rows routed to held experts over rows the grouped product was handed, in
the traced steps (the expert layers' own counters, `moe.rows{kind}`, read
by the driver before and after them): what is left is padding of the static
row bound (whole chunks).  None where the driver kept no such counters."""


def read(run, spec):
    moe = run["state"].get("moe_traced")
    if not moe or not moe["computed"]:
        return None
    return 100.0 * moe["routed"] / moe["computed"]
