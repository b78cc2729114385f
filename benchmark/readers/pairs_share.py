"""(Query, key) pairs the indexer selected over pairs the attention kernels
multiplied, in the traced steps (the attention layers' own counters,
`sparse_attn.pairs{kind}`, read by the driver before and after them): what
is left is work a kernel that visited only selected keys would not do.
None where the driver kept no such counters."""


def read(run, spec):
    pairs = run["state"].get("pairs_traced")
    if not pairs or not pairs["computed"]:
        return None
    return 100.0 * pairs["selected"] / pairs["computed"]
