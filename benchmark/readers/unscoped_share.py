"""Share of the device's time, over the leaf events of the traced window,
that no scope of the program accounts for: events that join no row of the
program's op table (another program's, or an instruction the table lacks)
and events whose op_name has none of the step's scopes in it.  100 where the
program has a ledger and the backend gave it no op table: loud, never
silent.  None where the program keeps no ledger at all."""
import re

from harness import common

scope_ms = common.load_module("readers", "scope_ms")


def read(run, spec):
    rows = scope_ms.joined(run, spec.get("program", "train_step"))
    if not rows:
        return None
    rx = re.compile(spec.get("scoped", scope_ms.SCOPED))
    total = sum(d for _, _, d in rows)
    unscoped = sum(d for op, _, d in rows if not op or not rx.search(op))
    return 100.0 * unscoped / total
