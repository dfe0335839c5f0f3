"""ops kernels: device milliseconds per executed query of the traced slice
under the programs of a join whose key has more than one column — ops whose
program or whose innermost named scope has `composite` in its name
(`join__join_composite`, `join__uprobe_composite`,
`join__join_prep_composite`; inside them `join__probe_lookup` — `search`:
such a key is mix-hashed to 64 bits, so its span fits no table —,
`join__probe_expand`, `join__composite_verify`, `join__output_gather`, the
build's `join__radix_pass` / `join__radix_gather`; `trace_programs.py`
names an op's owner "<program>/<scope>"). Nothing where no such op ran."""
import trace_programs


def composite_seconds(table) -> float:
    return sum(s for owner, s in table["by_owner"].items()
               if "composite" in owner)


def read(ctx):
    table = trace_programs.table(ctx)
    n = trace_programs.executed_in_slice(ctx) if table else 0.0
    if not table or n <= 0:
        return None
    seconds = composite_seconds(table)
    return 1e3 * seconds / n if seconds > 0 else None
