"""ops kernels: device milliseconds per executed query of the traced slice
under the programs and scopes of an OUTER join — ops whose program or whose
innermost named scope has `outer` in its name (`join__join_outer`, the
LEFT or FULL `hash_join` a probe page; `join__join_prep_outer`, its
build's sort; inside them `join__probe_lookup`, `join__probe_expand`,
`join__outer_fill` — the null-extended rows —, `join__output_gather`, the
build's `join__radix_pass` / `join__radix_gather`; `trace_programs.py`
names an op's owner "<program>/<scope>"). An INNER join's programs keep
their names and are not in it. Nothing where no such op ran."""
import trace_programs


def outer_seconds(table) -> float:
    return sum(s for owner, s in table["by_owner"].items()
               if "outer" in owner)


def read(ctx):
    table = trace_programs.table(ctx)
    n = trace_programs.executed_in_slice(ctx) if table else 0.0
    if not table or n <= 0:
        return None
    seconds = outer_seconds(table)
    return 1e3 * seconds / n if seconds > 0 else None
