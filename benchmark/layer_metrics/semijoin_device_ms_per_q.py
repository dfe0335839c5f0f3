"""ops kernels: device milliseconds per executed query of the traced slice
that the trace gives to the semi, anti and mark joins: ops whose program
or whose innermost named scope begins `join__semi` or `join__mark`
(`join__semijoin`, `join__semijoin_prep`, `join__markjoin`;
`join__semi_build`, `join__semi_probe`, `join__mark_probe` —
`trace_programs.py` names an op's owner "<program>/<scope>"). Nothing
where no such op ran."""
import trace_programs

PREFIXES = ("join__semi", "join__mark")


def read(ctx):
    table = trace_programs.table(ctx)
    n = trace_programs.executed_in_slice(ctx) if table else 0.0
    if not table or n <= 0:
        return None
    seconds = [s for owner, s in table["by_owner"].items()
               if any(part.startswith(PREFIXES)
                      for part in owner.split("/"))]
    return 1e3 * sum(seconds) / n if seconds else None
