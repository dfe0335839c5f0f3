"""ops kernels: device milliseconds per executed query of the traced slice
that the trace gives to the `sort` family — ops whose innermost
named scope, else whose program, is `sort__<tag>`
(`trace_programs.py`; shared kernels such as compaction and the radix
passes count for the operator that called them)."""
import trace_programs


def read(ctx):
    return trace_programs.family_ms_per_query(ctx, "sort")
