"""exchange: device milliseconds per executed query of the traced slice
that the trace gives to the `exchange` family, the mean over the cell's
chips — ops whose innermost named scope is `exchange__<tag>`
(`parallel/exchange.py`: `exchange__partition`, `exchange__all_to_all`,
`exchange__broadcast`, `exchange__compact`, ...), else whose program is
`exchange__mesh_prog` and which carry no scope of their own. Counted as
the other family metrics count (`trace_programs.family_ms_per_query`)."""
import trace_programs


def read(ctx):
    return trace_programs.family_ms_per_query(ctx, "exchange")
