"""executor: host milliseconds an executed query spent building LIKE
tables — the boolean table over a column's dictionary that a `LIKE` whose
pattern rides as an operand needs, built once per (dictionary, pattern) a
request (`local_planner._like_tables`): the executed queries' summed
`stats.host_ms["like_table"]` (the activity `like_table`, `host__like_table`
on the device trace's host plane; self time) ÷ their number. The counter
`stats.like_tables_built` counts the builds. None for a program without
the activity, or where no query built one."""
import trace_programs


def read(ctx):
    stats = [r["info"]["stats"] for r in trace_programs.executed(ctx)]
    if not any("like_table" in s.get("host_ms", {}) for s in stats):
        return None
    return sum(s.get("host_ms", {}).get("like_table", 0.0)
               for s in stats) / len(stats)
