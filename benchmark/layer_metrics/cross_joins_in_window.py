"""frontend: CROSS joins the window's executed queries ran, the sum of
`stats.cross_joins` — a JoinNode with no equality clause reaching the
executor (`local_planner._exec_cross_join`). The planner reorders a
connected join graph without one at any scale factor
(`optimizer.reorder_joins`), so anything but 0 here is a finding: Q9 at
SF10 planned supplier x part, 4.3e9 rows, until PR 42. None for a program
without the counter."""
import trace_programs


def read(ctx):
    stats = [r["info"]["stats"] for r in trace_programs.executed(ctx)
             if "cross_joins" in r["info"]["stats"]]
    return sum(s["cross_joins"] for s in stats) if stats else None
