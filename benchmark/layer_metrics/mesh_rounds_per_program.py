"""executor: dispatches of co-scheduled mesh programs per program, over
the window's executed queries (`stats.mesh_program_rounds` /
`stats.mesh_programs`, `exec/mesh_exec.run_co_scheduled`). A program
whose exchange buckets or join outputs overflow is run again with the
capacity doubled, a round per doubling; the converged capacities are
remembered by the program's shape, so in steady state this is 1.0 and
anything above it in the window is a finding."""
import trace_programs


def read(ctx):
    stats = [r["info"]["stats"] for r in trace_programs.executed(ctx)]
    programs = sum(s.get("mesh_programs", 0) for s in stats)
    if not programs:
        return None         # a local runner, or a program without the counter
    return sum(s.get("mesh_program_rounds", 0) for s in stats) / programs
