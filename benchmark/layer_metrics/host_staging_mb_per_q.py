"""serve caches: host-to-device megabytes a scan really moved, per
executed query (`stats.scan_host_staging_bytes`, counted where
`Column.from_numpy` puts host data on the device under a page source):
0 when the tables are generated on or cached on the device
(`stats.scan_staging_bytes` counts those pages too, so it is not read)."""
import trace_programs


def read(ctx):
    staged = [r["info"]["stats"]["scan_host_staging_bytes"]
              for r in trace_programs.executed(ctx)
              if "scan_host_staging_bytes" in r["info"]["stats"]]
    return sum(staged) / len(staged) / 1e6 if staged else None
