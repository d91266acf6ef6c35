"""Programs compiled inside the window: the server's own counter of cold
compiles after readiness plus the entries the window added to the
persistent compile cache."""

from perfbench.harness import server


def read(run):
    name = "sonata_runtime_cold_compiles_total"
    counted = (server.series_sum(run["metrics_after"], name)
               - server.series_sum(run["metrics_before"], name))
    return float(counted + max(run["cache_entries_added"], 0))
