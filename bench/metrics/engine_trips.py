"""While-loop trips the device ran per engine call, summed over the
stages (each stage's loop runs as many trips as its slowest lane): sum
of `loop_trips` / sum of `engine_calls` over the window's queries (the
program's own counters, set together on every engine call)."""
from bench.readers import stat_mean


def read(run):
    trips = stat_mean(run, "run", "loop_trips")
    calls = stat_mean(run, "run", "engine_calls")
    return None if trips is None or not calls else trips / calls
