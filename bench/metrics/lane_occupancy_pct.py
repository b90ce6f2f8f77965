"""Share of the engine's lockstep while-loop lane slots that a lane
needed, over the window's queries, in %: 100 * sum of `lane_trips` /
sum of `lane_slots` (the program's own counters). Every engine call
sets both, so the ratio of their means per query is that of their
sums."""
from bench.readers import stat_mean


def read(run):
    lane = stat_mean(run, "run", "lane_trips")
    slots = stat_mean(run, "run", "lane_slots")
    return None if lane is None or not slots else 100.0 * lane / slots
