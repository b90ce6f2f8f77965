"""Jobs carried across a cut, per cut (the program's own `carried_jobs`
and `pages` counters of `vectorsim._run_paged`): sum of `carried_jobs`
over sum of `pages - 1` (a paged query of one task has one cut fewer
than pages)."""
from bench.readers import stat_mean


def read(run):
    carried = stat_mean(run, "run", "carried_jobs")
    pages = stat_mean(run, "run", "pages")
    if carried is None or pages is None or pages <= 1:
        return None
    return carried / (pages - 1)
