"""Traffic: a copy of the workload generator and the arrival arithmetic,
plus one data file per mix (``<mix>.json``)."""
