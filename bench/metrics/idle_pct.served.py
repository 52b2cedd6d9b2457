"""Share of the traced window in which no operation ran on the device, in percent:
the same reading as ``idle_pct.master``, in the served cells, where it moves ``jobs_per_s``."""
from bench import harness

read = harness.load_module("metrics", "idle_pct.master").read
