"""Device milliseconds per served job under ``repro.gram.sketch_params``: the same
reading as ``sketch_params_ms``, in the served cells, where it moves ``jobs_per_s``."""
from bench import harness

read = harness.load_module("metrics", "sketch_params_ms").read
