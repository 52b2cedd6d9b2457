"""Device milliseconds per served job under ``repro.gram.input``: the same reading as
``gram_input_ms``, in the served cells, where each task joins and pads A anew and
the metric moves ``jobs_per_s``."""
from bench import harness

read = harness.load_module("metrics", "gram_input_ms").read
