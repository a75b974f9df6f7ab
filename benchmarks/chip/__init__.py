"""On-chip benchmark: one cell (a model configuration under a traffic
mix) per run, driven by the data files beside this package.  See
README.md."""
