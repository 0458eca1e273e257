"""One module per entry the measured window drives, named by the traffic
file's ``driver``: each has ``run(ctx) -> harness.Outcome``."""
