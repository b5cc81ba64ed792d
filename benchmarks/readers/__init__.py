"""Readers: one module per source (`client`, `stats`, `xplane`). A reader is
a function `fn(ctx, **params) -> float | None`; `ctx` is the run's collected
data (`run.py: RunContext`). A reader that finds nothing to read returns
None, and the harness leaves that metric out of the line."""
