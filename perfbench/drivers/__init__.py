"""One module per kind of job.  A driver exposes `run(ctx) -> dict` (see
perfbench.run.Context and perfbench.run.finish for what goes in and out)."""
