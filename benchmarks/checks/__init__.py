"""Checks a cell brings as files: `<name>.py` with `check(ctx, rep)`,
optionally `limits` (the tolerances it reads from the traffic file's
`limits`) and `control(traffic, seed, n_blocks, precision)`
(benchmarks/README.md, "Add a cell"). Nothing here imports `theia_tpu`
or jax."""
