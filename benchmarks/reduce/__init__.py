"""Reductions a metric brings as files: `<name>.py` with
`reduce(data, p) -> float or None`, named by the `reduce` key of the
metric's reader file (benchmarks/README.md, "Add a cell"). Nothing
here imports `theia_tpu` or jax."""
