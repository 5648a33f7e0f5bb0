"""Roofline functions a kernel's metric brings as files: `<name>.py`
with `least(data) -> {"bytes": int, "flops": int}`, named by the
`kernel` key of a `roofline_share` reader file (benchmarks/README.md,
"Add a cell"). Nothing here imports `theia_tpu` or jax."""
