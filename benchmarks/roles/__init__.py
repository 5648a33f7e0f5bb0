"""Worker roles a traffic mix brings as files: `<name>.py` with a class
`Role` (`__init__(spec)`, `handle(cmd)`), run as one process by
benchmarks/client.py (benchmarks/README.md, "Add a cell"). Nothing
here imports `theia_tpu` or jax."""
