"""Plain references that file-borne checks bring with them: float64
numpy over the generator's own rows, no jax, nothing of `theia_tpu`
(benchmarks/README.md, "Add a cell")."""
