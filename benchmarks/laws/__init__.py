"""Key laws a traffic mix brings as files: `<name>.py` with a class
`Stream` of `gen.ProducerStream`'s surface, chosen by the traffic
file's `generator.law` (benchmarks/README.md, "Add a cell"). Nothing
here imports `theia_tpu` or jax."""
