"""The on-chip benchmark of theia-tpu (BENCHMARK.json names this
package as its only path). Nothing here imports `theia_tpu`: the
system under test is a child process reached over HTTP, and traffic,
reference, reduction and the comparison that decides `correct` are
this package's own. README.md says how to run a cell and add one."""
