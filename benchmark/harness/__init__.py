"""The benchmark's harness: what is general to every cell. One
configuration, one traffic mix, one generator, one kernel and one
per-layer metric each sit in a file of their own beside this package and
are found by the names ``BENCHMARK.json`` gives them."""
